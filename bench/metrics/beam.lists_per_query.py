"""Adjacency lists fetched per query answered, from ``BatchReport``."""
from bench.readers import lists_fetched


def read(run):
    lists, nq = lists_fetched(run), run.counters.get("n_queries")
    return lists / nq if lists is not None and nq else None
