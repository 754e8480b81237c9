"""Shared by the benchmark's tests: the repository's root on the path, so
that they import the harness as the ``bench`` package the way
``bench/run.py`` does, and the ``tiny_cell`` fixture. Test modules import
this first (a ``conftest.py`` here would shadow the suite's own)."""
import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# Each cell cut to a size the CPU runs in seconds; widths that set the
# kernels' shapes (dim, PQ m, K, W) stay as configured.
TINY = {
    "sift-vamana-2k.open": dict(rows=256, query_pool=200, graph_degree=16,
                                build_list=24),
    "bigann-share-1m.closed32": dict(rows=1024, query_pool=200,
                                     graph_degree=32, search_list=32),
}


@pytest.fixture
def tiny_cell():
    """``tiny_cell(name)`` -> the cell of BENCHMARK.json at CPU size, with
    an open mix slowed to what the CPU serves."""
    from bench import spec

    def make(name):
        cell = spec.load_cell(name)
        mix = dict(cell.mix)
        if mix["loop"] == "open":
            mix["rate_qps"] = 40
        return dataclasses.replace(
            cell, config=dict(cell.config, **TINY[name]), mix=mix)
    return make
