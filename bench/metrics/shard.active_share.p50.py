"""Share of the fan-out's chip-rounds in which a shard still searched
rather than waited at the merge for its launch's slowest shard: the
shards' rounds over the shard count times the slowest shard's rounds, by
``BatchReport`` (%). Nothing where the program has no such counter."""


def read(run):
    c = run.counters
    if not c.get("fanout_rounds") or "rounds" not in c:
        return None
    return 100.0 * c["rounds"] / c["fanout_rounds"]
