"""A ShardedIndex placed one shard per device is served by BatchedSearcher as
one shard_map program per bucket: the same answers, counters and modeled
latencies as the serial shard loop, and exact against brute force.

Run in a subprocess with 4 XLA host devices forced BEFORE jax import (the
test process keeps its single device); the subprocess reports plain
numbers and flags, the tests below assert on them."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import numpy as np
    from repro.core.distributed.sharded_index import (
        build_router, build_sharded_index, place_on_mesh)
    from repro.core.search.beam import DeviceIndex, SearchParams, search
    from repro.serve.ann import BatchedSearcher, ServeConfig

    S, R, K = 4, 12, 5
    rng = np.random.default_rng(7)
    # Integer data: every squared L2 is an exact float32.
    vecs = rng.integers(0, 64, (S * 150, 24)).astype(np.float32)
    queries = rng.integers(0, 64, (70, 24)).astype(np.float32)
    index, per = build_sharded_index(vecs, S, r=R, l_build=24, pq_m=4,
                                     seed=3)
    mesh = jax.make_mesh((S,), ("data",))
    placed = place_on_mesh(index, mesh)
    p = SearchParams(l_size=24, beam_width=4, k=K, rerank_batch=5,
                     r_max=R, universe=per, max_iters=32)
    SIZES = [1, 5, 8, 9, 17, 32, 45, 70]    # buckets 1, 8, 32, ragged

    def ints(rep):
        return {k: v for k, v in vars(rep).items()
                if isinstance(v, int) and not isinstance(v, bool)}

    def compare(cfg, router=None, **kw):
        loop = BatchedSearcher(index, p, cfg, router=router)
        mesh_s = BatchedSearcher(placed, p, cfg, router=router)
        out = {"loop_path": loop._placed is None,
               "placed_path": mesh_s._placed is not None,
               "ids": True, "dists": True, "counters": True,
               "latency": True, "diff": {}}
        for n in SIZES:
            il, dl, rl = loop.search(queries[:n], **kw)
            ip, dp, rp = mesh_s.search(queries[:n], **kw)
            out["ids"] &= bool(np.array_equal(il, ip)) and il.dtype == ip.dtype
            out["dists"] &= bool(np.array_equal(dl, dp)) and dl.dtype == dp.dtype
            a, b = ints(rl), ints(rp)
            out["counters"] &= a == b
            out["diff"].update({f"{n}:{k}": [a[k], b[k]] for k in a
                                if a[k] != b[k]})
            out["latency"] &= (rl.per_query_latency_us
                               == rp.per_query_latency_us)
        return out

    result = {
        "plain": compare(ServeConfig()),
        "failed": compare(ServeConfig(), failed_shards=[1]),
        "routed": compare(ServeConfig(route_frac=0.5),
                          router=build_router(index, seed=0)),
    }

    # One launch and one fetch per bucket on the placed path.
    served = BatchedSearcher(placed, p)
    idx, run, rep = served._placed
    launches = []
    served._placed = (idx, lambda *a: launches.append(1) or run(*a), rep)
    fetches = []
    get = jax.device_get
    jax.device_get = lambda x: fetches.append(1) or get(x)
    ids, dists, report = served.search(queries[:45])
    jax.device_get = get
    result["launches"] = [len(launches), len(fetches), len(report.buckets)]

    # The plain reference: float64 NumPy exact top-K over every row.
    d2 = ((queries[:, None, :].astype(np.float64)
           - vecs[None].astype(np.float64)) ** 2).sum(-1)
    exact = np.sort(d2, 1)[:, :K]
    ids, dists, _ = BatchedSearcher(placed, p).search(queries)
    got = np.take_along_axis(d2, ids, 1)
    result["reference"] = {
        "dists_exact": bool(np.array_equal(got, dists.astype(np.float64))),
        "recall": float((got <= exact[:, -1:]).sum() / got.size)}

    # Round counters: one launch of 32 rows.
    rep = BatchedSearcher(placed, p).search(queries[:32])[2]
    per_shard = []
    for i in range(S):
        shard = DeviceIndex(*(np.asarray(x[i]) for x in index[:7]))
        per_shard.append(int(np.asarray(search(shard, queries[:32],
                                               p)[2].iters).max()))
    one = BatchedSearcher(DeviceIndex(*(np.asarray(x[0])
                                        for x in index[:7])), p)
    r1 = one.search(queries[:45])[2]
    result["rounds"] = {"rounds": rep.rounds, "fanout": rep.fanout_rounds,
                        "per_shard": per_shard, "one_rounds": r1.rounds,
                        "one_fanout": r1.fanout_rounds}

    # Layouts that keep the loop: the stacked index on one device, and
    # four shards placed over a one-device mesh.
    one_mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    result["loop_kept"] = [
        BatchedSearcher(index, p)._placed is None,
        BatchedSearcher(place_on_mesh(index, one_mesh), p)._placed is None]
    print("RESULT::" + json.dumps(result))
""")


@pytest.fixture(scope="module")
def served():
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=600,
                          env={"PYTHONPATH": str(REPO / "src"),
                               "PATH": "/usr/bin:/bin",
                               "JAX_PLATFORMS": "cpu"},
                          cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")]
    assert line, proc.stdout[-2000:]
    return json.loads(line[0][len("RESULT::"):])


@pytest.mark.parametrize("case", ["plain", "failed", "routed"])
def test_placed_program_equals_serial_loop(served, case):
    """Ids, distances, every int BatchReport counter and the per-query
    modeled latencies, exactly, over buckets 1/8/32 and ragged batches;
    with a failed shard and with routing to half the shards too."""
    got = served[case]
    assert got["loop_path"] and got["placed_path"]
    assert got["ids"] and got["dists"]
    assert got["counters"], got["diff"]
    assert got["latency"]


def test_one_launch_and_one_fetch_per_bucket(served):
    launches, fetches, buckets = served["launches"]
    assert launches == fetches == buckets == 3      # 45 rows: 32 + 8 + 5->8


def test_placed_path_against_exact_reference(served):
    ref = served["reference"]
    assert ref["dists_exact"]
    assert ref["recall"] >= 0.9, ref


def test_fanout_rounds(served):
    r = served["rounds"]
    assert r["rounds"] == sum(r["per_shard"])
    assert r["fanout"] == 4 * max(r["per_shard"])
    assert r["one_fanout"] == r["one_rounds"] > 0


def test_one_device_layouts_keep_the_serial_loop(served):
    assert served["loop_kept"] == [True, True]
