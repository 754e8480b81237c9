"""The four-chip cell over the BIGANN deployment's open loop, on the CPU at
a tiny size with the Pallas kernels in interpret mode:
``bigann-share-4x.open`` in a subprocess with four XLA host devices forced
before jax is imported. Each run is correct and reports exactly its listed
metrics; one shard left out of the merge is not correct."""
import json
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from bench_testing import ROOT
from bench import spec

# Cut as bench_testing.TINY cuts bigann-share-1m.closed32.
TINY = dict(rows=1024, query_pool=200, graph_degree=32, search_list=32)


def check_run(out: dict, want: set) -> None:
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["dist_err"] == {"value": 0.0, "limit": 0.0}
    assert set(out["metrics"]) == want
    assert all(m["value"] >= 0 for m in out["metrics"].values())


FOUR = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, json, sys, time
    sys.path[:0] = [{root!r}, {src!r}]
    from bench import harness, spec
    from repro.kernels.dispatch import KernelConfig
    from repro.serve.ann import BatchedSearcher

    def main():
        cell = spec.load_cell("bigann-share-4x.open")
        out = {{}}
        kernels = KernelConfig(*["pallas"] * 5).resolve()
        for traced in (False, True):
            c = dataclasses.replace(cell, config=dict(cell.config, **{tiny!r}),
                                    mix=dict(cell.mix, rate_qps=40))
            out[str(traced)] = harness.run_cell(
                c, 2**31 + 13, 1.0, traced, time.perf_counter(),
                kernels=kernels)
        # The planted fault: shard 1 is left out of every merge, at 1,024
        # rows a shard (at 256 a few of a query's ten nearest sit in each
        # shard, and a quarter of them is too near the limit to show).
        search = BatchedSearcher.search
        BatchedSearcher.search = (lambda self, q, *a, **kw:
                                  search(self, q, failed_shards=[1]))
        c = dataclasses.replace(
            cell, config=dict(cell.config, **dict({tiny!r}, rows=4096)),
            mix=dict(cell.mix, rate_qps=150))
        out["fault"] = harness.run_cell(c, 77, 1.0, False,
                                        time.perf_counter())
        out["listed"] = {{str(t): sorted(m["name"] for m in
                         (cell.per_layer if t else cell.end_to_end)
                         if not (t and m["source"] == "device_trace"))
                         for t in (False, True)}}
        print("RESULT::" + json.dumps(out))

    if __name__ == "__main__":
        main()
""")


@pytest.fixture(scope="module")
def four_chip_runs(tmp_path_factory):
    script = tmp_path_factory.mktemp("four") / "four.py"
    script.write_text(FOUR.format(root=str(ROOT), src=str(ROOT / "src"),
                                  tiny=TINY))
    proc = subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=600,
                          env={"PATH": "/usr/bin:/bin",
                               "JAX_PLATFORMS": "cpu",
                               "HOME": str(script.parent)},
                          cwd=str(ROOT))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")]
    assert line, proc.stdout[-2000:]
    return json.loads(line[0][len("RESULT::"):])


@pytest.mark.parametrize("traced", [False, True])
def test_rehearsal_4x_open(four_chip_runs, traced):
    want = set(four_chip_runs["listed"][str(traced)])
    want -= {"hbm_bytes_per_vector"}
    assert "shard.active_share.p50" in want or not traced
    check_run(four_chip_runs[str(traced)], want)


def test_one_shard_left_out_is_not_correct(four_chip_runs):
    out = four_chip_runs["fault"]
    assert out["correct"] is False
    rm = out["checks"]["recall_miss"]
    assert rm["value"] > rm["limit"]
    assert out["checks"]["dist_err"]["value"] == 0.0


@pytest.mark.parametrize("counters,want", [
    ({"rounds": 300, "fanout_rounds": 400}, 75.0),
    ({"rounds": 120, "fanout_rounds": 120}, 100.0),
    ({"rounds": 5, "fanout_rounds": 0}, None),
    ({"rounds": 5}, None),
])
def test_active_share_reader(counters, want):
    read = spec.reader("shard.active_share.p50")
    assert read(SimpleNamespace(counters=counters)) == want
