"""Sweep the offered rate of an open-loop cell on the chip, once, to find
its knee: the highest rate at which the backlog does not grow over the
window and p99 latency holds the stated limit.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s>
        --rates <qps> [<qps> ...]

One process builds the cell's world and warms it up once, then drives the
cell's mix at each rate in turn. One JSON line per rate: queries due and
answered, p50/p90/p99 latency from the due time, the queue still waiting when
the window closed, the p90 and p99 of the last quarter of queries against
the first, the longest call, and how late the generator woke. The benchmark's own runs never
run this; the rate it finds is written into the cell's mix file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def sweep_point(world, mix: dict, rate: float, seconds: float,
                seed: int) -> dict:
    from bench import harness, traffic
    mix = dict(mix, rate_qps=rate)
    win = traffic.open_loop(harness.Served(world.searcher), world.pool, mix,
                            seconds, seed)
    due = np.concatenate([c.due for c in win.calls])
    start = np.concatenate([np.full(len(c.rows), c.start)
                            for c in win.calls])
    lat = win.latencies() * 1e3
    q = len(lat) // 4
    late = np.asarray(win.lateness or [0.0]) * 1e3
    return {"rate_qps": rate, "due": win.attempted, "answered": len(lat),
            "unanswered": win.unanswered,
            "p50_ms": float(np.percentile(lat, 50)),
            "p90_ms": float(np.percentile(lat, 90)),
            "p99_ms": float(np.percentile(lat, 99)),
            "queue_at_close": int(((due < seconds)
                                   & (start >= seconds)).sum()),
            "p99_first_quarter_ms": float(np.percentile(lat[:q], 99)),
            "p99_last_quarter_ms": float(np.percentile(lat[-q:], 99)),
            "p90_first_quarter_ms": float(np.percentile(lat[:q], 90)),
            "p90_last_quarter_ms": float(np.percentile(lat[-q:], 90)),
            "mean_batch": float(np.mean([len(c.rows) for c in win.calls])),
            "longest_call_ms": 1e3 * max(c.end - c.start for c in win.calls),
            "late_p99_ms": float(np.percentile(late, 99))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    from bench import harness, spec
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = spec.load_cell(args.workload, ROOT)
    t0 = time.perf_counter()
    world = harness.build_world(cell.config, args.seed)
    harness.warm_up(world.searcher, world.pool, cell.mix)
    harness.settle()
    harness.log(f"sweep: {cell.name} set up in "
                f"{time.perf_counter() - t0:.1f} s")
    for i, rate in enumerate(args.rates):
        print(json.dumps(sweep_point(world, cell.mix, rate, args.seconds,
                                     args.seed + i)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
