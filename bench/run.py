"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything is
generated from ``--seed``. The run refuses to start unless JAX's first
device is a TPU and there are as many as the cell asks for: there is no
CPU fallback. ``--trace 0`` measures the cell's end-to-end metrics with
the profiler off; ``--trace 1`` traces a short window and reports the
per-layer metrics instead.

Log lines go to stderr, the compared numbers beside their limits last.
The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), and
``checks`` last.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec
    cell = spec.load_cell(args.workload, ROOT)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform} device(s); refusing "
              f"to run elsewhere", file=sys.stderr)
        return 3
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    harness.log(f"bench: {cell.name} seed {args.seed} on "
                f"{devices[0].device_kind} x{len(devices)}, jax "
                f"{jax.__version__}, compile cache {cache}")
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
