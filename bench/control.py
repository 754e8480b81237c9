"""The control of the comparison that decides ``correct``: the plain
reference put in the program's place and computed in bfloat16, the
precision below the float32 re-rank that the configurations state.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it draws the queries a run of the cell answers (the same
pool, draws and count as a run of ``run_seconds``), answers each with an
exact brute-force top-K computed in bfloat16 on the default device, and
holds those answers to the same comparison as the program's. Each line of
stdout is one seed's compared numbers with the cell's limits and whether
the comparison passed; the control is expected to fail it. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def bf16_topk(base: np.ndarray, queries: np.ndarray, k: int,
              block: int = 1 << 16) -> tuple:
    """Brute-force top-k by squared L2 with every operand and every result
    in bfloat16 -> (ids [m, k] int64, dists [m, k] float32)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(q, b, off, best_d, best_i):
        qq = jnp.sum(q * q, axis=1, dtype=jnp.bfloat16)
        bb = jnp.sum(b * b, axis=1, dtype=jnp.bfloat16)
        cross = jnp.dot(q, b.T, preferred_element_type=jnp.bfloat16)
        d = qq[:, None] + bb[None, :] - jnp.bfloat16(2) * cross
        ids = off + jnp.arange(b.shape[0], dtype=jnp.int32)
        d = jnp.concatenate([best_d, d], 1)
        i = jnp.concatenate([best_i, jnp.broadcast_to(ids, d.shape[:1]
                                                      + ids.shape)], 1)
        neg, pos = jax.lax.top_k(-d, k)
        return -neg, jnp.take_along_axis(i, pos, 1)

    q = jnp.asarray(queries, jnp.bfloat16)
    best_d = jnp.full((len(queries), k), jnp.inf, jnp.bfloat16)
    best_i = jnp.full((len(queries), k), -1, jnp.int32)
    n = len(base)
    for s in range(0, n, block):
        b = base[s:s + block]
        if len(b) < block and n > block:    # one shape for every block
            b = np.concatenate([b, np.full((block - len(b),) + b.shape[1:],
                                           1e4, b.dtype)])
        best_d, best_i = step(q, jnp.asarray(b, jnp.bfloat16), s, best_d,
                              best_i)
    ids = np.asarray(best_i).astype(np.int64)
    return np.where(ids < n, ids, -1), np.asarray(best_d, np.float32)


def control_answers(cell, seed: int, seconds: float, answers: int):
    """(base, pool, window) where the window holds the control's answers to
    the queries a run of the cell draws."""
    from bench import data, traffic
    cfg, mix = cell.config, cell.mix
    base = data.make_vectors(cfg["data"], cfg["rows"], cfg["dim"], seed)
    pool = data.make_queries(cfg["data"], cfg["query_pool"], cfg["dim"],
                             seed).astype(np.float32)
    if mix["loop"] == "open":
        count = len(traffic.open_schedule(mix, seconds, seed))
    else:
        count = answers
    rows = traffic.query_draws(len(pool), count, seed)
    ids, dists = bf16_topk(base.astype(np.float32), pool[rows], cfg["k"])
    win = traffic.Window(calls=[traffic.Call(0.0, 0.0, rows,
                                             np.zeros(len(rows)), ids,
                                             dists)])
    return base, pool, win


def control_check(cell, seed: int, seconds: float, answers: int) -> dict:
    from bench import reference
    base, pool, win = control_answers(cell, seed, seconds, answers)
    got = reference.compare(base, pool, win, cell.config)
    limits = {k: v for k, v in cell.config["limits"].items()
              if v is not None}
    return {"seed": seed, "answers": got["answers"],
            "numbers": {k: got[k] for k in ("bad_answers", "dist_err",
                                             "recall_miss")},
            "limits": limits,
            "passed": all(got[k] <= v for k, v in limits.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--answers", type=int, default=4096,
                    help="answers a closed-loop run compares")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec
    bench = spec.load_benchmark(ROOT)
    cell = spec.load_cell(args.workload, ROOT)
    for seed in args.seeds:
        print(json.dumps(control_check(cell, seed, bench["run_seconds"],
                                       args.answers)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
