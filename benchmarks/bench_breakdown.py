"""Exp#6 (Table 3) + Fig. 2: per-query resource breakdown.

I/O: graph cache hits, graph block I/Os, vector block I/Os. CPU: PQ ops,
decompressions, exact re-rank ops — with the engine's documented time
constants, giving the paper's CPU-vs-I/O-wait decomposition.
"""
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.search.engine import (EngineConfig, T_DEC, T_EX, T_IO, T_PQ,
                                      search_colocated, search_decoupled)

from .common import csv, reset_io, world


def main(quiet=False):
    w = world("sift-like")
    out = {}
    for name in ("diskann", "pipeann", "decouplevs"):
        reset_io(w)
        t0 = time.time()
        stats = []
        for q in w["queries"]:
            if name in ("diskann", "pipeann"):
                cfg = EngineConfig(l_size=96, pipelined=name == "pipeann")
                _, st = search_colocated(w["colo"], w["codes"], w["cb"], q,
                                         cfg)
            else:
                cfg = EngineConfig(l_size=96, latency_aware=True,
                                   compressed=True)
                _, st = search_decoupled(w["comp_ix"], w["vs"], w["codes"],
                                         w["cb"], q, cfg)
            stats.append(st)
        us = (time.time() - t0) * 1e6 / len(stats)
        mean = lambda f: float(np.mean([f(s) for s in stats]))
        io_time = mean(lambda s: s.io_rounds) * T_IO
        cpu_time = mean(lambda s: s.pq_ops * T_PQ + s.exact_ops * T_EX +
                        s.decompressions * T_DEC)
        decomp = mean(lambda s: s.decompressions * T_DEC)
        csv(f"exp6/{name}", us,
            f"cache_hits={mean(lambda s: s.cache_hits):.1f};"
            f"graph_ios={mean(lambda s: s.graph_ios):.1f};"
            f"vector_ios={mean(lambda s: s.vector_ios):.1f};"
            f"io_time_us={io_time:.0f};cpu_time_us={cpu_time:.1f};"
            f"decompress_us={decomp:.2f};"
            f"decompress_frac={decomp/max(cpu_time + io_time, 1e-9)*100:.2f}%;"
            f"io_wait_frac={io_time/max(cpu_time+io_time,1e-9)*100:.1f}%")
        out[name] = dict(io=io_time, cpu=cpu_time, decomp=decomp)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    main()
