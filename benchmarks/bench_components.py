"""Exp#1 (Fig. 5): contribution of each DecoupleVS component.

Six configurations on one dataset at matched recall target:
DiskANN / PipeANN / Decouple / DecoupleComp / DecoupleSearch / DecoupleVS.
Reported in the paper's normalization (relative to DiskANN) using the I/O
latency model (engine.py) — hardware-free units.
"""
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.index import recall_at_k
from repro.core.search.engine import (EngineConfig, search_colocated,
                                      search_decoupled)

from .common import csv, reset_io, world

CONFIGS = [
    ("diskann", dict(colocated=True, pipelined=False)),
    ("pipeann", dict(colocated=True, pipelined=True)),
    ("decouple", dict(ix="raw_ix", latency_aware=False, compressed=False)),
    ("decouple_comp", dict(ix="comp_ix", latency_aware=False, compressed=True)),
    ("decouple_search", dict(ix="raw_ix", latency_aware=True, compressed=False)),
    ("decouplevs", dict(ix="comp_ix", latency_aware=True, compressed=True)),
]


def run_config(w, name, spec, l_size=64):
    reset_io(w)
    ids_all, stats = [], []
    for q in w["queries"]:
        if spec.get("colocated"):
            cfg = EngineConfig(l_size=l_size, pipelined=spec["pipelined"])
            ids, st = search_colocated(w["colo"], w["codes"], w["cb"], q, cfg)
        else:
            cfg = EngineConfig(l_size=l_size,
                               latency_aware=spec["latency_aware"],
                               compressed=spec["compressed"])
            ids, st = search_decoupled(w[spec["ix"]], w["vs"] if
                                       spec["compressed"] else w["vs_raw"],
                                       w["codes"], w["cb"], q, cfg)
        ids_all.append(np.pad(ids, (0, 10 - len(ids)), constant_values=-1))
        stats.append(st)
    lat = float(np.mean([s.latency_us for s in stats]))
    rec = recall_at_k(np.stack(ids_all), w["gt"], 10)
    return dict(latency_us=lat, qps=1e6 / lat, recall=rec,
                graph_ios=float(np.mean([s.graph_ios for s in stats])),
                vector_ios=float(np.mean([s.vector_ios for s in stats])),
                cache_hits=float(np.mean([s.cache_hits for s in stats])))


def main(quiet=False):
    w = world("sift-like")
    base = None
    out = {}
    for name, spec in CONFIGS:
        t0 = time.time()
        r = run_config(w, name, spec)
        us = (time.time() - t0) * 1e6 / len(w["queries"])
        if base is None:
            base = r
        csv(f"exp1/{name}", us,
            f"qps_rel_diskann={r['qps']/base['qps']:.2f};"
            f"latency_us={r['latency_us']:.0f};recall={r['recall']:.3f};"
            f"graph_ios={r['graph_ios']:.1f};vector_ios={r['vector_ios']:.1f};"
            f"cache_hits={r['cache_hits']:.1f}")
        out[name] = r
    return out


if __name__ == "__main__":
    enable_compile_cache()
    main()
