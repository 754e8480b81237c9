"""Exp#8 (Fig. 11): tailored vs general-purpose compression — written to
``BENCH_compression.json`` (in the ``run.py`` harness).

(a) Auxiliary index vs R: Elias-Fano vs Huffman vs zlib (stand-in for the
    ZSTD family) on sorted adjacency lists — per-record compression
    preserving random access, as the paper requires.
(b) Vector data: Huffman vs XOR-delta+Huffman vs per-plane Huffman vs
    zlib-128KiB (the paper's point: block compressors win ratio but break
    per-vector random access).
(c) The codec registry's own estimates for the same data — the planner
    decision table (``codec.registry.plan_components``) cross-checked
    against the measured sizes above.

Env: REPRO_BENCH_COMPRESSION_OUT overrides the JSON path.
"""
import json
import os
import time
import zlib

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.codec import elias_fano as ef, huffman, xor_delta
from repro.core.codec import registry as codecs
from repro.core.graph.vamana import build_vamana

from .common import csv, dataset, world


def index_compression(r_sweep=(16, 24, 48)):
    out = {}
    vecs = dataset("sift-like").astype(np.float32)[:2000]
    for r in r_sweep:
        graph = build_vamana(vecs, r=r, l_build=max(48, r + 8), seed=0)
        raw = ef_b = huf_b = z_b = 0
        table = None
        # per-record compression (random-access preserving)
        all_bytes = []
        for adj in graph.adjacency:
            a = np.sort(adj).astype(np.uint64)
            raw += 4 * (len(a) + 1)
            ef_b += len(ef.encode_record(a, len(vecs)))
            b = a.astype(np.uint32).tobytes()
            all_bytes.append(np.frombuffer(b, np.uint8))
            z_b += len(zlib.compress(b, 6))
        cat = np.concatenate(all_bytes)
        table = huffman.HuffmanTable.from_data(cat)
        huf_b = sum(-(-huffman.encoded_size_bits(x, table) // 8)
                    for x in all_bytes)
        out[r] = dict(raw=raw, ef=ef_b, huffman=huf_b, zlib=z_b)
    return out


def vector_compression():
    out = {}
    for kind in ("sift-like", "prop-like"):
        data = dataset(kind)
        vb = xor_delta.as_bytes(data)
        raw = vb.size
        # Huffman per record
        t = huffman.HuffmanTable.from_data(vb)
        huf = huffman.encode_records(vb, t)[0].size
        # XOR-delta + Huffman (chunk-level base)
        use, base = xor_delta.delta_wins(vb)
        delta = xor_delta.apply_delta(vb, base) if use else vb
        t2 = huffman.HuffmanTable.from_data(delta)
        dh = huffman.encode_records(delta, t2)[0].size
        # Per-plane Huffman (one table per byte plane — fp32 columnar win)
        tp = huffman.PlaneTables.from_data(vb, data.dtype.itemsize)
        ph = huffman.encode_records(vb, tp)[0].size
        # zlib on 128 KiB blocks (ratio-optimal, random access lost)
        zb = sum(len(zlib.compress(vb[i:i + 2048].tobytes(), 6))
                 for i in range(0, len(vb), 2048))
        out[kind] = dict(raw=raw, huffman=huf, delta_huffman=dh,
                         plane_huffman=ph, zlib=zb, delta_used=use)
    return out


def planner_decisions():
    """The registry's decision table on each dataset's vector bytes +
    adjacency sample (cross-check against the measured sizes above)."""
    out = {}
    for kind in ("sift-like", "prop-like"):
        w = world(kind)
        rng = np.random.default_rng(5)
        sel = rng.choice(len(w["vecs"]), size=512, replace=False)
        manifest = codecs.plan_components(
            dict(adjacency=[np.sort(np.asarray(w["graph"].adjacency[int(i)],
                                               np.int64)) for i in sel],
                 vector_chunks=[np.ascontiguousarray(w["vecs"][int(i)])
                                .view(np.uint8) for i in sel]),
            universe=len(w["vecs"]), itemsize=w["vecs"].dtype.itemsize)
        out[kind] = manifest.to_json()
    return out


def main(quiet=False):
    t0 = time.time()
    ix = index_compression()
    for r, d in ix.items():
        csv(f"exp8/index_R{r}", 0.0,
            f"raw={d['raw']};ef={d['ef']};huffman={d['huffman']};"
            f"zlib={d['zlib']};"
            f"ef_saving={100*(1-d['ef']/d['raw']):.1f}%;"
            f"huf_saving={100*(1-d['huffman']/d['raw']):.1f}%")
    vc = vector_compression()
    us = (time.time() - t0) * 1e6
    for kind, d in vc.items():
        csv(f"exp8/vector_{kind}", us,
            f"raw={d['raw']};huffman={d['huffman']};"
            f"delta_huffman={d['delta_huffman']};"
            f"plane_huffman={d['plane_huffman']};zlib128k={d['zlib']};"
            f"delta_used={d['delta_used']};"
            f"dvs_saving={100*(1-d['delta_huffman']/d['raw']):.1f}%;"
            f"plane_saving={100*(1-d['plane_huffman']/d['raw']):.1f}%;"
            f"zlib_saving={100*(1-d['zlib']/d['raw']):.1f}%")
    doc = dict(
        index_vs_r={str(r): d for r, d in ix.items()},
        vector=vc,
        planner=planner_decisions(),
        note=("index_vs_r / vector are measured encoded sizes (bytes); "
              "planner is the registry decision table "
              "(plan_components manifests, candidates = estimated bytes "
              "per codec) on a 512-record sample of the same data."))
    path = os.environ.get("REPRO_BENCH_COMPRESSION_OUT",
                          "BENCH_compression.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    if not quiet:
        print(f"# wrote {path}")
    return ix, vc


if __name__ == "__main__":
    enable_compile_cache()
    main()
