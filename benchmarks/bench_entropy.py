"""Table 1: dataset compressibility characterization.

Verifies the paper's two orderings on our statistically-matched generators:
dimensional dispersion < global dispersion, columnar entropy < global
entropy — the structure the XOR-delta + Huffman pipeline exploits.
"""
import time

from repro.compile_cache import enable_compile_cache
from repro.core.codec.entropy import characterize

from .common import csv, dataset


def main(quiet=False):
    rows = []
    for kind, paper in (("sift-like", dict(gd=36.2, ge=2.63, ce=1.73)),
                        ("spacev-like", dict(gd=12.2, ge=5.59, ce=5.46)),
                        ("prop-like", dict(gd=0.09, ge=4.39, ce=2.86))):
        t0 = time.time()
        stats = characterize(dataset(kind))
        us = (time.time() - t0) * 1e6
        ok = (stats["dimensional_dispersion"] <= stats["global_dispersion"]
              and stats["columnar_entropy"] <= stats["global_entropy"])
        csv(f"table1/{kind}", us,
            f"gdisp={stats['global_dispersion']:.3g};"
            f"ddisp={stats['dimensional_dispersion']:.3g};"
            f"gent={stats['global_entropy']:.3f};"
            f"cent={stats['columnar_entropy']:.3f};"
            f"orderings_hold={ok};paper_gent={paper['ge']}")
        rows.append((kind, stats, ok))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
