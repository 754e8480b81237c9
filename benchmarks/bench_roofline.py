"""Roofline summary from the multi-pod dry-run artifacts (§Roofline).

Reads results/dryrun/*.json (produced by repro.launch.dryrun) and emits one
CSV row per (arch x shape) cell with the three terms, dominant bottleneck,
and useful-FLOPs ratio. Run the dry-run sweep first.
"""
from repro.compile_cache import enable_compile_cache
from repro.launch.summarize import load_cells

from .common import csv


def main(quiet=False):
    cells = load_cells("pod16x16")
    if not cells:
        csv("roofline/missing", 0.0, "run repro.launch.dryrun --all first")
        return
    for c in cells:
        name = f"roofline/{c['arch']}__{c['shape']}"
        if c.get("skipped"):
            csv(name, 0.0, f"SKIP:{c['why_skipped'][:60]}")
            continue
        r = c.get("roofline") or c.get("full_program")
        csv(name, r.get("step_time_s", max(r["compute_s"], r["memory_s"],
                                           r["collective_s"])) * 1e6,
            f"dominant={r['dominant']};compute_s={r['compute_s']:.3g};"
            f"memory_s={r['memory_s']:.3g};"
            f"collective_s={r['collective_s']:.3g};"
            f"peak_gib={c.get('memory', {}).get('peak_gib', 0):.1f};"
            f"model_flops_ratio={r.get('model_flops_ratio', 0):.2f};"
            f"roofline_frac={r.get('roofline_fraction', 0):.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
