"""What a cell is made of, found by name from ``BENCHMARK.json``.

Nothing here lists configurations, mixes or metrics: a cell's
configuration is the file its ``configs`` entry names, its traffic is
``bench/traffic/<traffic>.json``, and each metric is read by
``bench/metrics/<metric name>.py``. Adding any of them is adding files and
entries.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    mix: dict               # the traffic file's contents
    end_to_end: list        # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _listed(metric: dict, cell: str, default: bool) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else default


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    w = _by_name(bench["workloads"], name, "workload")
    entry = _by_name(bench["configs"], w["config"], "configuration")
    with open(root / entry["file"]) as f:
        config = json.load(f)
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        mix = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _listed(m, name, True)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _listed(m, name, m["moves"] in moved)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer)


def load_module(path: Path):
    """Import one file as a module, whatever characters its name holds."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric_name: str):
    """The ``read(run) -> float | None`` of a metric."""
    return load_module(BENCH_DIR / "metrics" / f"{metric_name}.py").read


def world_maker(name: str):
    """The ``build(config, base, seed, kernels)`` of a world kind."""
    return load_module(BENCH_DIR / "worlds" / f"{name}.py").build


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind. A kind that the table
    does not hold is an error, not a default."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]
