"""Reduction of a profiler trace to device busy and idle time, per-kernel
device time, and idle gaps by what the host was doing.

The trace is read with ``jax.profiler.ProfileData`` into plain ``Event``
records, and every number below is computed from those records alone, so
the reduction can be checked on a constructed trace without a chip:

- device events: the ``XLA Ops`` line of each ``/device:<KIND>:<i>``
  plane. An op that contains others on its line (a ``while`` around its
  body) is a container; busy time is the union of the leaf ops.
- host spans: the benchmark's own ``bench.*`` annotations, on whatever
  host thread ran them. ``bench.window`` bounds the traced window.

Host and device events share the trace's clock (ns from its start).
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:(?!CUSTOM)[A-Z]+:\d+$")
_HLO = re.compile(r"^%?(?P<name>[\w.-]+) = (?P<shape>.*?) (?P<op>[\w-]+)\(")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # plane name -> [Event]
    host: list = field(default_factory=list)      # bench.* spans


@dataclass
class Summary:
    window_s: float
    busy_s: float                   # mean over devices
    kernel_s: dict                  # kernel -> device s (mean over devices)
    kernel_calls: dict              # kernel -> events (mean over devices)
    device_ops: list                # [[op label, s]], longest first
    idle_gaps: list                 # [[host span, s]], longest first


def read(path: Path) -> Trace:
    """The events this reduction uses, from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = Trace()
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            out.devices[plane.name] = [
                Event(e.name, e.start_ns, e.duration_ns)
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events]
        elif plane.name.startswith("/host:"):
            out.host += [Event(e.name, e.start_ns, e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name.startswith("bench.")]
    return out


def leaves(events: list) -> list:
    """Events of one line that contain no other event of that line. On a
    line, two events are either nested or disjoint."""
    ev = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    return [e for e, nxt in zip(ev, ev[1:] + [None])
            if nxt is None or nxt.start_ns >= e.end_ns]


def union(intervals: list) -> list:
    """Merge [start, end] intervals into disjoint ones, ascending."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_label(name: str) -> str:
    """A short label of an HLO op event: its instruction name, its output
    shape without layouts, and its opcode."""
    m = _HLO.match(name)
    if not m:
        return name[:100]
    shape = re.sub(r"\{[^}]*\}", "", m["shape"])
    return f"{m['name']} = {shape} {m['op']}"[:100]


def is_kernel(name: str, kernel: str) -> bool:
    """Whether an op event is a launch of ``kernel``: a custom call whose
    instruction name is the kernel's name, with any suffix."""
    m = _HLO.match(name)
    if not m or m["op"] != "custom-call":
        return False
    base = re.sub(r"\.\d+$", "", m["name"])
    return base == kernel or base.startswith(kernel + "_")


def summarize(trace: Trace, kernels: list) -> Summary | None:
    """The window's numbers; None where the trace has no device plane (the
    CPU backend runs its ops on host threads)."""
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    if not trace.devices:
        return None
    w0 = min(e.start_ns for e in windows)
    w1 = max(e.end_ns for e in windows)
    n_dev = len(trace.devices)
    busy = 0.0
    kernel_s = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    ops, gaps, seen = {}, {}, {}
    host = HostSpans([e for e in trace.host if e.name != WINDOW_SPAN])
    for events in trace.devices.values():
        inside = [e for e in leaves(events)
                  if e.end_ns > w0 and e.start_ns < w1]
        merged = union([[max(e.start_ns, w0), min(e.end_ns, w1)]
                        for e in inside])
        busy += sum(e - s for s, e in merged)
        for e in inside:
            if e.name not in seen:
                seen[e.name] = (op_label(e.name),
                                [k for k in kernels if is_kernel(e.name, k)])
            label, of = seen[e.name]
            ops[label] = ops.get(label, 0.0) + e.dur_ns
            for k in of:
                kernel_s[k] += e.dur_ns
                kernel_calls[k] += 1
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                who = host.activity(s, e)
                gaps[who] = gaps.get(who, 0.0) + (e - s)
    top = lambda d: [[k, v / n_dev / 1e9]
                     for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                     ][:10]
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy / n_dev / 1e9,
                   kernel_s={k: v / n_dev / 1e9 for k, v in kernel_s.items()},
                   kernel_calls={k: v / n_dev
                                 for k, v in kernel_calls.items()},
                   device_ops=top(ops), idle_gaps=top(gaps))


class HostSpans:
    """The host's spans, for asking what the host was doing in an
    interval."""

    def __init__(self, spans: list):
        self.spans = sorted(spans, key=lambda e: e.start_ns)
        self.starts = [e.start_ns for e in self.spans]
        self.longest = max((e.dur_ns for e in spans), default=0.0)

    def activity(self, s: float, e: float) -> str:
        """The span that covers most of [s, e], the innermost (shortest)
        among equals; ``idle`` where none does."""
        best, best_cover, best_len = "idle", 0.0, float("inf")
        i = bisect.bisect_left(self.starts, e)
        while i > 0 and self.starts[i - 1] > s - self.longest:
            i -= 1
            sp = self.spans[i]
            cover = min(e, sp.end_ns) - max(s, sp.start_ns)
            if cover > best_cover or (cover == best_cover and cover > 0
                                      and sp.dur_ns < best_len):
                best, best_cover, best_len = sp.name, cover, sp.dur_ns
        return best


class Tracer:
    """The profiler over one window, into a directory of the caller's."""

    def __init__(self, directory: Path):
        self.dir = Path(directory)

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans only, no Python calls
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self) -> Path:
        import jax
        jax.profiler.stop_trace()
        files = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no trace written under {self.dir}")
        return files[-1]
