"""The program's own spans and scopes in a profiler trace, beside what
``bench/trace.py`` reduces: host self time by span, leaf device time by
named scope, and idle gaps named by the span the host was innermost in.

``harness.run_cell`` reduces its traces with ``trace.py`` alone; this
module is the step that also reads the program's spans and scopes.
``summarize`` returns ``trace.summarize``'s numbers for the same trace,
unchanged, with three more fields (``ProgramSummary``):

- ``span_s``: each host span name's self time inside the window: its time
  less what its child spans cover (spans of one thread nest);
- ``span_calls``: how many spans of each name the window holds;
- ``scope_s``: leaf device time by the program's innermost named scope,
  averaged over devices like ``kernel_s``.

``idle_gaps`` names each gap by the span whose own time, less its child
spans, covers most of it, the innermost among equals. Where no span nests
in another, as with the benchmark's ``bench.*`` spans alone, that is
``trace.py``'s rule and gives its numbers.

Host spans read are the benchmark's ``bench.*`` and the program's
``serve.*`` (``repro/serve/ann.py``). A scope is a ``<layer>.<phase>``
name of ``jax.named_scope`` (``beam.lut``, ``beam.traverse``,
``beam.rerank`` in ``repro/core/search/beam.py``): the innermost one in
an instruction's HLO ``op_name``. A TPU trace's ops carry no ``op_name``,
so ``scopes_from_hlo`` maps op labels to scopes from the compiled
programs' text, and an op with none of its own (the compiler's own loops,
such as a scatter lowered to a ``while``) takes the scope of the op that
contains it on its line.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field, replace
from pathlib import Path

from bench import trace as tracing

HOST_PREFIXES = ("bench.", "serve.")
_SCOPE = re.compile(r"/([a-z][a-z0-9_]*\.[a-z][a-z0-9_]*)(?=/|$)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclass(frozen=True)
class ScopedEvent(tracing.Event):
    scope: str | None = None        # device op: innermost program scope


@dataclass
class ProgramSummary(tracing.Summary):
    span_s: dict = field(default_factory=dict)      # span -> self s
    span_calls: dict = field(default_factory=dict)  # span -> spans
    scope_s: dict = field(default_factory=dict)     # scope -> device s


def scopes_from_hlo(texts: list) -> dict:
    """Op label (``trace.op_label``) -> scope, over the text of compiled
    HLO modules (``jit(f).lower(...).compile().as_text()``), whose
    instructions carry ``op_name`` metadata. A label without a scope, or
    that two modules give different scopes, maps to None."""
    out = {}
    for text in texts:
        for line in text.splitlines():
            line = line.strip()
            if tracing._HLO.match(line):
                m = _OP_NAME.search(line)
                found = _SCOPE.findall(m[1]) if m else []
                scope = found[-1] if found else None
                label = tracing.op_label(line)
                out[label] = scope if out.get(label, scope) == scope \
                    else None
    return out


def inherit_scopes(events: list) -> list:
    """The events of one line, each op without a scope of its own given
    that of the innermost op containing it (two events of a line are
    either nested or disjoint)."""
    out, open_ = [], []             # open_: (end, scope) of containers
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while open_ and open_[-1][0] <= e.start_ns:
            open_.pop()
        if e.scope is None and open_:
            e = replace(e, scope=open_[-1][1])
        out.append(e)
        open_.append((e.end_ns, e.scope))
    return out


def read(path: Path, scopes: dict | None = None) -> tracing.Trace:
    """``trace.read`` with the program's spans and each op's scope from
    ``scopes`` (``scopes_from_hlo``)."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(path)), scopes)


def from_profile(data, scopes: dict | None = None) -> tracing.Trace:
    out = tracing.Trace()
    scopes = scopes or {}
    for plane in data.planes:
        if tracing._DEVICE_PLANE.match(plane.name):
            out.devices[plane.name] = inherit_scopes([
                ScopedEvent(e.name, e.start_ns, e.duration_ns,
                            scopes.get(tracing.op_label(e.name)))
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events])
        elif plane.name.startswith("/host:"):
            out.host += [tracing.Event(e.name, e.start_ns, e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name.startswith(HOST_PREFIXES)]
    return out


class NestedSpans(tracing.HostSpans):
    """Host spans that nest, for asking which one the host was innermost
    in over an interval."""

    def __init__(self, spans: list):
        # A parent comes before the children that start with it.
        super().__init__(sorted(spans, key=lambda e: (e.start_ns,
                                                      -e.dur_ns)))
        self.parent, stack = [], []
        for i, sp in enumerate(self.spans):
            while stack and self.spans[stack[-1]].end_ns < sp.end_ns:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def activity(self, s: float, e: float) -> str:
        """The span whose own time (less its child spans) covers most of
        [s, e], the innermost (shortest) among equals; ``idle`` where none
        does."""
        cover = {}
        i = bisect.bisect_left(self.starts, e)
        while i > 0 and self.starts[i - 1] > s - self.longest:
            i -= 1
            sp = self.spans[i]
            cover[i] = min(e, sp.end_ns) - max(s, sp.start_ns)
        own = dict(cover)
        for i, c in cover.items():
            if c > 0 and self.parent[i] in own:
                own[self.parent[i]] -= c
        best, best_cover, best_len = "idle", 0.0, float("inf")
        for i, c in own.items():        # latest start first, as trace.py
            sp = self.spans[i]
            if c > best_cover or (c == best_cover and c > 0
                                  and sp.dur_ns < best_len):
                best, best_cover, best_len = sp.name, c, sp.dur_ns
        return best

    def self_s(self, w0: float, w1: float) -> dict:
        """Each span name's time inside [w0, w1] less its child spans' (s)."""
        inside = [max(0.0, min(sp.end_ns, w1) - max(sp.start_ns, w0))
                  for sp in self.spans]
        own = list(inside)
        for i, p in enumerate(self.parent):
            if p >= 0:
                own[p] -= inside[i]
        out = {}
        for sp, t in zip(self.spans, own):
            out[sp.name] = out.get(sp.name, 0.0) + t / 1e9
        return out

    def calls(self, w0: float, w1: float) -> dict:
        """Spans of each name that overlap [w0, w1]."""
        out = {}
        for sp in self.spans:
            if sp.end_ns > w0 and sp.start_ns < w1:
                out[sp.name] = out.get(sp.name, 0) + 1
        return out


def summarize(trace: tracing.Trace, kernels: list) -> ProgramSummary | None:
    """``trace.summarize``'s numbers, the program's spans and scopes, and
    idle gaps by the innermost span; None where the trace has no device
    plane."""
    windows = [e for e in trace.host if e.name == tracing.WINDOW_SPAN]
    base = tracing.summarize(tracing.Trace(trace.devices, windows), kernels)
    if base is None:
        return None
    w0 = min(e.start_ns for e in windows)
    w1 = max(e.end_ns for e in windows)
    n_dev = len(trace.devices)
    host = NestedSpans([e for e in trace.host
                        if e.name != tracing.WINDOW_SPAN])
    gaps, scope_s = {}, {}
    for events in trace.devices.values():
        inside = [e for e in tracing.leaves(events)
                  if e.end_ns > w0 and e.start_ns < w1]
        merged = tracing.union([[max(e.start_ns, w0), min(e.end_ns, w1)]
                                for e in inside])
        for e in inside:
            scope = getattr(e, "scope", None)
            if scope:
                scope_s[scope] = scope_s.get(scope, 0.0) + e.dur_ns
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                who = host.activity(s, e)
                gaps[who] = gaps.get(who, 0.0) + (e - s)
    base.idle_gaps = [[k, v / n_dev / 1e9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])][:10]
    return ProgramSummary(**vars(base), span_s=host.self_s(w0, w1),
                          span_calls=host.calls(w0, w1),
                          scope_s={k: v / n_dev / 1e9
                                   for k, v in scope_s.items()})
