"""The reduction of the program's own spans and scopes
(``bench/program_trace.py``), on constructed traces with hand-computed
answers, and on one trace of the served path recorded on the CPU."""
from types import SimpleNamespace

import numpy as np
import pytest

import bench_testing  # noqa: F401
from bench import program_trace as pt
from bench import spec
from bench import trace as tr

E, S = tr.Event, pt.ScopedEvent
BEAM = ("%beam_step_pallas.4 = (s32[32,1,256]{2,1,0:T(1,128)S(1)}, "
        "f32[32,1,256]{2,1,0}) custom-call(u8[32,32,512]{2,1,0} %a), "
        "custom_call_target=\"tpu_custom_call\"")
EF = ("%ef_decode_pallas.4 = (s32[128,128]{1,0:T(8,128)S(1)}, "
      "s32[128,1]{1,0}) custom-call(s32[128,37]{1,0} %w)")
WHILE = ("%while.6 = (s32[32,200]{1,0:T(8,128)}, f32[32]{0}) "
         "while(%tuple.1), body=%b")
FUSION = ("%fusion.85 = s32[16384]{0:T(1024)S(1)} "
          "fusion(s32[32,32768]{1,0} %g)")
KERNELS = ["beam_step", "ef_decode", "rerank_l2"]


def calls_trace():
    """Window 0..200 ns: two calls, each a bench.search around a
    serve.search with its five parts; bench.generate between them. The
    device runs 14..56 and 114..148."""
    host = [E("bench.window", 0, 200),
            E("bench.search", 0, 100), E("serve.search", 4, 92),
            E("serve.plan", 4, 4), E("serve.launch", 8, 12),
            E("serve.fetch", 20, 40), E("serve.account", 60, 30),
            E("serve.merge", 90, 4),
            E("bench.generate", 100, 4),
            E("bench.search", 104, 96), E("serve.search", 106, 90),
            E("serve.plan", 106, 2), E("serve.launch", 108, 8),
            E("serve.fetch", 116, 34), E("serve.account", 150, 40),
            E("serve.merge", 190, 6)]
    dev = [S(FUSION, 14, 42, "beam.traverse"),
           S(FUSION, 114, 34, "beam.traverse")]
    return tr.Trace(devices={"/device:TPU:0": dev}, host=host)


def test_gaps_go_to_the_innermost_program_span():
    s = pt.summarize(calls_trace(), KERNELS)
    # 0..14: bench.search's own 0..4, serve.plan 4..8, serve.launch 8..14
    # 56..114: fetch 4, account 30, merge 4, serve.search's own 2,
    #   bench.search's own 4 + 2, generate 4, plan 2, launch 6
    # 148..200: fetch 2, account 40, merge 6, bench.search's own 4
    assert dict(s.idle_gaps) == pytest.approx(
        {"serve.launch": 14e-9, "serve.account": 110e-9})
    # trace.py's rule gives each of these gaps to the span that covers
    # most of it, however much of that its children cover
    old = tr.summarize(calls_trace(), KERNELS)
    assert dict(old.idle_gaps) == pytest.approx({"bench.search": 124e-9})
    assert s.busy_s == old.busy_s == pytest.approx(76e-9)


def test_span_self_time_and_calls_by_hand():
    s = pt.summarize(calls_trace(), KERNELS)
    assert s.span_s == pytest.approx({
        "bench.search": (100 - 92 + 96 - 90) * 1e-9,
        "serve.search": (92 - 90) * 1e-9,       # 94..96 of the first
        "serve.plan": 6e-9, "serve.launch": 20e-9, "serve.fetch": 74e-9,
        "serve.account": 70e-9, "serve.merge": 10e-9,
        "bench.generate": 4e-9})
    assert sum(s.span_s.values()) == pytest.approx(s.window_s)
    assert s.span_calls == {"bench.search": 2, "serve.search": 2,
                            "serve.plan": 2, "serve.launch": 2,
                            "serve.fetch": 2, "serve.account": 2,
                            "serve.merge": 2, "bench.generate": 1}
    assert s.scope_s == pytest.approx({"beam.traverse": 76e-9})


def flat_trace():
    """test_bench_trace's constructed trace: only the benchmark's spans."""
    dev = [E(WHILE, 10, 60), E(BEAM, 10, 20), E(EF, 30, 10),
           E(FUSION, 50, 20), E(FUSION, 80, 10), E(FUSION, 120, 5)]
    host = [E("bench.window", 0, 100), E("bench.search", 5, 70),
            E("bench.wait", 75, 20)]
    return tr.Trace(devices={"/device:TPU:0": dev,
                             "/device:TPU:1": [E(FUSION, 0, 100)]},
                    host=host)


def test_without_program_spans_the_numbers_are_trace_pys():
    old = tr.summarize(flat_trace(), KERNELS)
    new = pt.summarize(flat_trace(), KERNELS)
    assert vars(new) == dict(vars(old), span_s=new.span_s,
                             span_calls=new.span_calls, scope_s={})
    assert new.span_s == pytest.approx({"bench.search": 70e-9,
                                        "bench.wait": 20e-9})
    # the metrics read from the trace come out the same
    base = dict(cell=SimpleNamespace(config={
                    "pq_m": 32, "search_list": 200, "beam_width": 4,
                    "pq_centroids": 256, "graph_degree": 128}),
                counters={"graph_ios": 300, "cache_hits": 100,
                          "pq_ops": 20000},
                state_shapes={"ef_slots": (1 << 20, 61)}, mean_degree=127.5,
                peaks=spec.peaks("TPU v5 lite"))
    for name in ["device.idle_share.qps", "device.idle_share.p50",
                 "ef_decode_roofline", "beam_step_roofline"]:
        read = spec.reader(name)
        got = read(SimpleNamespace(trace=new, **base))
        assert got is not None and got == read(SimpleNamespace(trace=old,
                                                               **base))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_flat_spans_are_named_as_trace_py_names_them(seed):
    """Spans that do not nest: the innermost-span rule is trace.py's."""
    rng = np.random.default_rng(seed)
    edges = np.sort(rng.choice(10_000, 120, replace=False)).astype(float)
    spans = [E(f"bench.s{i % 3}", a, b - a)
             for i, (a, b) in enumerate(zip(edges[::2], edges[1::2]))]
    old, new = tr.HostSpans(spans), pt.NestedSpans(spans)
    for a, b in rng.integers(-100, 10_100, (300, 2)):
        lo, hi = sorted((float(a), float(b) + 1))
        assert new.activity(lo, hi) == old.activity(lo, hi)


def test_scope_time_is_leaf_device_time_by_scope():
    dev = [S(WHILE, 10, 60, "beam.traverse"),
           S(BEAM, 10, 20, "beam.traverse"), S(EF, 30, 10, "beam.traverse"),
           S(FUSION, 50, 20, "beam.rerank"),
           S(FUSION, 80, 10, None), S(FUSION, 120, 5, "beam.lut")]
    t = tr.Trace(devices={"/device:TPU:0": dev,
                          "/device:TPU:1": [S(FUSION, 0, 40, "beam.lut")]},
                 host=[E("bench.window", 0, 100)])
    s = pt.summarize(t, KERNELS)
    # the while is a container, not a leaf; 120..125 is outside the window
    assert s.scope_s == pytest.approx({"beam.traverse": 30e-9 / 2,
                                       "beam.rerank": 20e-9 / 2,
                                       "beam.lut": 40e-9 / 2})
    assert s.kernel_s["beam_step"] == pytest.approx(20e-9 / 2)


HLO = """HloModule jit_f
ENTRY %main {
  %fusion.85 = s32[16384]{0} fusion(s32[32,32768]{1,0} %g), kind=kLoop, \
metadata={op_name="jit(f)/beam.lut/vmap()/sub"}
  %while.6 = (s32[32,200]{1,0}, f32[32]{0}) while(%tuple.1), body=%b, \
metadata={op_name="jit(f)/beam.traverse/while"}
  %copy.3 = s32[8]{0} copy(s32[8]{0} %x)
}"""


def test_scopes_from_compiled_text():
    scopes = pt.scopes_from_hlo([HLO])
    assert scopes[tr.op_label(FUSION)] == "beam.lut"
    assert scopes[tr.op_label(WHILE)] == "beam.traverse"
    assert scopes["copy.3 = s32[8] copy"] is None
    # one label, two scopes in two programs: neither is trusted
    other = HLO.replace("beam.lut", "beam.rerank")
    assert pt.scopes_from_hlo([HLO, other])[tr.op_label(FUSION)] is None
    assert pt.scopes_from_hlo([HLO, HLO])[tr.op_label(FUSION)] == "beam.lut"


def test_ops_take_the_scope_of_the_op_around_them():
    """A compiler-made loop (a scatter lowered to a while) carries no
    op_name: it and its body take the scope of the loop around them."""
    got = pt.inherit_scopes([
        S(FUSION, 5, 5, "beam.lut"), S(WHILE, 10, 60, "beam.traverse"),
        S(WHILE, 12, 30, None), S(BEAM, 12, 10, None),
        S(EF, 30, 10, "beam.rerank"), S(FUSION, 50, 20, None),
        S(FUSION, 80, 10, None)])
    assert [(e.start_ns, e.scope) for e in got] == [
        (5, "beam.lut"), (10, "beam.traverse"), (12, "beam.traverse"),
        (12, "beam.traverse"), (30, "beam.rerank"), (50, "beam.traverse"),
        (80, None)]


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 5000 duration_ps: 20000
      stats { metadata_id: 1 uint64_value: 5000 } }
    events { metadata_id: 2 offset_ps: 30000 duration_ps: 1000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 40000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.85 = s32[16384]{0:T(1024)} fusion(s32[32,32768] %g)" } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = f32[8] copy(y)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_f" } }
  stat_metadata { key: 1 value { id: 1 name: "device_offset_ps" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000 }
    events { metadata_id: 2 offset_ps: 1000 duration_ps: 40000 }
    events { metadata_id: 3 offset_ps: 2000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "serve.search" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(f)" } }
}
"""


def test_read_keeps_program_spans_and_scopes():
    from jax.profiler import ProfileData
    t = pt.from_profile(ProfileData.from_text_proto(XSPACE),
                        pt.scopes_from_hlo([HLO]))
    ops = t.devices["/device:TPU:0"]
    assert [(e.start_ns, e.dur_ns, e.scope) for e in ops] == [
        (1005.0, 20.0, "beam.lut"), (1030.0, 1.0, None)]
    assert [(e.name, e.start_ns) for e in t.host] == [
        ("bench.window", 1000.0), ("serve.search", 1001.0)]


def test_recorded_trace_of_the_served_path(tmp_path):
    """One call of ``BatchedSearcher.search`` traced on the CPU: its spans
    are read with the benchmark's, on one clock, and nest as documented."""
    import jax
    from repro.core.index import build_device_index
    from repro.core.search.beam import SearchParams
    from repro.data.synthetic import make_queries, make_vector_dataset
    from repro.serve.ann import BatchedSearcher, ServeConfig

    vecs = make_vector_dataset("prop-like", n=200, dim=16,
                               seed=0).astype(np.float32)
    index, _, _ = build_device_index(vecs, r=8, l_build=16, pq_m=4, seed=0)
    p = SearchParams(l_size=16, beam_width=4, k=5, rerank_batch=5, r_max=8,
                     universe=len(vecs), max_iters=32)
    searcher = BatchedSearcher(index, p, ServeConfig(buckets=(1, 8)))
    queries = make_queries("prop-like", 9, 16).astype(np.float32)
    searcher.search(queries)                    # compile outside the trace
    tracer = tr.Tracer(tmp_path)
    tracer.start()
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.search"):
            searcher.search(queries)            # runs as 8 + 1
    t = pt.read(tracer.stop())
    spans = pt.NestedSpans([e for e in t.host if e.name != tr.WINDOW_SPAN])
    parent = {sp.name: spans.spans[i].name if i >= 0 else None
              for sp, i in zip(spans.spans, spans.parent)}
    assert parent == {"bench.search": None, "serve.search": "bench.search",
                      "serve.plan": "serve.search",
                      "serve.launch": "serve.search",
                      "serve.fetch": "serve.search",
                      "serve.account": "serve.search",
                      "serve.merge": "serve.search"}
    window = next(e for e in t.host if e.name == tr.WINDOW_SPAN)
    assert all(window.start_ns <= sp.start_ns and sp.end_ns <= window.end_ns
               for sp in spans.spans)
    assert spans.calls(window.start_ns, window.end_ns) == {
        "bench.search": 1, "serve.search": 1, "serve.plan": 1,
        "serve.launch": 2, "serve.fetch": 2, "serve.account": 2,
        "serve.merge": 1}
    assert pt.summarize(t, KERNELS) is None     # no device plane on the CPU


def test_active_rows_reader():
    read = spec.reader("beam.active_rows.qps")
    run = SimpleNamespace(counters={"rounds": 60, "row_rounds": 1536,
                                    "slot_rounds": 60 * 32})
    assert read(run) == pytest.approx(80.0)
    assert read(SimpleNamespace(counters={"n_queries": 32})) is None
    assert read(SimpleNamespace(counters={"row_rounds": 0,
                                          "slot_rounds": 0})) is None
