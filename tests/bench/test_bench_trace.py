"""The trace reduction, on constructed traces with hand-computed answers,
and on one trace recorded on the CPU."""
import pytest

import bench_testing  # noqa: F401
from bench import trace as tr

E = tr.Event
WHILE = ("%while.6 = (s32[32,200]{1,0:T(8,128)}, f32[32]{0}) "
         "while(%tuple.1), body=%b")
BEAM = ("%beam_step_pallas.4 = (s32[32,1,256]{2,1,0:T(1,128)S(1)}, "
        "f32[32,1,256]{2,1,0}) custom-call(u8[32,32,512]{2,1,0} %a), "
        "custom_call_target=\"tpu_custom_call\"")
EF = ("%ef_decode_pallas.4 = (s32[128,128]{1,0:T(8,128)S(1)}, "
      "s32[128,1]{1,0}) custom-call(s32[128,37]{1,0} %w)")
FUSION = "%fusion.85 = s32[16384]{0:T(1024)S(1)} fusion(s32[32,32768]{1,0} %g)"


def constructed():
    """Window 0..100 ns. Device: a while (10..70) holding beam 10..30,
    ef 30..40, fusion 50..70; a fusion 80..90. Host: search 5..75,
    wait 75..95."""
    dev = [E(WHILE, 10, 60), E(BEAM, 10, 20), E(EF, 30, 10),
           E(FUSION, 50, 20), E(FUSION, 80, 10), E(FUSION, 120, 5)]
    host = [E("bench.window", 0, 100), E("bench.search", 5, 70),
            E("bench.wait", 75, 20)]
    return tr.Trace(devices={"/device:TPU:0": dev}, host=host)


def test_leaves_drop_containers():
    got = tr.leaves(constructed().devices["/device:TPU:0"])
    assert WHILE not in [e.name for e in got]
    assert len(got) == 5


def test_union_merges_touching_and_overlapping():
    assert tr.union([[5, 7], [0, 2], [2, 3], [6, 9]]) == [[0, 3], [5, 9]]


def test_summary_by_hand():
    s = tr.summarize(constructed(), ["beam_step", "ef_decode", "rerank_l2"])
    assert s.window_s == pytest.approx(100e-9)
    # leaves inside the window: 10..30, 30..40, 50..70, 80..90 -> 60 ns
    assert s.busy_s == pytest.approx(60e-9)
    assert s.kernel_s == pytest.approx(
        {"beam_step": 20e-9, "ef_decode": 10e-9, "rerank_l2": 0.0})
    assert s.kernel_calls == {"beam_step": 1, "ef_decode": 1,
                              "rerank_l2": 0}
    # gaps: 0..10 (search covers 5 of it) and 40..50 go to search; 70..80
    # (search and wait cover 5 each: the shorter, wait) and 90..100 to wait
    gaps = dict(s.idle_gaps)
    assert gaps["bench.search"] == pytest.approx(20e-9)
    assert gaps["bench.wait"] == pytest.approx(20e-9)
    assert sum(gaps.values()) == pytest.approx(40e-9)
    ops = dict(s.device_ops)
    assert ops["fusion.85 = s32[16384] fusion"] == pytest.approx(30e-9)


def test_busy_is_averaged_over_devices():
    t = constructed()
    t.devices["/device:TPU:1"] = [E(FUSION, 0, 100)]
    s = tr.summarize(t, [])
    assert s.busy_s == pytest.approx((60e-9 + 100e-9) / 2)


def test_labels_and_kernel_match():
    assert tr.op_label(BEAM).startswith(
        "beam_step_pallas.4 = (s32[32,1,256], f32[32,1,256]) custom-call")
    assert tr.is_kernel(BEAM, "beam_step")
    assert tr.is_kernel(EF, "ef_decode")
    assert not tr.is_kernel(EF, "beam_step")
    assert not tr.is_kernel(FUSION, "fusion")       # not a custom call


def test_host_activity_prefers_most_cover_then_innermost():
    spans = tr.HostSpans([E("bench.search", 0, 100),
                          E("bench.generate", 40, 10)])
    assert spans.activity(40, 50) == "bench.generate"
    assert spans.activity(10, 60) == "bench.search"
    assert spans.activity(200, 210) == "idle"


def test_recorded_cpu_trace(tmp_path):
    """A real trace file: the window and host spans are read back; the CPU
    backend has no device plane, so there is no summary."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    t = tr.Tracer(tmp_path)
    t.start()
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.search"):
            f(x).block_until_ready()
    got = tr.read(t.stop())
    names = [e.name for e in got.host]
    assert tr.WINDOW_SPAN in names and "bench.search" in names
    win = next(e for e in got.host if e.name == tr.WINDOW_SPAN)
    search = next(e for e in got.host if e.name == "bench.search")
    assert win.start_ns <= search.start_ns and search.end_ns <= win.end_ns
    assert tr.summarize(got, ["beam_step"]) is None
