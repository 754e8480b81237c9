"""Kernel work counts, roofline shares and metric readers, by hand."""
from types import SimpleNamespace

import numpy as np
import pytest

import bench_testing  # noqa: F401
from bench import spec, traffic

CFG = {"pq_m": 32, "search_list": 200, "beam_width": 4, "pq_centroids": 256,
       "graph_degree": 128}
PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def fake_run(**kw):
    base = dict(cell=SimpleNamespace(config=CFG),
                counters={"graph_ios": 300, "cache_hits": 100,
                          "pq_ops": 20000, "n_queries": 40,
                          "n_padded": 6, "rows_dispatched": 48},
                state_shapes={"ef_slots": (1 << 20, 61)}, mean_degree=127.5,
                trace=SimpleNamespace(kernel_s={"beam_step": 1e-3,
                                                "ef_decode": 2e-4},
                                      busy_s=0.3, window_s=1.2),
                peaks=PEAKS, rows=1 << 20, state_bytes=(1 << 20) * 1300,
                peak_bytes=(1 << 20) * 1340, setup_s=12.5,
                check={"recall": 0.99, "recall_answers": 40})
    base.update(kw)
    return SimpleNamespace(**base)


def kernel(name):
    return spec.load_module(spec.BENCH_DIR / "kernels" / f"{name}.py")


def test_beam_step_work():
    # 400 lists over W=4: 100 hops; 20,000 new neighbours
    ops, nbytes = kernel("beam_step").work(fake_run())
    assert ops == 20000 * 32 + 20000 + 100 * 200
    assert nbytes == 100 * (32 * 256 * 4 + 4 * 200 * 4) + 20000 * (32 + 4)


def test_ef_decode_work():
    ops, nbytes = kernel("ef_decode").work(fake_run())
    assert ops == pytest.approx(400 * 127.5)
    assert nbytes == pytest.approx(400 * (61 * 4 + 127.5 * 4 + 4))


def test_roofline_share_is_bytes_bound_here():
    share = spec.reader("beam_step_roofline")(fake_run())
    assert share == pytest.approx(100 * (4_316_800 / 819e9) / 1e-3)
    ef = spec.reader("ef_decode_roofline")(fake_run())
    assert ef == pytest.approx(100 * (400 * 758 / 819e9) / 2e-4)


def test_roofline_is_silent_without_its_kernel_or_trace():
    run = fake_run(trace=SimpleNamespace(kernel_s={"ef_decode": 1e-4},
                                         busy_s=1, window_s=2))
    assert spec.reader("beam_step_roofline")(run) is None
    assert spec.reader("beam_step_roofline")(fake_run(trace=None)) is None
    assert spec.reader("ef_decode_roofline")(fake_run(peaks=None)) is None


def test_layer_readers():
    run = fake_run()
    assert spec.reader("serve.pad_share")(run) == pytest.approx(12.5)
    assert spec.reader("beam.lists_per_query")(run) == pytest.approx(10.0)
    assert spec.reader("device.idle_share.qps")(run) == pytest.approx(75.0)
    assert spec.reader("device.idle_share.p50")(run) == pytest.approx(75.0)
    assert spec.reader("index.state_bytes_per_vector")(run) == 1300
    assert spec.reader("hbm_bytes_per_vector")(run) == 1340
    assert spec.reader("recall_at_10")(run) == 0.99
    assert spec.reader("setup_s")(run) == 12.5


def test_window_readers():
    calls = [traffic.Call(0.0, 0.1, np.arange(4), np.zeros(4), None, None),
             traffic.Call(0.1, 0.4, np.arange(2), np.array([0.05, 0.3]),
                          None, None)]
    run = fake_run(window=traffic.Window(calls=calls, seconds=0.4))
    assert spec.reader("qps")(run) == pytest.approx(6 / 0.4)
    assert spec.reader("serve.call_ms.qps")(run) == pytest.approx(200.0)
    assert spec.reader("serve.call_ms.p50")(run) == pytest.approx(200.0)
    lat = [0.1] * 4 + [0.35, 0.1]
    assert spec.reader("latency.p99_ms")(run) == pytest.approx(
        1e3 * np.percentile(lat, 99))
    assert spec.reader("p50_ms")(run) == pytest.approx(
        1e3 * np.percentile(lat, 50))
