"""chip_smoke.py off the chip: it must refuse a non-TPU backend, and its
phases must still hold at a small size with the kernels interpreted."""
import importlib.util
from pathlib import Path

import numpy as np
import pytest


@pytest.fixture(scope="module")
def smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert all('"ok"' not in line for line in out.splitlines())


def test_capacity_phase_small(smoke):
    """The capacity phase's checks (interpreted Pallas ids == ref ids,
    distances == float64 L2) at 4096 rows instead of 2^20."""
    from repro.kernels.dispatch import KernelConfig
    fast = KernelConfig(*(["pallas"] * len(KernelConfig._fields))).resolve()
    assert set(fast) == {"pallas-interpret"}
    smoke.capacity_phase(0, fast, n=4096, n_queries=4)


def test_exact_l2_masks_empty_slots(smoke):
    base = np.array([[0, 0], [3, 4]], np.uint8)
    q = np.zeros((1, 2), np.float32)
    d = smoke._exact_l2(base, q, np.array([[1, -1]]))
    assert d[0, 0] == 25.0 and np.isinf(d[0, 1])
