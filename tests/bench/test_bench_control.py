"""The control of the comparison: the reference in bfloat16, put in the
program's place, fails it; the program at the same size passes it."""
import time

from bench_testing import tiny_cell  # noqa: F401
from bench import control, harness


def test_control_fails_and_program_passes(tiny_cell):
    cell = tiny_cell("sift-vamana-2k.open")
    got = control.control_check(cell, 2**31 + 9, 2.0, 0)
    assert got["answers"] > 0
    assert got["passed"] is False
    assert got["numbers"]["dist_err"] > got["limits"]["dist_err"]
    out = harness.run_cell(cell, 2**31 + 9, 1.0, False, time.perf_counter())
    assert out["correct"] is True
    assert out["checks"]["dist_err"]["value"] == 0.0


def test_bf16_control_answers_are_near_but_not_exact():
    import numpy as np
    from bench import data, reference
    base = data.make_vectors("sift-like", 512, 128, 4).astype(np.float32)
    q = data.make_queries("sift-like", 32, 128, 4).astype(np.float32)
    ids, dists = control.bf16_topk(base, q, 10, block=128)
    assert ids.shape == (32, 10) and (ids >= 0).all()
    assert reference.well_formed(ids, dists, 512, 10).all()
    exact = reference.exact_sq_l2(base, q, ids)
    err = np.abs(dists - exact) / exact
    assert err.max() > 0 and np.median(err) < 0.05


def test_reference_kth_distance_is_exact():
    """The device's integer k-th distance equals float64 brute force,
    block edges and a ragged last block included."""
    import numpy as np
    from bench import data, reference
    base = data.make_vectors("sift-like", 700, 128, 6)
    q = data.make_queries("sift-like", 37, 128, 6).astype(np.float32)
    d = ((base[None].astype(np.float64) - q[:, None]) ** 2).sum(-1)
    want = np.sort(d, 1)[:, 9]
    np.testing.assert_array_equal(
        reference.kth_nearest(base, q, 10, block=16), want)
