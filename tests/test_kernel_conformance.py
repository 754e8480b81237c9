"""Kernel conformance tier: every op's pallas-interpret output must match
its jnp oracle to tight tolerance across ragged shapes and degenerate
inputs, through the SAME dispatch layer the search hot path uses.

Property tests run under ``hypothesis`` when it is installed; where it is
absent (this container) the same property functions are driven by seeded
``numpy.random`` draws, so the tier never silently skips — that is how the
seed's broken ef_decode kernel went unnoticed behind a module-level
``importorskip``.
"""
import zlib

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.codec.elias_fano import encode_slot
from repro.kernels import dispatch
from repro.kernels.dispatch import KernelConfig, get_impl, resolve_backend

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

REF = KernelConfig("ref", "ref", "ref", "ref", "ref")
PAL = KernelConfig("pallas-interpret", "pallas-interpret",
                   "pallas-interpret", "pallas-interpret",
                   "pallas-interpret")


def hypothesize(n_fallback=8, **bounds):
    """@given(**integer strategies) when hypothesis is available; otherwise
    a deterministic seeded-numpy parametrization of the same bounds."""
    if HAVE_HYPOTHESIS:
        strats = {k: st.integers(lo, hi) for k, (lo, hi) in bounds.items()}

        def deco(fn):
            return settings(max_examples=16, deadline=None)(
                given(**strats)(fn))
        return deco

    def deco(fn):
        rng = np.random.default_rng(zlib.crc32(fn.__name__.encode()))
        cases = [tuple(int(rng.integers(lo, hi + 1))
                       for lo, hi in bounds.values())
                 for _ in range(n_fallback)]
        return pytest.mark.parametrize(",".join(bounds), cases)(fn)
    return deco


# ------------------------------------------------------------------ pq_adc
# Required sweep: M in {8, 16, 32}, K = 256, row counts that are not
# multiples of the BN=128 tile, plus degenerate inputs.
@pytest.mark.parametrize("n", [1, 7, 127, 129, 300])
@pytest.mark.parametrize("m", [8, 16, 32])
def test_pq_adc_conformance(n, m):
    rng = np.random.default_rng(1000 * n + m)
    codes = jnp.asarray(rng.integers(0, 256, (n, m), dtype=np.uint8))
    lut = jnp.asarray(rng.normal(size=(m, 256)).astype(np.float32))
    out_p = dispatch.pq_adc(codes, lut, PAL)
    out_r = dispatch.pq_adc(codes, lut, REF)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("m", [8, 16, 32])
def test_pq_adc_all_equal_codes(m):
    """Degenerate: every row the same code word -> one distance, exactly."""
    codes = jnp.full((130, m), 3, jnp.uint8)
    lut = jnp.asarray(np.random.default_rng(m).normal(
        size=(m, 256)).astype(np.float32))
    out_p = np.asarray(dispatch.pq_adc(codes, lut, PAL))
    out_r = np.asarray(dispatch.pq_adc(codes, lut, REF))
    assert len(set(out_p.tolist())) == 1
    np.testing.assert_allclose(out_p, out_r, rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("nq,n", [(1, 1), (3, 130), (8, 96)])
def test_pq_adc_batched_conformance(nq, n):
    """The batched-queries entry the beam loop calls: each query scored
    against ITS OWN LUT, rows batch-invariant."""
    rng = np.random.default_rng(nq * 100 + n)
    codes = jnp.asarray(rng.integers(0, 256, (nq, n, 8), dtype=np.uint8))
    luts = jnp.asarray(rng.normal(size=(nq, 8, 256)).astype(np.float32))
    out_p = np.asarray(dispatch.pq_adc_batched(codes, luts, PAL))
    out_r = np.asarray(dispatch.pq_adc_batched(codes, luts, REF))
    np.testing.assert_allclose(out_p, out_r, rtol=1e-6, atol=1e-5)
    # row qi is what the single-query op computes with lut qi
    for qi in range(nq):
        solo = np.asarray(dispatch.pq_adc(codes[qi], luts[qi], PAL))
        np.testing.assert_allclose(out_p[qi], solo, rtol=1e-6, atol=1e-5)


@hypothesize(n_fallback=8, n=(1, 300), m=(1, 32), seed=(0, 2**31))
def test_pq_adc_property(n, m, seed):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 256, size=(n, m), dtype=np.uint8)
    lut = rng.normal(size=(m, 256)).astype(np.float32)
    out_p = dispatch.pq_adc(jnp.asarray(codes), jnp.asarray(lut), PAL)
    expected = lut[np.arange(m)[None, :], codes].sum(-1)
    np.testing.assert_allclose(np.asarray(out_p), expected,
                               rtol=1e-5, atol=1e-4)


# --------------------------------------------------------------- ef_decode
@pytest.mark.parametrize("r_max,universe",
                         [(8, 64), (16, 1000), (24, 10**5), (32, 10**6)])
def test_ef_decode_conformance(r_max, universe):
    """Ragged list lengths including EMPTY lists and full r_max lists; the
    decode is integer so pallas-interpret must match the oracle exactly."""
    rng = np.random.default_rng(r_max)
    lens = [0, 1, r_max, r_max // 2, min(13, r_max), 0]
    slots, truth = [], []
    for ln in lens:
        vals = np.sort(rng.choice(universe, size=ln,
                                  replace=False).astype(np.uint64))
        slots.append(encode_slot(vals, r_max, universe))
        truth.append(vals)
    slots = jnp.asarray(np.stack(slots))
    nb_p, ct_p = dispatch.ef_decode(slots, r_max, universe, PAL)
    nb_r, ct_r = dispatch.ef_decode(slots, r_max, universe, REF)
    np.testing.assert_array_equal(np.asarray(nb_p), np.asarray(nb_r))
    np.testing.assert_array_equal(np.asarray(ct_p), np.asarray(ct_r))
    for i, vals in enumerate(truth):
        assert int(ct_p[i]) == len(vals)
        np.testing.assert_array_equal(
            np.asarray(nb_p[i][:len(vals)]), vals.astype(np.int64))


@hypothesize(n_fallback=6, r_max=(1, 48), log_u=(4, 20), seed=(0, 2**31))
def test_ef_decode_property(r_max, log_u, seed):
    universe = max(2 ** log_u, r_max + 1)
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, r_max + 1, size=4)
    slots = np.stack([
        encode_slot(np.sort(rng.choice(universe, size=int(ln),
                                       replace=False).astype(np.uint64)),
                    r_max, universe) for ln in lens])
    nb_p, ct_p = dispatch.ef_decode(jnp.asarray(slots), r_max, universe, PAL)
    nb_r, ct_r = dispatch.ef_decode(jnp.asarray(slots), r_max, universe, REF)
    np.testing.assert_array_equal(np.asarray(nb_p), np.asarray(nb_r))
    np.testing.assert_array_equal(np.asarray(ct_p), np.asarray(ct_r))


# --------------------------------------------------------------- rerank_l2
@pytest.mark.parametrize("q,c,d", [(1, 1, 8), (7, 20, 100), (8, 128, 96),
                                   (9, 130, 200), (3, 5, 129)])
def test_rerank_l2_conformance(q, c, d):
    """Ragged (q, c, d) off the (8, 128, 128) tile boundaries."""
    rng = np.random.default_rng(q * c + d)
    queries = jnp.asarray(rng.normal(size=(q, d)).astype(np.float32))
    cands = jnp.asarray(rng.normal(size=(q, c, d)).astype(np.float32))
    out_p = dispatch.rerank_l2(queries, cands, PAL)
    out_r = dispatch.rerank_l2(queries, cands, REF)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-4, atol=1e-3)


def test_rerank_l2_degenerate_equal_rows():
    """Candidate == query -> distance exactly ~0 under both backends."""
    q = jnp.asarray(np.random.default_rng(0).normal(
        size=(4, 32)).astype(np.float32))
    cands = jnp.repeat(q[:, None, :], 9, axis=1)
    for cfg in (REF, PAL):
        out = np.asarray(dispatch.rerank_l2(q, cands, cfg))
        np.testing.assert_allclose(out, 0.0, atol=1e-4)


@hypothesize(n_fallback=6, q=(1, 12), c=(1, 140), d=(1, 160),
             seed=(0, 2**31))
def test_rerank_l2_property(q, c, d, seed):
    rng = np.random.default_rng(seed)
    queries = rng.normal(size=(q, d)).astype(np.float32)
    cands = rng.normal(size=(q, c, d)).astype(np.float32)
    out_p = dispatch.rerank_l2(jnp.asarray(queries), jnp.asarray(cands), PAL)
    expected = ((cands - queries[:, None, :]) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(out_p), expected,
                               rtol=1e-3, atol=1e-2)


# --------------------------------------------------------------- byteplane
@hypothesize(n_fallback=6, n=(1, 400), v=(1, 96), seed=(0, 2**31))
def test_byteplane_property(n, v, seed):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size=(n, v), dtype=np.uint8)
    base = rng.integers(0, 256, size=v, dtype=np.uint8)
    out_p = dispatch.byteplane_decode(jnp.asarray(data), jnp.asarray(base),
                                      PAL)
    out_r = dispatch.byteplane_decode(jnp.asarray(data), jnp.asarray(base),
                                      REF)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_r))
    twice = dispatch.byteplane_decode(out_p, jnp.asarray(base), PAL)
    np.testing.assert_array_equal(np.asarray(twice), data)   # involution


def test_byteplane_in_vector_store_load():
    """The store's load path with a kernel config returns bit-identical
    vectors to the host numpy path (the XOR transform is lossless)."""
    from repro.core.storage.vector_store import (DecoupledVectorStore,
                                                 StoreConfig)
    rng = np.random.default_rng(3)
    vecs = (rng.normal(size=(256, 16)) * 16).astype(np.int8)
    ids = np.arange(256)
    stores = []
    for kernels in (None, PAL):
        s = DecoupledVectorStore(StoreConfig(dim=16, dtype=np.int8,
                                             segment_capacity=128,
                                             chunk_bytes=1 << 10,
                                             kernels=kernels))
        s.append(ids, vecs)
        s.seal_active()
        stores.append(s)
    got_ref = stores[0].get(ids[3:200])
    got_pal = stores[1].get(ids[3:200])
    np.testing.assert_array_equal(got_ref, got_pal)
    np.testing.assert_array_equal(got_pal, vecs[3:200])


# --------------------------------------------------------------- beam_step
# The fused hop kernel must be BIT-IDENTICAL on ids/top_idx to the unfused
# composition (jax.lax.top_k tie-breaking included) — fusion is an execution
# plan change, never an algorithm change.

def _beam_step_case(nq, e, l_size, m, seed, mask_p=0.85, ties=False):
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(rng.integers(0, 256, (nq, e, m), dtype=np.uint8))
    luts = rng.normal(size=(nq, m, 256)).astype(np.float32)
    if ties:   # quantize hard so merged distances collide constantly
        luts = np.round(luts)
    luts = jnp.asarray(luts)
    cand_d = np.sort(rng.normal(size=(nq, l_size)).astype(np.float32) ** 2, 1)
    if ties:
        cand_d = np.round(cand_d * 2) / 2
    cand_ids = rng.integers(0, 10**6, (nq, l_size)).astype(np.int32)
    new_ids = np.where(rng.random((nq, e)) < mask_p,
                       rng.integers(0, 10**6, (nq, e)), -1).astype(np.int32)
    return (codes, luts, jnp.asarray(cand_ids), jnp.asarray(cand_d),
            jnp.asarray(new_ids))


@pytest.mark.parametrize("nq,e,l_size,m",
                         [(1, 1, 1, 1), (3, 5, 4, 8), (7, 130, 48, 4),
                          (2, 17, 10, 16), (8, 64, 32, 8)])
def test_beam_step_conformance(nq, e, l_size, m):
    """Ragged (nq, E, L, M) off every tile boundary: ids and the top_idx
    permutation exactly equal; distances to float tolerance."""
    args = _beam_step_case(nq, e, l_size, m, seed=nq * 1000 + e)
    ids_p, d_p, ix_p = dispatch.beam_step(*args, PAL)
    ids_r, d_r, ix_r = dispatch.beam_step(*args, REF)
    np.testing.assert_array_equal(np.asarray(ids_p), np.asarray(ids_r))
    np.testing.assert_array_equal(np.asarray(ix_p), np.asarray(ix_r))
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_r),
                               rtol=1e-5, atol=1e-4)


def test_beam_step_ties_bit_identical():
    """Massive distance collisions: the fused stable-rank select must
    reproduce lax.top_k's lower-index-wins tie-break exactly."""
    args = _beam_step_case(4, 40, 16, 4, seed=7, ties=True)
    ids_p, _, ix_p = dispatch.beam_step(*args, PAL)
    ids_r, _, ix_r = dispatch.beam_step(*args, REF)
    np.testing.assert_array_equal(np.asarray(ids_p), np.asarray(ids_r))
    np.testing.assert_array_equal(np.asarray(ix_p), np.asarray(ix_r))


def test_beam_step_all_masked():
    """Every new id masked (-1): the candidate list passes through
    unchanged and top_idx is the identity permutation."""
    args = _beam_step_case(3, 12, 8, 8, seed=11, mask_p=0.0)
    for cfg in (REF, PAL):
        ids, d, ix = dispatch.beam_step(*args, cfg)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(args[2]))
        np.testing.assert_allclose(np.asarray(d), np.asarray(args[3]),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(ix),
                                      np.tile(np.arange(8), (3, 1)))


def test_beam_step_matches_unfused_composition():
    """The fused op == pq_adc_batched + mask + concat + top_k, bit-for-bit
    on ids (the guarantee the hot path's beam_step branch relies on)."""
    codes, luts, cand_ids, cand_d, new_ids = _beam_step_case(
        5, 33, 20, 8, seed=23)
    import jax
    d = dispatch.pq_adc_batched(codes, luts, REF)
    new_d = jnp.where(new_ids >= 0, d, jnp.inf)
    merged_ids = jnp.concatenate([cand_ids, new_ids], 1)
    merged_d = jnp.concatenate([cand_d, new_d], 1)
    top_d, top_i = jax.lax.top_k(-merged_d, 20)
    want_ids = jnp.take_along_axis(merged_ids, top_i, 1)
    for cfg in (REF, PAL):
        got_ids, got_d, got_ix = dispatch.beam_step(
            codes, luts, cand_ids, cand_d, new_ids, cfg)
        np.testing.assert_array_equal(np.asarray(got_ids),
                                      np.asarray(want_ids))
        np.testing.assert_array_equal(np.asarray(got_ix), np.asarray(top_i))
        np.testing.assert_allclose(np.asarray(got_d), np.asarray(-top_d),
                                   rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------- dispatch layer
def test_resolution_rules():
    assert resolve_backend("auto", "tpu") == "pallas"
    assert resolve_backend("auto", "cpu") == "ref"
    assert resolve_backend("pallas", "cpu") == "pallas-interpret"
    assert resolve_backend("pallas", "tpu") == "pallas"
    assert resolve_backend("ref", "tpu") == "ref"
    assert resolve_backend("pallas-interpret", "tpu") == "pallas-interpret"
    with pytest.raises(ValueError):
        resolve_backend("mxu", "tpu")
    cfg = KernelConfig("pallas", "auto", "ref", "auto", "off").resolve("cpu")
    assert cfg == KernelConfig("pallas-interpret", "ref", "ref", "ref",
                               "off")
    assert cfg.resolve("cpu") == cfg                   # idempotent


def test_auto_gating_rules():
    """No op is gated: plain 'auto' resolves EVERY op, byteplane included,
    to compiled pallas on TPU and to ref elsewhere. 'off' is a fixed point
    for beam_step and an error elsewhere."""
    assert resolve_backend("auto", "tpu", op="byteplane") == "pallas"
    assert resolve_backend("auto", "cpu", op="byteplane") == "ref"
    assert resolve_backend("auto", "tpu", op="pq_adc") == "pallas"
    assert resolve_backend("auto", "tpu", op="beam_step") == "pallas"
    assert resolve_backend("off", "tpu", op="beam_step") == "off"
    assert resolve_backend("off", "cpu", op="beam_step") == "off"
    with pytest.raises(ValueError, match="beam_step"):
        resolve_backend("off", "cpu", op="pq_adc")
    auto = KernelConfig().resolve("tpu")
    assert auto == KernelConfig(*(["pallas"] * len(KernelConfig._fields)))
    assert KernelConfig().resolve("cpu") == KernelConfig(
        *(["ref"] * len(KernelConfig._fields)))


def test_unresolved_auto_raises():
    """'auto' leaking past config time is the bug this layer exists to
    prevent — dispatch must refuse it loudly. 'off' reaching dispatch means
    the hot path forgot to branch before calling it."""
    with pytest.raises(RuntimeError, match="config time"):
        get_impl("pq_adc", "auto")
    with pytest.raises(RuntimeError, match="config time"):
        get_impl("beam_step", "auto-tuned")
    with pytest.raises(RuntimeError, match="branch"):
        get_impl("beam_step", "off")
    with pytest.raises(KeyError):
        get_impl("pq_adc", "nonsense")


def test_env_override(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_VAR, "ref")
    assert dispatch.from_env() == REF
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    cfg = dispatch.from_env()
    assert cfg.is_resolved and "pallas" in cfg.pq_adc
    monkeypatch.delenv(dispatch.ENV_VAR)
    assert dispatch.from_env().is_resolved             # auto default


@pytest.mark.slow
def test_interpret_sweep_large():
    """Wide interpret-mode sweep (multiple row-blocks per op) — slow tier."""
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, 256, (1000, 16), dtype=np.uint8))
    lut = jnp.asarray(rng.normal(size=(16, 256)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(dispatch.pq_adc(codes, lut, PAL)),
        np.asarray(dispatch.pq_adc(codes, lut, REF)), rtol=1e-6, atol=1e-4)
    q = jnp.asarray(rng.normal(size=(17, 64)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(17, 300, 64)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(dispatch.rerank_l2(q, c, PAL)),
        np.asarray(dispatch.rerank_l2(q, c, REF)), rtol=1e-4, atol=1e-3)
