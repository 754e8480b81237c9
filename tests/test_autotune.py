"""Autotune cache tier: persistence round-trip, deterministic resolution,
shape-bucket fallback, platform keying, and the dispatch-rule gate that
``auto-tuned`` can never resolve to a backend that lost its own bench."""
import json

import numpy as np
import pytest

from repro.kernels import autotune, dispatch
from repro.kernels.autotune import (AutotuneCache, bucket_dims, bucket_key,
                                    _log_distance)
from repro.kernels.dispatch import KernelConfig, resolve_backend


def _cache(platform="cpu"):
    c = AutotuneCache(platform=platform)
    c.record("pq_adc", "ref", 500.0, n=1024, m=8, k=256)
    c.record("pq_adc", "pallas", 1100.0, n=1024, m=8, k=256)
    c.record("pq_adc", "ref", 800.0, n=4096, m=8, k=256)
    c.record("pq_adc", "pallas", 6100.0, n=4096, m=8, k=256)
    c.record("ef_decode", "ref", 7000.0, lists=256, r=32)
    c.record("ef_decode", "pallas-interpret", 590.0, lists=256, r=32)
    c.record("beam_step", "off", 5200.0, nq=32, e=64, l=48, m=8)
    c.record("beam_step", "ref", 9900.0, nq=32, e=64, l=48, m=8)
    c.record("beam_step", "pallas", 15000.0, nq=32, e=64, l=48, m=8)
    return c


# ------------------------------------------------------------------ buckets
def test_bucket_dims_power_of_two():
    assert bucket_dims(n=1000, m=8) == {"n": 1024, "m": 8}
    assert bucket_dims(n=1025) == {"n": 2048}
    assert bucket_dims(n=1) == {"n": 1}
    # same bucket -> same key (deterministic, sorted dims)
    assert bucket_key("op", b=2, a=1) == bucket_key("op", a=1, b=2)
    assert bucket_key("pq_adc", n=900, m=8) == bucket_key("pq_adc",
                                                          n=1024, m=8)


def test_log_distance_prefers_shared_dims():
    a = bucket_dims(n=1024, m=8)
    assert _log_distance(a, bucket_dims(n=2048, m=8)) == 1.0
    assert _log_distance(a, bucket_dims(n=1024, m=16)) == 1.0
    # an unshared key is worse than any 16x size gap on a shared dim
    assert _log_distance(a, bucket_dims(n=1024)) == 4.0


# ------------------------------------------------------------- round-trip
def test_cache_round_trip(tmp_path):
    c = _cache()
    path = tmp_path / "cache.json"
    c.save(path)
    loaded = AutotuneCache.load(path, platform="cpu")
    assert loaded.entries == c.entries
    assert loaded.best("pq_adc", dict(n=1024, m=8, k=256)) == "ref"
    # JSON is stable: saving the loaded cache reproduces the bytes
    p2 = tmp_path / "cache2.json"
    loaded.save(p2)
    assert path.read_text() == p2.read_text()


def test_cache_platform_mismatch_is_empty(tmp_path):
    """A cpu-measured cache (pallas column = interpreter) must NEVER drive
    tpu decisions: loading under the other platform yields an empty cache
    and resolution falls back to the auto rule."""
    path = tmp_path / "cache.json"
    _cache(platform="cpu").save(path)
    tpu_view = AutotuneCache.load(path, platform="tpu")
    assert tpu_view.entries == {}
    assert tpu_view.best("pq_adc", dict(n=1024, m=8, k=256),
                         fallback="pallas") == "pallas"


def test_cache_missing_or_corrupt_is_empty(tmp_path):
    assert AutotuneCache.load(tmp_path / "nope.json", "cpu").entries == {}
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert AutotuneCache.load(bad, "cpu").entries == {}
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({"version": -1, "platform": "cpu",
                                 "entries": {"x|n=1": {"us": {"ref": 1}}}}))
    assert AutotuneCache.load(stale, "cpu").entries == {}


def test_record_keeps_minimum():
    c = AutotuneCache(platform="cpu")
    c.record("pq_adc", "ref", 900.0, n=1024, m=8, k=256)
    c.record("pq_adc", "ref", 500.0, n=1024, m=8, k=256)   # faster rerun
    c.record("pq_adc", "ref", 800.0, n=1000, m=8, k=256)   # same bucket
    key = bucket_key("pq_adc", n=1024, m=8, k=256)
    assert c.entries[key]["us"]["ref"] == 500.0


# ------------------------------------------------------------- resolution
def test_best_is_deterministic_and_never_loses():
    c = _cache()
    for _ in range(3):   # same inputs -> same answer, every time
        assert c.best("pq_adc", dict(n=1024, m=8, k=256)) == "ref"
        assert c.best("ef_decode", dict(lists=256, r=32)) \
            == "pallas-interpret"
        assert c.best("beam_step", dict(nq=32, e=64, l=48, m=8)) == "off"
    # the gate: the pick always has the minimum measured time
    for key, entry in c.entries.items():
        pick = c._argmin(entry)
        assert entry["us"][pick] == min(entry["us"].values())


def test_best_tie_breaks_to_ref():
    c = AutotuneCache(platform="cpu")
    c.record("op", "pallas", 100.0, n=8)
    c.record("op", "ref", 100.0, n=8)
    assert c.best("op", dict(n=8)) == "ref"


def test_bucket_fallback_nearest_then_majority():
    c = _cache()
    # unseen n=16384 bucket -> nearest measured pq_adc bucket (n=4096): ref
    assert c.best("pq_adc", dict(n=16384, m=8, k=256)) == "ref"
    # no dims at all -> majority vote over the op's buckets
    assert c.best("pq_adc") == "ref"
    assert c.best("ef_decode") == "pallas-interpret"
    # unknown op -> fallback verbatim
    assert c.best("no_such_op", dict(n=4)) == "ref"
    assert c.best("no_such_op", fallback="pallas") == "pallas"


# ------------------------------------------------- dispatch integration
def test_auto_tuned_resolution_through_dispatch(tmp_path, monkeypatch):
    """REPRO_AUTOTUNE_CACHE + 'auto-tuned' config: resolution reads the
    cache per op, degrades measured picks per platform, and is idempotent
    — the resolved config is concrete static jit state."""
    path = tmp_path / "cache.json"
    _cache(platform="cpu").save(path)
    monkeypatch.setenv(autotune.ENV_CACHE, str(path))
    cfg = KernelConfig(*(["auto-tuned"] * 5)).resolve("cpu")
    assert cfg.is_resolved
    assert cfg.pq_adc == "ref"
    assert cfg.ef_decode == "pallas-interpret"
    assert cfg.beam_step == "off"      # unfused wins its bench on cpu
    assert cfg.resolve("cpu") == cfg   # idempotent
    # per-shape resolution via the shapes hint
    shaped = KernelConfig(*(["auto-tuned"] * 5)).resolve(
        "cpu", shapes={"pq_adc": dict(n=1024, m=8, k=256)})
    assert shaped.pq_adc == "ref"


def test_auto_tuned_empty_cache_falls_back_to_gated_auto(tmp_path,
                                                         monkeypatch):
    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "missing.json"))
    assert resolve_backend("auto-tuned", "tpu", op="pq_adc") == "pallas"
    assert resolve_backend("auto-tuned", "tpu", op="byteplane") == "pallas"
    assert resolve_backend("auto-tuned", "cpu", op="pq_adc") == "ref"


def test_committed_cache_never_loses_its_bench():
    """The SHIPPED cache (kernels/autotune_cache.json): for every entry the
    recorded pick must be the measured argmin — i.e. the committed
    artefact satisfies the auto-never-loses dispatch rule on its own
    platform."""
    doc = json.loads(autotune.DEFAULT_CACHE_PATH.read_text())
    assert doc["version"] == autotune.CACHE_VERSION
    cache = AutotuneCache.load(autotune.DEFAULT_CACHE_PATH,
                               platform=doc["platform"])
    assert cache.entries, "committed cache is empty — rerun bench_kernels"
    for key, entry in cache.entries.items():
        pick = cache._argmin(entry)
        assert entry["us"][pick] == min(entry["us"].values()), key
    # byteplane pallas lost its interpreter bench -> the cpu cache picks ref
    op_names = {k.split("|")[0] for k in cache.entries}
    if "byteplane" in op_names:
        assert cache.best("byteplane") == "ref"
