"""Flash-attention runtime block autotuner (reference analog:
``csrc/includes/gemm_test.h``'s cached algorithm search)."""

import json

import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import kernel_tuner as kt


def test_anchored_shapes_keep_heuristic():
    # the measured calibration set never re-tunes by default
    assert kt.anchored(512, 512, 64, False)
    assert kt.anchored(1024, 1024, 64, True)   # single-tile causal anchor
    assert kt.anchored(2048, 2048, 64, False)
    assert not kt.anchored(1536, 1536, 64, False)   # off-grid length
    assert not kt.anchored(512, 512, 128, False)    # un-measured head_dim
    assert not kt.anchored(512, 1024, 64, False)    # cross-attention


def test_candidates_respect_constraints():
    for s, kv, d, causal in [(1536, 1536, 64, False), (512, 512, 128, True),
                             (768, 768, 96, False)]:
        cands = kt.candidates(s, kv, d, causal)
        assert cands and len(cands) <= 6
        for bq, bk in cands:
            assert s % bq == 0 and kv % bk == 0
            assert bk * d <= 128 * 1024  # VMEM cap (mirrors _auto_blocks)
            if causal:
                assert bk <= bq  # no diagonal-straddling k blocks


def test_tune_returns_heuristic_off_tpu(monkeypatch, tmp_path):
    """On non-TPU backends (this CI tier) tune() must fall back to the
    heuristic without touching the kernel."""
    monkeypatch.setattr(kt, "_cache_path",
                        lambda: str(tmp_path / "cache.json"))
    monkeypatch.setattr(kt, "_memory_cache", {})
    monkeypatch.setattr(kt, "_disk_loaded", False)

    def boom(*a, **k):  # noqa: ANN001
        raise AssertionError("kernel must not run on CPU tier")

    got = kt.tune(1536, 1536, 64, False, 0.0, boom, (512, 512))
    assert got == (512, 512)


class _FakeTpu:
    platform = "tpu"
    device_kind = "faketpu v0"


def test_cache_roundtrip(monkeypatch, tmp_path):
    """A cached winner short-circuits the search in a fresh 'process';
    cache keys carry the device kind (a v5e winner must not be reused on
    a different TPU generation)."""
    cache = tmp_path / "cache.json"
    key = kt._key(1536, 1536, 64, False, 0.0, _FakeTpu.device_kind)
    assert "faketpu_v0" in key
    monkeypatch.setattr(kt, "_cache_path", lambda: str(cache))
    monkeypatch.setattr(kt, "_memory_cache", {key: [256, 512]})
    monkeypatch.setattr(kt, "_disk_loaded", True)
    monkeypatch.setattr(kt.jax, "devices", lambda *a: [_FakeTpu()])
    kt._save_disk()
    assert json.loads(cache.read_text())

    # fresh in-memory state: disk cache must be honored before any search
    monkeypatch.setattr(kt, "_memory_cache", {})
    monkeypatch.setattr(kt, "_disk_loaded", False)

    def boom(*a, **k):  # noqa: ANN001
        raise AssertionError("cached shape must not re-tune")

    got = kt.tune(1536, 1536, 64, False, 0.0, boom, (512, 512))
    assert got == (256, 512)

    # a DIFFERENT device kind must not see that cache entry (falls back
    # to the heuristic rather than searching, since boom cannot compile)
    class OtherTpu(_FakeTpu):
        device_kind = "faketpu v1"

    monkeypatch.setattr(kt.jax, "devices", lambda *a: [OtherTpu()])

    def heuristic_only(*a, **k):  # noqa: ANN001
        raise RuntimeError("no kernels on this backend")

    got2 = kt.tune(1536, 1536, 64, False, 0.0, heuristic_only, (512, 512))
    assert got2 == (512, 512)


def test_tuner_version_bump_invalidates_cache(monkeypatch, tmp_path):
    """VERDICT r5 item 6: winners persist to disk indefinitely, so a
    ranking produced by an older tuner must not survive a tuner upgrade
    — the cache key carries TUNER_VERSION, and a bump forces re-tune."""
    assert f"v{kt.TUNER_VERSION}|" in kt._key(
        1536, 1536, 64, False, 0.0, _FakeTpu.device_kind)

    cache = tmp_path / "cache.json"
    monkeypatch.setattr(kt, "_cache_path", lambda: str(cache))
    monkeypatch.setattr(kt.jax, "devices", lambda *a: [_FakeTpu()])
    # a winner cached by the CURRENT tuner version...
    key = kt._key(1536, 1536, 64, False, 0.0, _FakeTpu.device_kind)
    monkeypatch.setattr(kt, "_memory_cache", {key: [256, 512]})
    monkeypatch.setattr(kt, "_disk_loaded", True)
    kt._save_disk()

    def boom(*a, **k):  # noqa: ANN001
        raise AssertionError("same-version cached shape must not re-tune")

    monkeypatch.setattr(kt, "_memory_cache", {})
    monkeypatch.setattr(kt, "_disk_loaded", False)
    assert kt.tune(1536, 1536, 64, False, 0.0, boom, (512, 512)) == (256, 512)

    # ...is INVISIBLE to a bumped tuner: the stale entry is ignored and
    # the search runs again (falls back to the heuristic here, since no
    # candidate can compile on this fake backend)
    monkeypatch.setattr(kt, "TUNER_VERSION", kt.TUNER_VERSION + 1)
    monkeypatch.setattr(kt, "_memory_cache", {})
    monkeypatch.setattr(kt, "_disk_loaded", False)

    def no_compile(*a, **k):  # noqa: ANN001
        raise RuntimeError("no kernels on this backend")

    got = kt.tune(1536, 1536, 64, False, 0.0, no_compile, (512, 512))
    assert got == (512, 512)  # re-tuned (heuristic fallback), not [256, 512]


@pytest.mark.tpu
def test_tune_searches_on_chip(monkeypatch, tmp_path):
    """First-use micro-search on the real chip for an un-anchored shape:
    returns a legal candidate, caches it, and the tuned geometry is not
    slower than ~5% vs the heuristic would require a perf harness — here
    the gate is that the search completes, returns a valid divisor pair,
    and a second call is a cache hit (no recompiles)."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    monkeypatch.setattr(kt, "_cache_path",
                        lambda: str(tmp_path / "cache.json"))
    monkeypatch.setattr(kt, "_memory_cache", {})
    monkeypatch.setattr(kt, "_disk_loaded", False)

    s = 1536  # off the anchored grid → triggers the search
    got = kt.tune(s, s, 64, False, 0.0, flash_attention, (512, 512), bh=4)
    assert s % got[0] == 0 and s % got[1] == 0

    def boom(*a, **k):  # noqa: ANN001
        raise AssertionError("second call must hit the cache")

    again = kt.tune(s, s, 64, False, 0.0, boom, (512, 512))
    assert tuple(again) == tuple(got)
    data = json.loads((tmp_path / "cache.json").read_text())
    assert list(data.values())[0] == list(got)
