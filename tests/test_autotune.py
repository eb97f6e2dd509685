"""Kernel autotuner (reference phi/kernels/autotune: AutoTuneBase::Run +
AutoTuneCache serialization)."""
import json
import os

import numpy as np
import pytest

from paddle_infer_tpu.framework.flags import set_flags
from paddle_infer_tpu.ops.pallas import autotune as at


@pytest.fixture(autouse=True)
def _reset(tmp_path):
    at.clear()
    at._LOADED = True      # don't read ambient cache files
    # winners persist to the checkout by default; tests write elsewhere
    set_flags({"autotune_cache_file": str(tmp_path / "ambient.json")})
    yield
    set_flags({"autotune_cache_file": ""})
    at.clear()


def test_disabled_off_tpu_returns_default(monkeypatch):
    # CPU backend in tests -> disabled -> default wins untouched
    calls = []
    out = at.autotune("k", (512, 512), [(256, 256)],
                      lambda c: calls.append(c) or 1.0)
    assert out == (512, 512)
    assert calls == []


def test_challenger_must_beat_incumbent_by_margin(monkeypatch):
    monkeypatch.setattr(at, "enabled", lambda: True)
    times = {(512, 512): 1.00, (256, 256): 0.98, (128, 128): 0.90}
    out = at.autotune("k1", (512, 512), list(times),
                      lambda c: times[c])
    assert out == (128, 128)     # >3% better
    # 2% better challenger does NOT displace the incumbent
    times2 = {(512, 512): 1.00, (256, 256): 0.98}
    out = at.autotune("k2", (512, 512), list(times2),
                      lambda c: times2[c])
    assert out == (512, 512)


def test_cache_hit_skips_measurement(monkeypatch):
    monkeypatch.setattr(at, "enabled", lambda: True)
    calls = []

    def measure(c):
        calls.append(c)
        return 0.5 if c == (256, 256) else 1.0

    assert at.autotune("k", (512, 512), [(256, 256)], measure) \
        == (256, 256)
    n = len(calls)
    assert at.autotune("k", (512, 512), [(256, 256)], measure) \
        == (256, 256)
    assert len(calls) == n       # second call answered from cache


def test_invalid_candidate_skipped(monkeypatch):
    monkeypatch.setattr(at, "enabled", lambda: True)

    def measure(c):
        if c == (999, 999):
            raise ValueError("doesn't fit")
        return {(512, 512): 1.0, (256, 256): 0.5}[c]

    out = at.autotune("k", (512, 512), [(999, 999), (256, 256)], measure)
    assert out == (256, 256)


def test_failing_incumbent_propagates(monkeypatch):
    """The default is what ships: if it cannot compile, the caller sees
    that error instead of silently running an untested configuration."""
    monkeypatch.setattr(at, "enabled", lambda: True)

    def measure(c):
        if c == (512, 512):
            raise RuntimeError("Mosaic refused the default")
        return 0.5

    with pytest.raises(RuntimeError, match="refused the default"):
        at.autotune("k", (512, 512), [(256, 256)], measure)


def test_default_cache_file_is_fixed_in_checkout():
    set_flags({"autotune_cache_file": ""})
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert at._cache_path() == os.path.join(root, ".autotune_cache.json")


def test_persistence_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setattr(at, "enabled", lambda: True)
    cache_file = str(tmp_path / "tune.json")
    set_flags({"autotune_cache_file": cache_file})
    try:
        at.autotune("persist_k", (512, 512), [(256, 256)],
                    lambda c: 0.1 if c == (256, 256) else 1.0)
        with open(cache_file) as f:
            disk = json.load(f)
        # keyed by chip generation, so a carried file cannot answer for
        # another device
        assert disk == {"cpu|persist_k": [256, 256]}
        # a fresh process state loads the winner without measuring
        at.clear()
        at._LOADED = False
        out = at.autotune("persist_k", (512, 512), [(256, 256)],
                          lambda c: (_ for _ in ()).throw(AssertionError))
        assert out == (256, 256)
    finally:
        set_flags({"autotune_cache_file": ""})
