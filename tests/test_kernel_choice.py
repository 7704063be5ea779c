"""kernel=auto must follow the measured (L, dedup) regime matrix
(ops/kernel_choice.py), not a blanket Pallas-on-TPU rule —
round-4 review: the old policy picked a measured-slower kernel in half
the matrix's cells (Pallas 0.67x XLA at L=48/dedup=device)."""

import dataclasses

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.models.fm import ModelSpec, resolved_kernel
from fast_tffm_tpu.ops.kernel_choice import auto_kernel


def test_auto_kernel_matrix_cells():
    # the four measured cells, verbatim
    assert auto_kernel("device", 48) == "xla"     # 0.67x cell
    assert auto_kernel("host", 48) == "xla"       # 0.94x
    assert auto_kernel("host", 64) == "xla"       # 0.87x
    assert auto_kernel("device", 64) == "pallas"  # 1.42x
    # extrapolation: sub-tile widths never pick pallas; larger
    # device-dedup buckets keep the winner
    assert auto_kernel("device", 32) == "xla"
    assert auto_kernel("device", 128) == "pallas"
    assert auto_kernel("host", 256) == "xla"


def _spec(**kw):
    base = dict(model_type="fm", order=2, factor_num=8, field_num=0,
                vocabulary_size=1024, loss_type="logistic",
                factor_lambda=0.0, bias_lambda=0.0, learning_rate=0.01,
                kernel="auto", dedup="device")
    base.update(kw)
    return ModelSpec(**base)


def test_resolved_kernel_policy():
    s = _spec()
    assert resolved_kernel(s, 48) == "xla"
    assert resolved_kernel(s, 64) == "pallas"
    assert resolved_kernel(_spec(dedup="host"), 64) == "xla"
    # explicit config always beats the matrix
    assert resolved_kernel(_spec(kernel="pallas"), 48) == "pallas"
    assert resolved_kernel(_spec(kernel="xla"), 64) == "xla"
    # non-2nd-order / ffm never run the pallas kernel
    assert resolved_kernel(_spec(order=3, kernel="pallas"), 64) == "xla"
    assert resolved_kernel(
        _spec(model_type="ffm", field_num=4, kernel="pallas"), 64) == "xla"


def test_from_config_keeps_auto_only_on_tpu(monkeypatch):
    import jax
    # CPU backend (the test env): auto resolves to xla at config time
    assert ModelSpec.from_config(FmConfig()).kernel == "xla"
    # TPU backend: auto SURVIVES so _scores can decide per bucket
    import fast_tffm_tpu.models.fm as fm_mod
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ModelSpec.from_config(FmConfig()).kernel == "auto"
    # ...but not where the fused kernel doesn't apply
    assert ModelSpec.from_config(FmConfig(order=3)).kernel == "xla"


def test_scores_dispatch_follows_resolution(monkeypatch):
    """The trace-time dispatch in _scores must route through
    resolved_kernel — pin it by intercepting the pallas entry point."""
    import fast_tffm_tpu.ops.pallas_fm as pallas_mod
    from fast_tffm_tpu.models.fm import _scores
    calls = []
    real = pallas_mod.fm_batch_scores_pallas

    def spy(*a, **k):
        calls.append(True)
        return real(*a, **k)

    monkeypatch.setattr(pallas_mod, "fm_batch_scores_pallas", spy)
    U, D = 16, 9
    gathered = np.random.default_rng(0).normal(
        size=(U, D)).astype(np.float32)
    for L, expect_pallas in ((48, False), (64, True)):
        calls.clear()
        local_idx = np.zeros((4, L), np.int32)
        vals = np.zeros((4, L), np.float32)
        _scores(_spec(), gathered, local_idx, vals, None)
        assert bool(calls) == expect_pallas, (L, calls)
