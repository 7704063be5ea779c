"""kernel=auto with a multi-rung ladder: one job can (correctly) run
DIFFERENT kernels for different bucket widths — sub-64 buckets take the
XLA scorer, 64+ device-dedup buckets take Pallas (interpret mode on the
CPU test rig). The round-5 per-bucket resolution must hold inside one
training run: same data, mixed dispatch, finite converging loss, and
byte-equal results vs forcing each kernel globally would differ — so
instead we pin that the mixed run equals a run where each batch's
kernel is resolved the same way manually."""

import dataclasses

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import batch_iterator
from fast_tffm_tpu.models.fm import (ModelSpec, batch_args,
                                     init_accumulator, init_table,
                                     make_train_step, resolved_kernel)


def _lines(rng, n, nnz_lo, nnz_hi, vocab):
    out = []
    for _ in range(n):
        ids = rng.choice(vocab, size=int(rng.integers(nnz_lo, nnz_hi)),
                         replace=False)
        out.append(" ".join(["1" if rng.random() < 0.5 else "0"]
                            + [f"{i}:1" for i in sorted(ids)]))
    return out


def test_one_job_spans_both_kernel_regimes(tmp_path, rng):
    vocab = 512
    # alternate sparse stretches (bucket 32 -> xla) with dense ones
    # (bucket 64 -> pallas under device dedup)
    lines = []
    for block in range(6):
        lo, hi = ((2, 8) if block % 2 == 0 else (40, 60))
        lines.extend(_lines(rng, 32, lo, hi, vocab))
    data = tmp_path / "mix.txt"
    data.write_text("\n".join(lines) + "\n")
    cfg = FmConfig(vocabulary_size=vocab, factor_num=4, batch_size=32,
                   shuffle=False, kernel="auto", dedup="device",
                   max_features_per_example=64, bucket_ladder=(32, 64),
                   learning_rate=0.1,
                   model_file=str(tmp_path / "m" / "fm"))
    spec = ModelSpec.from_config(cfg)
    # On the CPU rig from_config resolves auto -> xla; force the
    # TPU-side behavior (auto survives) to exercise mixed dispatch.
    spec = dataclasses.replace(spec, kernel="auto")
    step = make_train_step(spec)
    table, acc = init_table(cfg), init_accumulator(cfg)
    seen_L = set()
    losses = []
    for batch in batch_iterator(cfg, [str(data)], training=True,
                                epochs=1, raw_ids=True):
        L = batch.vals.shape[-1]
        seen_L.add(L)
        table, acc, loss, _ = step(table, acc, **batch_args(batch))
        losses.append(float(loss))
    assert {32, 64} <= seen_L, seen_L
    assert {resolved_kernel(spec, L) for L in seen_L} == {"xla",
                                                         "pallas"}
    assert np.isfinite(losses).all()
    # parity: the same run with each batch's kernel forced explicitly
    # to what resolution picked must be bit-identical
    table2, acc2 = init_table(cfg), init_accumulator(cfg)
    steps = {k: make_train_step(dataclasses.replace(spec, kernel=k))
             for k in ("xla", "pallas")}
    losses2 = []
    for batch in batch_iterator(cfg, [str(data)], training=True,
                                epochs=1, raw_ids=True):
        k = resolved_kernel(spec, batch.vals.shape[-1])
        table2, acc2, loss, _ = steps[k](table2, acc2,
                                         **batch_args(batch))
        losses2.append(float(loss))
    np.testing.assert_array_equal(np.asarray(losses),
                                  np.asarray(losses2))
    np.testing.assert_array_equal(np.asarray(table),
                                  np.asarray(table2))


@pytest.mark.parametrize("L", [24, 40, 80])
def test_an_explicit_pallas_step_runs_at_a_rung_that_is_no_power_of_two(
        tmp_path, rng, L):
    """ISSUE 34: the default ladder's rungs are multiples of 8, not
    powers of two. ``kernel = pallas`` with ``dedup = device`` at such a
    width runs (the kernel's blocks take the whole of L) and agrees
    with the XLA step; ``auto`` still draws its line at 64."""
    vocab = 512
    data = tmp_path / "d.txt"
    data.write_text("\n".join(_lines(rng, 32, L - 6, L, vocab)) + "\n")
    cfg = FmConfig(vocabulary_size=vocab, factor_num=4, batch_size=32,
                   shuffle=False, kernel="pallas", dedup="device",
                   max_features_per_example=L, learning_rate=0.1,
                   model_file=str(tmp_path / "m" / "fm"))
    assert L in cfg.bucket_ladder
    spec = ModelSpec.from_config(cfg)
    assert (spec.kernel, spec.dedup) == ("pallas", "device")
    batch, = batch_iterator(cfg, [str(data)], training=True, epochs=1,
                            raw_ids=True)
    assert batch.vals.shape == (32, L)
    auto = dataclasses.replace(spec, kernel="auto")
    assert resolved_kernel(auto, L) == ("pallas" if L >= 64 else "xla")
    out = {}
    for k in ("pallas", "xla"):
        step = make_train_step(dataclasses.replace(spec, kernel=k))
        out[k] = step(init_table(cfg), init_accumulator(cfg),
                      **batch_args(batch))
    np.testing.assert_allclose(float(out["pallas"][2]),
                               float(out["xla"][2]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out["pallas"][0]),
                               np.asarray(out["xla"][0]),
                               rtol=1e-4, atol=1e-6)
