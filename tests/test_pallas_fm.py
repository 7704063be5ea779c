"""Pallas fused FM kernel == XLA path, values and gradients.

Runs in interpret mode on the CPU mesh (the kernel compiles for real on
TPU; chip_smoke.py exercises that). Parity tolerances are tight
because both paths accumulate in f32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.ops.interaction import fm_batch_scores
from fast_tffm_tpu.ops.pallas_fm import fm_batch_scores_pallas


def _rand_case(rng, B=64, L=16, U=128, K=8):
    params = jnp.asarray(rng.normal(size=(U, K + 1)) * 0.1,
                         dtype=jnp.float32)
    local_idx = jnp.asarray(rng.integers(0, U, size=(B, L)), dtype=jnp.int32)
    vals = jnp.asarray(rng.random(size=(B, L)) *
                       (rng.random(size=(B, L)) > 0.3),  # real padding zeros
                       dtype=jnp.float32)
    return params, local_idx, vals


@pytest.mark.parametrize("shape", [(64, 16, 128, 8), (32, 64, 512, 4),
                                   (8, 8, 16, 16)])
def test_forward_parity(rng, shape):
    B, L, U, K = shape
    params, idx, vals = _rand_case(rng, B, L, U, K)
    ref = fm_batch_scores(params, idx, vals)
    out = fm_batch_scores_pallas(params, idx, vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_gradient_parity(rng):
    params, idx, vals = _rand_case(rng)

    def loss_ref(p, v):
        return jnp.sum(jnp.tanh(fm_batch_scores(p, idx, v)))

    def loss_pal(p, v):
        return jnp.sum(jnp.tanh(fm_batch_scores_pallas(p, idx, v)))

    gp_ref, gv_ref = jax.grad(loss_ref, argnums=(0, 1))(params, vals)
    gp_pal, gv_pal = jax.grad(loss_pal, argnums=(0, 1))(params, vals)
    np.testing.assert_allclose(np.asarray(gp_pal), np.asarray(gp_ref),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gv_pal), np.asarray(gv_ref),
                               rtol=1e-4, atol=1e-6)


def test_jit_and_odd_batch_blocks(rng):
    # B with a small power-of-two factor exercises the block chooser.
    params, idx, vals = _rand_case(rng, B=24, L=8, U=64, K=8)
    f = jax.jit(fm_batch_scores_pallas)
    out = f(params, idx, vals)
    ref = fm_batch_scores(params, idx, vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_train_step_with_pallas_kernel(tmp_path):
    """End-to-end: ModelSpec(kernel='pallas') trains and matches the XLA
    kernel's losses step for step."""
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.data.pipeline import batch_iterator
    from fast_tffm_tpu.models.fm import (ModelSpec, batch_args,
                                         init_accumulator, init_table,
                                         make_train_step)
    rng = np.random.default_rng(0)
    lines = []
    for _ in range(64):
        nnz = rng.integers(1, 10)
        ids = rng.choice(64, size=nnz, replace=False)
        lines.append(" ".join(["1" if rng.random() < 0.5 else "0"]
                              + [f"{i}:{rng.random():.3f}" for i in ids]))
    p = tmp_path / "t.txt"
    p.write_text("\n".join(lines) + "\n")
    base = dict(vocabulary_size=64, factor_num=4, batch_size=16,
                train_files=(str(p),), shuffle=False, learning_rate=0.1)
    cfg_x = FmConfig(**base, kernel="xla")
    cfg_p = FmConfig(**base, kernel="pallas")
    states = {}
    for cfg in (cfg_x, cfg_p):
        spec = ModelSpec.from_config(cfg)
        table, acc = init_table(cfg, 0), init_accumulator(cfg)
        step = make_train_step(spec)
        losses = []
        for batch in batch_iterator(cfg, cfg.train_files, training=True):
            table, acc, loss, _ = step(table, acc, **batch_args(batch))
            losses.append(float(loss))
        states[cfg.kernel] = (np.asarray(table), losses)
    np.testing.assert_allclose(states["pallas"][1], states["xla"][1],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(states["pallas"][0], states["xla"][0],
                               rtol=1e-4, atol=1e-6)
