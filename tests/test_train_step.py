"""The jitted train step vs the oracle: gradients (finite differences) and
a full Adagrad update on touched rows; loss decreases on a learnable toy
problem."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import make_device_batch
from fast_tffm_tpu.data.parser import parse_lines
from fast_tffm_tpu.models import oracle
from fast_tffm_tpu.models.fm import (ModelSpec, TrainStep, batch_args,
                                     grad_body, init_accumulator,
                                     init_table, make_train_step,
                                     sparse_adagrad_apply)
from fast_tffm_tpu.ops import gather_rows
from fast_tffm_tpu.ops.pair_scatter import pair_scatter_add

V, K = 30, 3
CFG = FmConfig(vocabulary_size=V, factor_num=K, batch_size=4,
               bucket_ladder=(4, 8), learning_rate=0.1,
               factor_lambda=0.01, bias_lambda=0.02, adagrad_init=0.1)


def toy_batch():
    lines = ["1 3:0.5 7:1.0 9:2.0", "0 3:1.0 12:0.5", "1 20:1.0",
             "0 7:0.25 20:1.0"]
    block = parse_lines(lines, V)
    batch = [([3, 7, 9], [0.5, 1.0, 2.0]), ([3, 12], [1.0, 0.5]),
             ([20], [1.0]), ([7, 20], [0.25, 1.0])]
    labels = np.array([1.0, 0.0, 1.0, 0.0])
    return make_device_batch(block, CFG), batch, labels


def scatter_dense(uniq_ids, grad_rows, num_rows):
    g = np.zeros((num_rows, grad_rows.shape[1]), dtype=np.float64)
    for u, row in zip(uniq_ids, grad_rows):
        if u < V:
            g[u] += row
    return g


def test_step_matches_oracle_adagrad():
    spec = ModelSpec.from_config(CFG)
    table0 = np.asarray(init_table(CFG, seed=1))
    acc0 = np.asarray(init_accumulator(CFG))
    b, batch, labels = toy_batch()

    step = make_train_step(spec)
    t1, a1, loss, scores = step(jax.numpy.asarray(table0),
                                jax.numpy.asarray(acc0), **batch_args(b))
    t1, a1 = np.asarray(t1), np.asarray(a1)

    # oracle: dense FD grad -> dense adagrad
    g = oracle.grad_fd(table0[:-1].astype(np.float64), batch, labels,
                       factor_lambda=CFG.factor_lambda,
                       bias_lambda=CFG.bias_lambda)
    want_t, want_a = oracle.adagrad_step(
        table0[:-1].astype(np.float64), acc0[:-1].astype(np.float64), g,
        CFG.learning_rate)

    np.testing.assert_allclose(t1[:-1], want_t, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(a1[:-1], want_a, rtol=2e-3, atol=2e-4)
    # the dead padding row never moves
    np.testing.assert_array_equal(t1[-1], 0.0)
    np.testing.assert_allclose(a1[-1], CFG.adagrad_init)

    # loss value matches oracle
    s = oracle.batch_scores(table0[:-1].astype(np.float64), batch)
    want_loss = (oracle.logistic_loss(s, labels)
                 + oracle.regularization(table0[:-1].astype(np.float64),
                                         batch, CFG.factor_lambda,
                                         CFG.bias_lambda))
    assert float(loss) == pytest.approx(want_loss, rel=1e-4)


def test_untouched_rows_unchanged():
    spec = ModelSpec.from_config(CFG)
    table0 = np.asarray(init_table(CFG, seed=1))
    acc0 = np.asarray(init_accumulator(CFG))
    b, batch, _ = toy_batch()
    step = make_train_step(spec)
    t1, a1, _, _ = step(jax.numpy.asarray(table0), jax.numpy.asarray(acc0),
                        **batch_args(b))
    touched = {3, 7, 9, 12, 20}
    untouched = [i for i in range(V) if i not in touched]
    np.testing.assert_array_equal(np.asarray(t1)[untouched],
                                  table0[untouched])
    np.testing.assert_array_equal(np.asarray(a1)[untouched],
                                  acc0[untouched])


def test_zero_weight_examples_do_not_train():
    spec = ModelSpec.from_config(CFG)
    table0 = init_table(CFG, seed=2)
    acc0 = init_accumulator(CFG)
    # batch of 1 real + 3 dummies: only ids {5} may change
    block = parse_lines(["1 5:1.0"], V)
    b = make_device_batch(block, CFG)
    step = make_train_step(spec)
    t1, _, _, _ = step(table0, acc0, **batch_args(b))
    t0, t1 = np.asarray(init_table(CFG, seed=2)), np.asarray(t1)
    changed = np.where(np.any(t0 != t1, axis=1))[0]
    assert changed.tolist() == [5]


def test_fractional_weights_keep_weighted_mean_loss():
    """A batch whose TOTAL weight is in (0, 1) must still get the
    weighted-MEAN data loss the docstring promises: the old floor of
    1.0 on sum(w) silently rescaled loss and gradients by the batch's
    weight mass for fractional weight_files (review finding). Scaling
    all weights by a constant must leave the data loss unchanged."""
    import dataclasses
    spec = dataclasses.replace(ModelSpec.from_config(CFG),
                               factor_lambda=0.0, bias_lambda=0.0)
    block = parse_lines(["1 5:1.0 7:0.5", "0 9:2.0"], V)
    step = make_train_step(spec)
    losses = []
    for scale in (1.0, 0.1):  # sum(w) = 2.0 vs 0.2 (< 1.0)
        b = make_device_batch(block, CFG)
        args = batch_args(b)
        args["weights"] = np.asarray(args["weights"]) * scale
        # fresh state per call: the step donates table/acc
        _, _, loss, _ = step(init_table(CFG, seed=4),
                             init_accumulator(CFG), **args)
        losses.append(float(loss))
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)


def test_loss_decreases_on_toy_problem():
    rng = np.random.default_rng(0)
    spec = ModelSpec.from_config(CFG)
    table = init_table(CFG, seed=3)
    acc = init_accumulator(CFG)
    step = make_train_step(spec)
    # learnable rule: label = 1 iff feature 1 present (else feature 2)
    lines = []
    for _ in range(64):
        y = int(rng.integers(0, 2))
        fid = 1 if y else 2
        extra = int(rng.integers(10, 20))
        lines.append(f"{y} {fid}:1 {extra}:1")
    losses = []
    for epoch in range(15):
        for i in range(0, 64, 4):
            block = parse_lines(lines[i:i + 4], V)
            b = make_device_batch(block, CFG)
            table, acc, loss, _ = step(table, acc, **batch_args(b))
            losses.append(float(loss))
    assert np.mean(losses[-16:]) < 0.55 * np.mean(losses[:16])


def test_ffm_step_matches_fd_oracle():
    """FFM backward (jax.grad through the field-bucketed interaction)
    against dense finite differences of oracle.ffm_score + loss + reg,
    pushed through one Adagrad step — the FFM analogue of
    test_step_matches_oracle_adagrad."""
    Vf, F, Kf = 16, 3, 2
    cfg = FmConfig(vocabulary_size=Vf, factor_num=Kf, model_type="ffm",
                   field_num=F, batch_size=4, bucket_ladder=(4, 8),
                   learning_rate=0.1, factor_lambda=0.01, bias_lambda=0.02,
                   adagrad_init=0.1)
    spec = ModelSpec.from_config(cfg)
    lines = ["1 0:3:0.5 1:7:1.0 2:9:2.0", "0 0:3:1.0 2:12:0.5",
             "1 1:15:1.0", "0 2:7:0.25 0:15:1.0"]
    batch = [([3, 7, 9], [0, 1, 2], [0.5, 1.0, 2.0]),
             ([3, 12], [0, 2], [1.0, 0.5]),
             ([15], [1], [1.0]),
             ([7, 15], [2, 0], [0.25, 1.0])]
    labels = np.array([1.0, 0.0, 1.0, 0.0])
    block = parse_lines(lines, Vf, field_aware=True, field_num=F)
    b = make_device_batch(block, cfg)

    table0 = np.asarray(init_table(cfg, seed=4))
    acc0 = np.asarray(init_accumulator(cfg))
    step = make_train_step(spec)
    t1, a1, loss, _ = step(jax.numpy.asarray(table0),
                           jax.numpy.asarray(acc0), **batch_args(b))
    t1 = np.asarray(t1)

    t64 = table0[:-1].astype(np.float64)

    def total(t):
        s = np.array([oracle.ffm_score(t, F, ids, flds, vals)
                      for ids, flds, vals in batch])
        uniq = np.unique(np.concatenate([ids for ids, _, _ in batch]))
        v, w = t[uniq, :-1], t[uniq, -1]
        return (oracle.logistic_loss(s, labels)
                + cfg.factor_lambda * np.sum(v * v)
                + cfg.bias_lambda * np.sum(w * w))

    eps = 1e-5
    g = np.zeros_like(t64)
    touched = np.unique(np.concatenate([ids for ids, _, _ in batch]))
    for r in touched:
        for c in range(t64.shape[1]):
            t = t64.copy()
            t[r, c] += eps
            up = total(t)
            t[r, c] -= 2 * eps
            g[r, c] = (up - total(t)) / (2 * eps)

    want_t, _ = oracle.adagrad_step(t64, acc0[:-1].astype(np.float64), g,
                                    cfg.learning_rate)
    np.testing.assert_allclose(t1[:-1], want_t, rtol=2e-3, atol=2e-4)
    assert float(loss) == pytest.approx(total(t64), rel=1e-4)


# ---- the update is one pass over the slots (ISSUE 38) -------------------

def three_visits(table, acc, uniq_ids, grad_rows, lr):
    """``sparse_adagrad_apply`` as it stood until PR 38: a scatter-add,
    a gather of the same rows and a scatter-add. The scatter-adds drop
    an index past the block and the gather clamps it."""
    acc = acc.at[uniq_ids].add(jnp.square(grad_rows))
    upd = -lr * grad_rows * lax.rsqrt(acc[uniq_ids])
    return table.at[uniq_ids].add(upd), acc


R, U_SLOTS, LR = 257, 64, 0.05


def slots(case, rng, dim):
    """``(ids, grad_rows, rows that must change)``: U slots over a
    block of R rows whose last row is the dead one."""
    ids = rng.choice(R - 1, size=U_SLOTS, replace=False).astype(np.int32)
    grad = rng.normal(size=(U_SLOTS, dim)).astype(np.float32)
    if case == "pad_slots":         # the batch's tail: dead row, zero grad
        ids[-20:] = R - 1
        grad[-20:] = 0.0
    elif case == "past_the_block":  # another shard's rows (_block_index)
        ids[::3] = R
        ids[1] = R + 1000
    return ids, grad, np.unique(ids[ids < R - 1])


@pytest.mark.parametrize("dim", [9, 17, 89])
@pytest.mark.parametrize("case", ["distinct", "pad_slots",
                                  "past_the_block"])
def test_one_pass_equals_the_three_visits(case, dim):
    """Accumulator exactly and table to two ulps of the largest of the
    row's value, before or after, and its update (XLA's CPU rounds
    ``rsqrt`` of the gathered sum and the add of the update once each
    otherwise than in the three-visit program's fusions); a row no slot
    names, the dead row under repeated zero-gradient slots and
    everything an index past the block would touch stay bit for bit
    what they were."""
    rng = np.random.default_rng(dim)
    table = rng.normal(size=(R, dim)).astype(np.float32)
    acc = (0.1 + rng.random((R, dim))).astype(np.float32)
    ids, grad, named = slots(case, rng, dim)
    got_t, got_a = jax.jit(sparse_adagrad_apply, static_argnums=4)(
        table, acc, ids, grad, LR)
    want_t, want_a = jax.jit(three_visits, static_argnums=4)(
        table, acc, ids, grad, LR)
    got_t, got_a = np.asarray(got_t), np.asarray(got_a)
    np.testing.assert_array_equal(got_a, np.asarray(want_a))
    want_t = np.asarray(want_t)
    ulp = np.spacing(np.maximum(np.maximum(np.abs(table), np.abs(want_t)),
                                np.abs(want_t - table)))
    assert (np.abs(got_t - want_t) <= 2 * ulp).all()
    assert (got_t[named] != table[named]).any(axis=1).all()
    assert (got_a[named] > acc[named]).all()
    rest = np.setdiff1d(np.arange(R), named)
    assert R - 1 in rest
    assert got_t[rest].tobytes() == table[rest].tobytes()
    assert got_a[rest].tobytes() == acc[rest].tobytes()


def test_the_eager_call_is_the_jitted_one():
    rng = np.random.default_rng(1)
    table = rng.normal(size=(R, 5)).astype(np.float32)
    acc = np.full((R, 5), 0.1, np.float32)
    ids, grad, _ = slots("pad_slots", rng, 5)
    eager = sparse_adagrad_apply(jnp.asarray(table), jnp.asarray(acc),
                                 jnp.asarray(ids), jnp.asarray(grad), LR)
    jitted = jax.jit(sparse_adagrad_apply, static_argnums=4)(
        table, acc, ids, grad, LR)
    for a, b in zip(eager, jitted):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("transform", ["grad", "vmap"])
def test_no_rule_for_autodiff_or_batching_fails_by_name(transform):
    table, acc = jnp.ones((8, 3)), jnp.ones((8, 3))
    ids, grad = jnp.arange(4, dtype=jnp.int32), jnp.ones((4, 3))
    if transform == "grad":
        f = jax.grad(lambda t: pair_scatter_add(t, acc, ids, grad, grad)[0]
                     .sum())
        args = (table,)
    else:
        f = jax.vmap(lambda g: pair_scatter_add(table, acc, ids, g, g))
        args = (jnp.ones((2, 4, 3)),)
    # jax's one-operand scatter-add has both rules: the name in the
    # error is this primitive's (ops/pair_scatter.py says why it is)
    with pytest.raises(NotImplementedError, match="'scatter-add'"):
        f(*args)


def test_four_steps_equal_the_three_visit_formulas_four_steps():
    """``TrainStep`` against the same step with the update written out
    as three operations, on four different batches."""
    spec = ModelSpec.from_config(CFG)

    def plain(table, acc, labels, weights, uniq_ids, local_idx, vals):
        loss, _, grad = grad_body(spec, gather_rows(table, uniq_ids),
                                  labels, weights, uniq_ids, local_idx,
                                  vals)
        return (*three_visits(table, acc, uniq_ids, grad,
                              spec.learning_rate), loss)
    plain = jax.jit(plain)
    step = TrainStep(spec)
    rng = np.random.default_rng(8)
    table, acc = init_table(CFG, seed=2), init_accumulator(CFG)
    want_t, want_a = init_table(CFG, seed=2), init_accumulator(CFG)
    for _ in range(4):
        lines = [f"{int(rng.integers(0, 2))} " + " ".join(
            f"{i}:{rng.random() + 0.1:.3f}"
            for i in rng.choice(V, size=int(rng.integers(1, 5)),
                                replace=False)) for _ in range(4)]
        args = batch_args(make_device_batch(parse_lines(lines, V), CFG))
        table, acc, loss, _ = step(table, acc, **args)
        want_t, want_a, want_loss = plain(want_t, want_a, **args)
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    np.testing.assert_allclose(np.asarray(table), np.asarray(want_t),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want_a),
                               rtol=1e-6, atol=1e-7)
    assert (np.asarray(acc) != np.asarray(init_accumulator(CFG))).any()


def test_the_mesh_step_drops_a_slot_whose_row_another_shard_holds():
    """Four virtual devices, 2,048 rows a shard. Segment 0 of the feed
    names a row of shard 1 (a feed cut wrongly: the step cannot see
    it): its update is dropped, table and accumulator row bit for bit
    as they were; nothing clamps the index onto shard 0's last row any
    more, which stays as it was too, and the rows in their own
    segments move."""
    from fast_tffm_tpu.parallel.sharded import (make_mesh,
                                                make_sharded_train_step,
                                                shard_batch)
    spec = ModelSpec(model_type="fm", order=2, factor_num=4, field_num=0,
                     vocabulary_size=8191, loss_type="logistic",
                     factor_lambda=1e-4, bias_lambda=1e-4,
                     learning_rate=0.1, kernel="xla", dedup="host")
    mesh = make_mesh(jax.devices()[:4], model_axis=1)
    rng = np.random.default_rng(38)
    B, L, U, shard = 16, 4, 256, 2048
    seg = U // 4
    uniq = np.full(U, 8191, np.int32)
    for s in range(4):
        uniq[s * seg:s * seg + 8] = s * shard + rng.choice(
            shard - 1, size=8, replace=False)
    stray = shard + 77
    assert stray not in uniq
    uniq[8] = stray                         # segment 0, shard 1's row
    real = np.flatnonzero(uniq != 8191)
    local_idx = rng.choice(real, size=(B, L)).astype(np.int32)
    local_idx[0, 0] = 8                     # the stray slot has a gradient
    table0 = rng.normal(scale=0.1, size=(8192, spec.row_dim)).astype(
        np.float32)
    acc0 = np.full((8192, spec.row_dim), 0.1, np.float32)
    step = make_sharded_train_step(spec, mesh)
    table, acc, loss, _ = step(
        jnp.asarray(table0), jnp.asarray(acc0), **shard_batch(
            mesh, labels=(rng.random(B) < 0.5).astype(np.float32),
            weights=np.ones(B, np.float32), uniq_ids=uniq,
            local_idx=local_idx,
            vals=(rng.random((B, L)) + 0.1).astype(np.float32)))
    table, acc = np.asarray(table), np.asarray(acc)
    assert np.isfinite(float(loss))
    for row in (stray, shard - 1):
        assert table[row].tobytes() == table0[row].tobytes()
        assert acc[row].tobytes() == acc0[row].tobytes()
    moved = np.setdiff1d(uniq[local_idx], [stray])
    assert (acc[moved] > acc0[moved]).any(axis=1).all()
    assert (table[moved] != table0[moved]).any(axis=1).all()
    # (the regulariser alone moves a row that no cell names)
    rest = np.setdiff1d(np.arange(8192), np.setdiff1d(uniq[real], [stray]))
    assert table[rest].tobytes() == table0[rest].tobytes()
    assert acc[rest].tobytes() == acc0[rest].tobytes()
