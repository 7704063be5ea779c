"""Device math (ops/interaction.py) vs the NumPy oracle, through the real
pipeline (bucketed padding, host-side unique)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.parser import ParsedBlock, parse_lines
from fast_tffm_tpu.data.pipeline import make_device_batch
from fast_tffm_tpu.models import oracle
from fast_tffm_tpu.ops.interaction import (batch_reg, ffm_batch_scores,
                                           fm_batch_scores, gather_rows)

V, K = 50, 4


def random_batch(rng, n, max_nnz=6, with_fields=False, field_num=3):
    examples, blocks = [], dict(labels=[], poses=[0], ids=[], vals=[],
                                fields=[])
    for _ in range(n):
        nnz = int(rng.integers(1, max_nnz + 1))
        ids = rng.choice(V, size=nnz, replace=False)
        vals = rng.normal(size=nnz)
        blocks["labels"].append(float(rng.integers(0, 2)))
        blocks["ids"].extend(ids.tolist())
        blocks["vals"].extend(vals.tolist())
        blocks["poses"].append(len(blocks["ids"]))
        if with_fields:
            flds = rng.integers(0, field_num, size=nnz)
            blocks["fields"].extend(flds.tolist())
            examples.append((ids.tolist(), flds.tolist(), vals.tolist()))
        else:
            examples.append((ids.tolist(), vals.tolist()))
    block = ParsedBlock(
        labels=np.array(blocks["labels"], np.float32),
        poses=np.array(blocks["poses"], np.int32),
        ids=np.array(blocks["ids"], np.int32),
        vals=np.array(blocks["vals"], np.float32),
        fields=(np.array(blocks["fields"], np.int32) if with_fields
                else None))
    return examples, block


def make_cfg(**kw):
    kw.setdefault("vocabulary_size", V)
    kw.setdefault("factor_num", K)
    kw.setdefault("batch_size", 8)
    kw.setdefault("bucket_ladder", (8,))
    return FmConfig(**kw)


def padded_table(rng, cfg):
    t = rng.normal(size=(cfg.num_rows, cfg.row_dim)).astype(np.float32) * 0.3
    t[-1] = 0.0
    return t


@pytest.mark.parametrize("order", [2, 3])
def test_scores_match_oracle(rng, order):
    cfg = make_cfg(order=order)
    examples, block = random_batch(rng, 5)
    b = make_device_batch(block, cfg)
    table = padded_table(rng, cfg)
    gathered = gather_rows(table, b.uniq_ids)
    got = np.asarray(fm_batch_scores(gathered, b.local_idx, b.vals,
                                     order=order))
    want = oracle.batch_scores(table[:-1].astype(np.float64), examples,
                               order=order)
    np.testing.assert_allclose(got[:b.num_real], want, rtol=2e-4, atol=2e-4)
    # padded dummy examples score exactly 0
    np.testing.assert_array_equal(got[b.num_real:], 0.0)


# (field_num, k, bucket width L): the original small case, the benchmark
# cell's shape (ffm-k4-avazu: 22 fields, k=4, the 32 rung), and a k that
# is no power of two.
FFM_SHAPES = [(3, 4, 8), (22, 4, 32), (5, 3, 16)]


def ffm_oracle_scores(table, field_num, examples):
    return np.array([
        oracle.ffm_score(table[:-1].astype(np.float64), field_num, i, f, x)
        for i, f, x in examples])


@pytest.mark.parametrize("field_num,k,L", FFM_SHAPES)
def test_ffm_scores_match_oracle(rng, field_num, k, L):
    cfg = make_cfg(model_type="ffm", field_num=field_num, factor_num=k,
                   bucket_ladder=(L,))
    examples, block = random_batch(rng, 4, max_nnz=L - 2, with_fields=True,
                                   field_num=field_num)
    b = make_device_batch(block, cfg)
    table = padded_table(rng, cfg)
    gathered = gather_rows(table, b.uniq_ids)
    got = np.asarray(ffm_batch_scores(gathered, field_num, b.local_idx,
                                      b.fields, b.vals))
    want = ffm_oracle_scores(table, field_num, examples)
    np.testing.assert_allclose(got[:b.num_real], want, rtol=2e-4, atol=2e-4)
    # padded dummy examples score exactly 0
    np.testing.assert_array_equal(got[b.num_real:], 0.0)


@pytest.mark.parametrize("fields_of,why", [
    ([[0, 0, 2], [1, 1, 1, 1]], "two features share a field"),
    ([[0, 2], [2]], "a field no feature of the example has"),
])
def test_ffm_scores_crowded_and_empty_fields(rng, fields_of, why):
    field_num = 3
    cfg = make_cfg(model_type="ffm", field_num=field_num)
    examples = [(rng.choice(V, size=len(f), replace=False).tolist(), f,
                 rng.normal(size=len(f)).round(3).tolist())
                for f in fields_of]
    block = parse_lines(
        ["0 " + " ".join(f"{f}:{i}:{x}" for i, f, x in zip(*example))
         for example in examples], V, field_aware=True, field_num=field_num)
    b = make_device_batch(block, cfg)
    table = padded_table(rng, cfg)
    got = np.asarray(ffm_batch_scores(gather_rows(table, b.uniq_ids),
                                      field_num, b.local_idx, b.fields,
                                      b.vals))
    np.testing.assert_allclose(got[:b.num_real],
                               ffm_oracle_scores(table, field_num, examples),
                               rtol=2e-4, atol=2e-4, err_msg=why)


def ffm_pairwise_scores(params, field_num, local_idx, fields, vals):
    """The definition, pair by pair over [B, L, L]: what the bucketed
    body must equal, value and gradient."""
    rows = params[local_idx]
    B, L = local_idx.shape
    v = rows[..., :-1].reshape(B, L, field_num, -1)
    # vs[b, i, j] = v[b, i, fields[b, j]]: what i uses against j's field
    vs = jnp.take_along_axis(
        v[:, :, None], fields[:, None, :, None, None], axis=3)[:, :, :, 0]
    pair = jnp.einsum("bijk,bjik,bi,bj->bij", vs, vs, vals, vals)
    off_diagonal = 1.0 - jnp.eye(L, dtype=pair.dtype)
    return ((rows[..., -1] * vals).sum(axis=1)
            + 0.5 * (pair * off_diagonal).sum(axis=(1, 2)))


def ffm_einsum_scores(params, field_num, local_idx, fields, vals):
    """The bucketed body as it stood until PR 55, left to autodiff: the
    per-field sums S, each factor's [F, F] slab against its own
    transpose, less the i = j diagonal. Kept HERE, and nowhere in
    fast_tffm_tpu/, as the second reference of the hand-written VJP."""
    F, D = field_num, params.shape[-1]
    k = (D - 1) // F
    major = np.arange(F * k).reshape(F, k).T.ravel()
    rows = params[:, np.append(major, D - 1)][local_idx]
    a = jax.nn.one_hot(fields, F, dtype=rows.dtype) * vals[..., None]
    s = jnp.einsum("blg,blm->bgm", a, rows)
    slabs = s[:, :, :-1].reshape(-1, F, k, F)
    cross = jnp.einsum("bgkf,bfkg->b", slabs, slabs)
    own = np.append(np.tile(np.arange(F), k), -1) == fields[..., None]
    diag = jnp.sum(jnp.where(own, jnp.square(rows * vals[..., None]), 0.0),
                   axis=(1, 2))
    return s[:, :, -1].sum(axis=1) + 0.5 * (cross - diag)


def random_ffm_arrays(rng, B, L, field_num, k, U, dtype=np.float32):
    params = rng.normal(size=(U, field_num * k + 1)).astype(dtype) * 0.3
    local_idx = rng.integers(0, U, size=(B, L)).astype(np.int32)
    fields = rng.integers(0, field_num, size=(B, L)).astype(np.int32)
    vals = rng.normal(size=(B, L)).astype(dtype)
    return params, local_idx, fields, vals


def _fields_of(how, rng, B, L, F):
    """The cells' fields of a case of ``FFM_VJP_CASES``."""
    if how == "random":
        return rng.integers(0, F, size=(B, L))
    if how == "one field an example":       # every cell of an example in one
        return np.repeat(rng.integers(0, F, size=(B, 1)), L, axis=1)
    assert how == "a field no cell has" and F > 1
    missing = rng.integers(0, F, size=(B, 1))
    return (missing + rng.integers(1, F, size=(B, L))) % F


# (F, k, L, how the fields are drawn): the shapes of FFM_SHAPES, then D
# no multiple of 8 (15, 13), F = 1, k = 1, L over and under F, a field
# no cell of an example has, every cell of an example in one field.
FFM_VJP_CASES = [(F, k, L, "random") for F, k, L in FFM_SHAPES] + [
    (7, 2, 8, "random"), (1, 4, 8, "random"), (6, 1, 8, "random"),
    (3, 4, 16, "random"), (22, 4, 8, "random"),
    (5, 3, 8, "a field no cell has"), (22, 4, 24, "a field no cell has"),
    (5, 3, 8, "one field an example"), (22, 4, 24, "one field an example"),
    (1, 1, 4, "random"),
    # fields NOT padded: 30 x 4 + 1 = 121 columns are one 128-lane line
    # and 32 x 4 + 1 would be two; 8 fields are their own sublane count
    (30, 4, 8, "random"), (8, 2, 8, "a field no cell has")]


@pytest.mark.parametrize("F,k,padded", [
    (22, 4, 24), (39, 4, 40), (3, 4, 8), (1, 1, 8), (8, 2, 8), (24, 5, 24),
    (30, 4, 30), (31, 4, 31), (63, 2, 63), (33, 4, 40)])
def test_ffm_fields_pad_to_a_sublane_multiple_only_on_the_same_lines(F, k,
                                                                      padded):
    """``_padded_fields``: F goes up to a multiple of 8 (so that the
    batch-minor split ``[F, k*F, B]`` → ``[F, k, F, B]`` is a bitcast)
    where the row ``k*F + 1`` keeps its count of 128-lane lines, and
    stays where a line more would double what ``expand`` moves."""
    from fast_tffm_tpu.ops.interaction import _factor_major, _padded_fields
    assert _padded_fields(F, k) == padded
    m = _factor_major(F, k, padded, np.float32)
    assert m.shape == (F * k + 1, k * padded + 1)
    assert (m.sum(axis=1) == 1).all() and m.sum() == F * k + 1
    row = np.arange(F * k + 1, dtype=np.float32)    # column f*k+κ holds f*k+κ
    moved = row @ m
    for f in range(F):
        for kappa in range(k):
            assert moved[kappa * padded + f] == f * k + kappa
    assert moved[-1] == F * k
    np.testing.assert_array_equal(moved @ m.T, row)     # and back


@pytest.mark.parametrize("reference", ["pairwise", "einsum"])
@pytest.mark.parametrize("field_num,k,L,how", FFM_VJP_CASES)
def test_ffm_vjp_matches_autodiff_of_a_reference(rng, field_num, k, L, how,
                                                 reference):
    """``ffm_batch_scores``' hand-written VJP (ISSUE 55) against
    autodiff of two references that share no code with it: scores, and
    the gradient w.r.t. a row of every cell under a random cotangent
    (``params[b*L + l]`` IS the row of cell (b, l)), and the gradient
    w.r.t. shared slots, where the segment-sum adds cells up."""
    score_fn = {"pairwise": ffm_pairwise_scores,
                "einsum": ffm_einsum_scores}[reference]
    B = 6
    fields = _fields_of(how, rng, B, L, field_num).astype(np.int32)
    vals = rng.normal(size=(B, L)).astype(np.float32)
    vals[rng.random(size=(B, L)) < 0.2] = 0.0          # pad cells, anywhere
    cot = rng.normal(size=B).astype(np.float32)        # d loss / d score
    own_rows = np.arange(B * L, dtype=np.int32).reshape(B, L)
    shared = rng.integers(0, 40, size=(B, L)).astype(np.int32)
    for local_idx, U in ((own_rows, B * L), (shared, 40)):
        params = (rng.normal(size=(U, field_num * k + 1)) * 0.3).astype(
            np.float32)

        def summed(fn):
            return lambda p: (fn(p, field_num, local_idx, fields, vals)
                              * cot).sum()

        with jax.default_matmul_precision("highest"):
            want_s = score_fn(params, field_num, local_idx, fields, vals)
            want_g = jax.grad(summed(score_fn))(params)
        got_s = ffm_batch_scores(params, field_num, local_idx, fields, vals)
        got_g = jax.grad(summed(ffm_batch_scores))(params)
        np.testing.assert_allclose(got_s, want_s, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got_g, want_g, rtol=1e-5,
                                   atol=1e-5 * float(np.abs(want_g).max()))
        assert np.abs(np.asarray(want_g)).max() > 0.0


@pytest.mark.parametrize("field_num,k,L,how", [
    (3, 4, 8, "random"), (7, 2, 5, "random"), (1, 3, 4, "random"),
    (5, 1, 6, "a field no cell has"), (4, 2, 6, "one field an example")])
def test_ffm_vjp_first_order_in_float64(rng, field_num, k, L, how):
    """``jax.test_util.check_grads`` in float64 on the CPU, reverse mode,
    first order: the VJP against central differences of the function
    itself, where float32 against a reference shows 1e-5."""
    from jax.test_util import check_grads
    B, U = 3, 12
    with jax.enable_x64():
        params, local_idx, _, vals = random_ffm_arrays(
            rng, B, L, field_num, k, U, dtype=np.float64)
        fields = _fields_of(how, rng, B, L, field_num).astype(np.int32)
        vals[:, -1] = 0.0
        check_grads(
            lambda p: ffm_batch_scores(p, field_num, local_idx, fields, vals),
            (jnp.asarray(params),), order=1, modes=["rev"], atol=1e-7,
            rtol=1e-7)


def test_ffm_padded_slots_and_zero_weight_rows_are_exact_zeros(rng):
    """x = 0 slots and examples whose loss weight is 0 give the rows
    they point at a gradient of exactly 0.0, not a small number: the
    step scatter-adds every slot's gradient into the table."""
    field_num, k, L, B, U = 22, 4, 32, 6, 40
    params, local_idx, fields, vals = random_ffm_arrays(rng, B, L, field_num,
                                                        k, U - 2)
    pad_row, dead_row = U - 2, U - 1
    params = np.concatenate(
        [params, rng.normal(size=(2, params.shape[1])).astype(np.float32)])
    local_idx[:, 20:], vals[:, 20:] = pad_row, 0.0      # padded slots
    local_idx[4, :20] = dead_row                         # a zero-weight example
    vals[5] = 0.0                                        # an all-padding one
    cot = rng.normal(size=B).astype(np.float32)
    cot[4] = 0.0

    def loss(p):
        return (ffm_batch_scores(p, field_num, local_idx, fields, vals)
                * cot).sum()

    scores = np.asarray(ffm_batch_scores(params, field_num, local_idx,
                                         fields, vals))
    grad = np.asarray(jax.grad(loss)(params))
    assert scores[5] == 0.0
    np.testing.assert_array_equal(grad[[pad_row, dead_row]], 0.0)
    assert np.abs(grad[:pad_row]).max() > 0.0


def _walk(jaxpr, scope=""):
    """Every equation with the name stack it runs under: its own, or,
    inside a call (one_hot, where), the calling equation's."""
    for eqn in jaxpr.eqns:
        stack = scope or str(eqn.source_info.name_stack)
        yield eqn, stack
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(inner, stack)


def test_ffm_grad_keeps_the_factor_axis_off_the_minor_dimension():
    """The record that the mechanism is in every FFM program (PERF.md
    section 6, PR 30): the grad of the FFM loss at the benchmark cell's
    F, k, L holds no [B,L,F,k], [B,F,F,k], [B,L,1,k] or [B,L,k] array,
    which a TPU pads 32-fold or re-lays, and every equation of it runs
    under one of the step's named scopes, so a trace can place it."""
    from fast_tffm_tpu.models.fm import ModelSpec, loss_and_scores
    B, L, F, k, U = 16, 32, 22, 4, 100
    spec = ModelSpec(model_type="ffm", order=2, factor_num=k, field_num=F,
                     vocabulary_size=1000, loss_type="logistic",
                     factor_lambda=1e-6, bias_lambda=1e-6, learning_rate=0.05)
    f32, i32 = jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct

    def loss(gathered, *batch):
        return loss_and_scores(spec, gathered, *batch)[0]

    jaxpr = jax.make_jaxpr(jax.grad(loss))(
        S((U, F * k + 1), f32), S((B,), f32), S((B,), f32), S((U,), i32),
        S((B, L), i32), S((B, L), f32), S((B, L), i32)).jaxpr
    k_minor_rows = {B * L * F, B * F * F, B * L}
    eqns = list(_walk(jaxpr))
    assert len(eqns) > 50
    for eqn, stack in eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert not (len(shape) > 1 and shape[-1] == k
                        and int(np.prod(shape[:-1])) in k_minor_rows), (
                f"{eqn.primitive.name} makes {shape} under {stack!r}")
        assert any(s in stack for s in ("interaction", "expand", "loss")), (
            f"{eqn.primitive.name} runs under no scope ({stack!r})")


def test_reg_matches_oracle(rng):
    cfg = make_cfg()
    examples, block = random_batch(rng, 5)
    b = make_device_batch(block, cfg)
    table = padded_table(rng, cfg)
    gathered = gather_rows(table, b.uniq_ids)
    got = float(batch_reg(gathered, b.uniq_ids, V, 0.1, 0.05))
    want = oracle.regularization(table[:-1].astype(np.float64),
                                 examples, 0.1, 0.05)
    assert got == pytest.approx(want, rel=1e-4)


def test_empty_example_scores_zero(rng):
    cfg = make_cfg()
    # one real example, rest padding; a dummy has no features
    _, block = random_batch(rng, 1)
    b = make_device_batch(block, cfg)
    table = padded_table(rng, cfg)
    got = np.asarray(fm_batch_scores(gather_rows(table, b.uniq_ids),
                                     b.local_idx, b.vals))
    assert np.all(got[1:] == 0.0)


@pytest.mark.parametrize("where", ["trailing", "leading", "between"])
@pytest.mark.parametrize("row", ["the dead row", "a live row", "a cell's own row"])
def test_order3_pad_slot_of_any_row_and_value_0_changes_nothing(rng, where,
                                                                row):
    """What ``_anova_terms`` asks of a pad slot is ``z_j = 0``: value 0,
    whatever row the slot indexes and wherever it sits in the line. The
    scan's state passes such a slot unchanged (``a[t] + a[t-1] * 0``),
    so the kernels are those of the line without it to the bit, and
    score and row gradient to the rounding of the linear term's sum
    over a longer line. Changes to how pad cells are shipped (PERF.md section 7: an
    index past U, a second rectangle, flat cells) go through here."""
    B, n, pads, U, k = 4, 6, 3, 12, K
    params = jnp.asarray(rng.normal(size=(U, k + 1)) * 0.3, jnp.float32)
    params = params.at[-1].set(0.0)
    idx = rng.integers(0, U - 1, size=(B, n)).astype(np.int32)
    vals = rng.normal(size=(B, n)).astype(np.float32)
    pad_row = {"the dead row": U - 1, "a live row": 3,
               "a cell's own row": int(idx[0, 0])}[row]
    at = {"trailing": n, "leading": 0, "between": n // 2}[where]
    idx_p = np.insert(idx, [at] * pads, pad_row, axis=1)
    vals_p = np.insert(vals, [at] * pads, 0.0, axis=1)
    ds = jnp.asarray(rng.normal(size=B), jnp.float32)

    def f(p, i, v):
        s = fm_batch_scores(p, jnp.asarray(i), jnp.asarray(v), order=3)
        return (s * ds).sum(), s

    (_, s0), g0 = jax.value_and_grad(f, has_aux=True)(params, idx, vals)
    (_, s1), g1 = jax.value_and_grad(f, has_aux=True)(params, idx_p, vals_p)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=1e-6,
                               atol=1e-7)
    assert np.abs(np.asarray(g0)).max() > 0
    from fast_tffm_tpu.ops.interaction import _anova_terms
    z, z_p = (jnp.transpose(params[jnp.asarray(i), :-1]
                            * jnp.asarray(v)[..., None], (1, 2, 0))
              for i, v in ((idx, vals), (idx_p, vals_p)))   # [L, k, B]
    np.testing.assert_array_equal(np.asarray(_anova_terms(z_p, 3)),
                                  np.asarray(_anova_terms(z, 3)))
    # a slot that is NOT neutral, for contrast: value 0 is what counts
    vals_bad = vals_p.copy()
    vals_bad[:, at] = 0.5
    (_, s2), _ = jax.value_and_grad(f, has_aux=True)(params, idx_p, vals_bad)
    if row != "the dead row":
        assert np.abs(np.asarray(s2) - np.asarray(s0)).max() > 1e-4


# ---- the expanded rows kept whole (ISSUE 42) --------------------------------

def _row_a_cell(rng, D, B=5, n=7, pads=3):
    """A batch whose every cell has a row of its own (``params[b*L + l]``
    IS the row of cell (b, l), so the gradient w.r.t. ``params`` is the
    row gradient ``[B, L, D]``), real-valued cells, and ``pads`` cells a
    line of value 0 at any index, each on a live, non-zero row."""
    L = n + pads
    rows = (rng.normal(size=(B, L, D)) * 0.3).astype(np.float32)
    vals = rng.normal(size=(B, L)).astype(np.float32)
    pad = np.zeros((B, L), bool)
    for b in range(B):
        pad[b, rng.choice(L, size=pads, replace=False)] = True
    vals[pad] = 0.0
    idx = np.arange(B * L, dtype=np.int32).reshape(B, L)
    cot = rng.normal(size=B).astype(np.float32)   # d loss / d score
    return rows, idx, vals, pad, cot


def _oracle_scores_and_row_grads(rows, vals, pad, cot, order, eps=1e-5):
    """``oracle.fm_score`` at float64 on each line WITHOUT its pad cells,
    and the gradient of ``cot_b * score_b`` w.r.t. every cell's row by
    central differences (the score is a polynomial of degree <= order
    in a row's entries, so the step's error is ~eps^2)."""
    B, L, D = rows.shape
    scores, grads = np.zeros(B), np.zeros((B, L, D))
    for b in range(B):
        table = rows[b].astype(np.float64)
        ids = np.flatnonzero(~pad[b])
        x = vals[b, ids].astype(np.float64)
        scores[b] = oracle.fm_score(table, ids, x, order=order)
        for l in range(L):
            for d in range(D):
                hi, lo = table.copy(), table.copy()
                hi[l, d] += eps
                lo[l, d] -= eps
                grads[b, l, d] = cot[b] * (
                    oracle.fm_score(hi, ids, x, order=order)
                    - oracle.fm_score(lo, ids, x, order=order)) / (2 * eps)
    return scores, grads


@pytest.mark.parametrize("D", [9, 17])
@pytest.mark.parametrize("order", [2, 3])
def test_whole_rows_scores_and_row_gradients_match_the_oracle(rng, order, D):
    """``fm_batch_scores`` takes the linear term from the last column of
    per-example sums over whole rows, never from ``rows[..., -1]``
    (ISSUE 42). So, beside scores and row gradients against the oracle:
    the w column of the row gradient is exactly ``g_b * x_bl`` (the
    pair and ANOVA terms leave that column out of their sums and give
    it nothing, not a rounding's worth), a pad cell's gradient is
    exactly zero in every column though its row is not, and the same
    lines at a wider rung score the same."""
    rows, idx, vals, pad, cot = _row_a_cell(rng, D)
    B, L, _ = rows.shape

    def f(p, i, v):
        s = fm_batch_scores(p, jnp.asarray(i), jnp.asarray(v), order=order)
        return (s * cot).sum(), s

    (_, got_s), got_g = jax.value_and_grad(f, has_aux=True)(
        jnp.asarray(rows.reshape(B * L, D)), idx, vals)
    got_s, got_g = np.asarray(got_s), np.asarray(got_g).reshape(B, L, D)
    want_s, want_g = _oracle_scores_and_row_grads(rows, vals, pad, cot,
                                                  order)
    np.testing.assert_allclose(got_s, want_s, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got_g, want_g, rtol=1e-5,
                               atol=1e-5 * float(np.abs(want_g).max()))
    np.testing.assert_array_equal(got_g[..., -1], cot[:, None] * vals)
    assert np.abs(rows[pad]).min() > 0.0
    np.testing.assert_array_equal(got_g[pad], 0.0)
    assert np.abs(got_g[~pad]).min() > 0.0
    # the same lines at a wider rung: five more cells of value 0, each
    # on a live row
    wide_i = np.concatenate([idx, np.tile(idx[:, :1], (1, 5))], axis=1)
    wide_v = np.concatenate([vals, np.zeros((B, 5), np.float32)], axis=1)
    (_, wide_s), wide_g = jax.value_and_grad(f, has_aux=True)(
        jnp.asarray(rows.reshape(B * L, D)), wide_i, wide_v)
    np.testing.assert_allclose(np.asarray(wide_s), got_s, rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(wide_g).reshape(B, L, D), got_g,
                               rtol=1e-6, atol=1e-7)
