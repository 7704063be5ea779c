"""FM interaction math as pure-XLA JAX — the ``fm_scorer`` equivalent.

The reference computes, in a multithreaded C++ TF op over a CSR batch
(SURVEY.md §2 ``fm_scorer``, §3.5):

    linear  = sum_j w[id_j] x_j
    pair    = 1/2 sum_f [(sum_j v[id_j,f] x_j)^2 - sum_j v[id_j,f]^2 x_j^2]
    reg     = factor_lambda * sum_unique ||v||^2 + bias_lambda * sum w^2

Here the same math runs on fixed-shape bucketed batches (data/pipeline.py)
as einsums the TPU compiler fuses end-to-end; ``jax.grad`` through these
functions *is* the ``fm_grad`` equivalent for FM (a hand-fused Pallas
version with a custom VJP lives in ops/pallas_fm.py); FFM's interaction
carries a hand-written VJP of its own (``ffm_batch_scores``). Padding
contributes exactly zero because padded ``vals`` are 0 and every term
carries an ``x_j`` factor.

One rule for both scorers: the expanded rows ``[B, L, D]`` are consumed
whole. A TPU tiles an array's last two dimensions (8, 128), so the
gather lays a row of 9, 17 or 89 columns on a 128-lane line, and an
array with 1 or k as the minor dimension of a ``[B, L, ...]`` shape (the
w column, the factor columns, sliced off the rows) is a full pass over
those padded lines to make, another to re-lay, and pads up to 128-fold
itself. Slices are taken of the per-example sums ``[B, D]``, or where
the column axis is a major one; the row gradient is born ``[B, L, D]``,
the shape ``expand_rows``' segment-sum takes (PERF.md section 6, PR 30
for FFM, PR 42 for FM). The backward's half of the rule: a layout swap
the forward has made is KEPT for the backward, not made again on the
cotangent. Autodiff transposes every re-lay of the forward, so a
cotangent walks each of them back, pass by pass; where the backward
needs the forward's re-laid array itself (FFM: the field-transpose P
of the per-field sums IS the sums' gradient), a hand-written VJP saves
it and the backward is the one pass that writes ``[B, L, D]`` (PR 55).

Shapes: ``params`` are the batch's gathered unique rows ``[U, D]``
(D = k+1 for FM, field_num*k+1 for FFM); ``local_idx [B, L]`` indexes
into them; ``vals [B, L]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# FM latent values are tiny (init ±0.01) and scores are heavy on
# cancellation ((Σv)²−Σv²); the platform's default matmul precision may
# downcast dot inputs (bf16 passes on TPU) which visibly distorts scores.
# Every einsum here is small (k ≤ a few dozen), so full-f32 accumulation
# costs nothing measurable and is required for oracle parity.
_F32 = lax.Precision.HIGHEST


# The device step's named parts (README "Observability"): each helper
# below opens one ``jax.named_scope``, so every step and score program
# built from them carries the part's name on its operations' op paths
# (``jit(fm_train_step)/transpose(jvp(expand))/scatter-add``), which a
# profiler trace keeps and benchmarks/readers/scope_device_ms.py reads.
# The other three live in models/fm.py: dedup, loss, adagrad.


def gather_rows(table: jax.Array, uniq_ids: jax.Array) -> jax.Array:
    """Gather the batch's unique rows from the (possibly huge) table
    (scope ``gather``).

    Padding slots hold ``pad_id == vocabulary_size`` which indexes the
    dead extra row (all-zero, never updated), so no clipping is needed.
    """
    with jax.named_scope("gather"):
        # fmlint: disable=R011 -- the one sanctioned batch gather below
        # the slot seam (admit-mode ids are already physical rows here)
        return table[uniq_ids]


def expand_rows(params: jax.Array, local_idx: jax.Array) -> jax.Array:
    """The U gathered slots out to ``[B, L, D]`` (scope ``expand``).
    Its transpose is the segment-sum of the row gradients back into the
    slots, which therefore reads ``transpose(jvp(expand))``."""
    with jax.named_scope("expand"):
        return params[local_idx]


def fm_batch_scores(params: jax.Array, local_idx: jax.Array,
                    vals: jax.Array, order: int = 2) -> jax.Array:
    """Per-example FM scores. order==2 uses the (Σv)²−Σv² identity; order>2
    adds ANOVA-kernel terms of degree 2..order (BASELINE config #4).

    The expanded rows ``[B, L, D]`` (D = k+1) are consumed WHOLE (the
    module's rule): no array here is a minor-axis slice of ``[B, L, D]``
    and none has 1 or k as the minor dimension of a ``[B, L, ...]``
    shape. ``expand_rows``' gather writes a row a cell, 9 or 17 columns
    on a 128-lane line: 512 B a cell, 403 MB at ``[8192, 96, 9]`` for
    28 MB of numbers. ``rows[..., -1]`` reads all of that to write one
    number a line, another 403 MB, and w and v sliced apart are each
    re-laid batch-minor: on the v5e that slice and second copy were
    0.73 ms of FM's 8.60 ms step and 1.85 / 2.08 of the bags cells'
    15.75 / 17.50 (PERF.md section 6, PR 42). So the slices are taken
    where they cost nothing:

    - order 2: ``s = Σ_l rows·x`` and ``q = Σ_l rows²·x²`` over all D
      columns, ``[B, D]``; the linear term is ``s[:, -1]``, the pair
      term is over the other k columns. The w column's square sum is
      computed and thrown away (a D-th of one pass).
    - order > 2: ``z = rows·x`` is re-laid ONCE to ``[L, D, B]`` (the
      batch on the lanes, D on the sublanes), where the w column is a
      major-axis slice: the linear term is its sum over L, and the scan
      takes the k factor columns ``[L, k, B]``. (The w column riding
      the scan as a ninth factor pads the carries from 8 sublanes to 16
      and the scan takes 1.8 times as long; same section.)

    Either way the compiled step holds one layout copy of ``[B, L, D]``
    forward and one backward, and the row gradient is born ``[B, L, D]``,
    the shape ``expand_rows``' segment-sum takes
    (tests/test_state_layout.py compiles it for a described v5e)."""
    rows = expand_rows(params, local_idx)         # [B, L, k+1]
    with jax.named_scope("interaction"):
        if order == 2:
            s = jnp.einsum("bld,bl->bd", rows, vals, precision=_F32)
            q = jnp.einsum("bld,bl->bd", jnp.square(rows), jnp.square(vals),
                           precision=_F32)
            return s[:, -1] + 0.5 * (jnp.square(s[:, :-1])
                                     - q[:, :-1]).sum(axis=-1)
        z = jnp.transpose(rows * vals[..., None], (1, 2, 0))   # [L, k+1, B]
        return z[:, -1].sum(axis=0) + _anova_terms(z[:, :-1], order)


def _anova_terms(z: jax.Array, order: int) -> jax.Array:
    """Sum of ANOVA kernels of degree 2..order, all latent dims, of
    ``z [L, k, B]`` (slot-major, the batch minor).

    Classic DP (a_new[t] = a[t] + a[t-1]*z_j) run as a ``lax.scan`` over
    the L feature slots — static trip count, O(L * order * k), L
    sequential steps forward and L backward (autodiff keeps the L
    carries ``[order+1, k, B]``). Scope ``anova_scan``, nested inside
    ``interaction``: both names ride the scan's op paths, forward and
    backward, so ``interaction_ms`` still holds it and
    benchmarks/readers/op_scope_device_ms.py reads the scan alone.

    What a pad slot must look like: ``z_j = 0`` in every factor, which
    ``fm_batch_scores`` gives it by ``vals == 0`` whatever row the slot
    indexes (in the w column too, whose sum is the linear term). The
    step is then ``a[t] + a[t-1] * 0``: the state passes
    unchanged and the slot's gradient w.r.t. ``z_j`` is multiplied by
    the slot's value on its way to the row, so score and row gradients
    are those of the line without it. A pad slot of any other form (a
    masked lane, an index past U read as NaN or as a fill that is not
    finite) is NOT neutral here: ``0 * nan`` poisons every later slot.
    """
    with jax.named_scope("anova_scan"):
        L, k, B = z.shape
        a0 = jnp.zeros((order + 1, k, B), dtype=z.dtype).at[0].set(1.0)

        def step(a, z_j):                              # z_j: [k, B]
            return a.at[1:].add(a[:-1] * z_j), None

        a, _ = lax.scan(step, a0, z)
        return a[2:].sum(axis=(0, 1))


def ffm_batch_scores(params: jax.Array, field_num: int,
                     local_idx: jax.Array, fields: jax.Array,
                     vals: jax.Array) -> jax.Array:
    """Field-aware FM (BASELINE config #3): row layout [U, field_num*k+1];
    v[i, f], the latent vector row i uses against field f, sits at
    columns f*k .. f*k+k-1, the linear weight w in the last one.

        score = Σ_j w_j x_j + Σ_{i<j} <v[i, f_j], v[j, f_i]> x_i x_j

    Computed by bucketing features by field instead of forming the
    [B, L, L, k] pair tensor, and with the rows kept whole: no array
    here has the factor axis k as its minor dimension (a TPU tiles the
    last two dimensions (8, 128), so a [..., k=4] array pads 32-fold or
    is re-laid, forward and backward; PERF.md section 6, PR 30). With
    F = field_num and the columns taken factor-major, column κ*F + f
    holding v[., f][κ] and w the last (``_factor_major``: a 0/1 matrix
    on the U gathered slots, not on the B·L expanded rows, exact in
    float32 at ``HIGHEST``; its transpose brings the slot gradient
    back, so the table and its checkpoints keep their layout):

        S[b, g, :] = Σ_l [fields[b, l] = g] · x[b, l] · rows[b, l, :]
        P[b, f, κ*F+g] = S[b, g, κ*F+f],   P[b, f, last] = 1
        cross = Σ_{i,j} <v_i[f_j], v_j[f_i]> x_i x_j
              = Σ_κ Σ_{g,f} S[b, g, κ*F+f] · S[b, f, κ*F+g] = Σ S ⊙ P

    (each ordered pair (i, j) lands in the (g, f) = (f_i, f_j) bucket
    exactly once: per κ an [F, F] slab against its own transpose), then
    the i=j diagonal Σ_l x_l²·||v_l[f_l]||², a mask over the columns,
    is subtracted and the sum halved; the linear term Σ_l x_l·w_l is
    taken in the diagonal's pass over the rows, not from S.

    **The gradient is written by hand** (``_ffm_interaction``, a
    ``jax.custom_vjp``) on one identity. P, the field-transpose of S,
    is what the cross term's gradient w.r.t. S is: the cross term is a
    symmetric quadratic form in S, so half its gradient at S[g, κF+f]
    is S[f, κF+g] = P[g, κF+f], and the linear term's is P's last
    column, 1. Through S's sum over the cells that is, for a cell's row,

        ∂score_b / ∂rows[b, l, :] = x_l · P[b, f_l, :]
                                    − x_l² · own[b, l, :] ⊙ rows[b, l, :]

    (``own``: the columns the cell uses against its own field). So the
    lane-to-sublane swap is made ONCE, forward, and P is the residual:
    the backward is one pass, ``one_hot(fields)·P`` scaled by x·ds less
    the masked rows, born [B, L, D] as ``expand_rows``' segment-sum
    takes it, and needs neither S nor a batch-minor array. Autodiff of
    the same forward walked dS through batch-minor and back instead.
    The passes of the scope at ``ffm4-train-zipf``'s size (B 8192, L 24,
    F 22, k 4; MB in tiled bytes of the program compiled for a v5e, ms
    a step on the chip; PERF.md section 6, PR 55):

        autodiff (until PR 55)            MB    ms | hand-written VJP             MB    ms
        S = a·rows (6 bf16 passes)       210  0.45 | x·rows, diagonal + linear   205  0.29
        the diagonal                     109  0.54 | S = one_hot·(x·rows), 1 x 3 210  0.31
        the linear term, read off S      101  0.16 | S to batch-minor            176  0.14
        S to batch-minor, reshape        297  0.17 | the (g, f) swap             151  0.12
        cross                             69  0.05 | cross                        76  0.10
        bwd: dS in batch-minor, swap     276  0.20 | P back to row-major, kept   176  0.12
        bwd: reshape, column 88 put back 266  0.31 | bwd: one_hot·P less rows    315  0.49
        bwd: dS back to row-major        170  0.30 |
        bwd: a·dS less the diagonal      310  0.47 |
        the 0/1 matrix, both ways         18  0.02 | the same                     18  0.03
        no op path (copies, slices)            0.22 |                                  0.04
        sum                            1,826  2.92 |                           1,327  1.63

    Three things besides the identity make the right-hand column:

    - **F is padded to the sublane count** where that keeps the row on
      as many 128-lane lines (``_padded_fields``: 22 → 24, a row of 97
      columns where 89, one line either way, so ``expand`` moves what
      it moved). In batch-minor S is ``[F, k·F, B]``, and the swap
      needs ``[F, k, F, B]``: with 22 fields on 8 sublanes that split
      is a re-lay (the two ``reshape`` passes, 0.20 ms each on the
      chip); with 24 it is a bitcast. The two fields no feature has
      give zero columns, zero rows of S and zero gradient.
    - **The one-hot operand of both matmuls is exact in ONE bf16
      pass**, so x rides the other operand (forward: ``x·rows``, three
      passes keep its float32) or the result (backward: ``x·ds``
      scales ``one_hot·P``). At ``HIGHEST`` on both operands the S
      matmul was bound by its six passes, not by its bytes.
    - The compiler keeps arrays in the chip's 128 MiB of fast memory
      where they fit, and copies there run twice as fast; which arrays
      fit is its choice per program, and only a chip run says.

    The forward-only scorers (validation, predict, serve) run the
    primal alone: P is dead code there and no array goes back from
    batch-minor to row-major (tests/test_state_layout.py).

    **What is not differentiated.** The VJP returns a cotangent for
    the rows alone; ``vals`` and ``fields`` get none. Nothing in the
    tree differentiates w.r.t. either (``grad_body`` takes the
    gradient w.r.t. the gathered rows); a caller that did would read
    zeros.

    **What a pad cell must look like**: x = 0 on a FINITE row, whatever
    row it indexes. Its one-hot row then meets ``x·rows = 0`` forward,
    and backward ``one_hot·P`` (finite) is scaled by ``x·ds = 0``: score
    and gradient are exactly 0.0, as for an example whose cotangent is
    0. The order matters: x·ds multiplies P's rows, never the other way
    round with a P that is not finite. A row holding inf or NaN under a
    pad cell poisons its example (0·inf), as it did before PR 55.
    ``fields`` lie in [0, F) (the parsers refuse any other); a feature
    outside would drop out of the cross term whole. Nothing here
    depends on which of F, k, L is large: the biggest arrays are
    [B, L, D] and [B, F, D].
    """
    with jax.named_scope("interaction"):
        F, D = field_num, params.shape[-1]
        k = (D - 1) // F
        Fp = _padded_fields(F, k)
        params = jnp.dot(params, _factor_major(F, k, Fp, params.dtype),
                         precision=_F32)
    rows = expand_rows(params, local_idx)          # [B, L, k*Fp+1]
    return _ffm_interaction(Fp, rows, fields, vals)


def _padded_fields(F: int, k: int) -> int:
    """F rounded up to the sublane count 8 where the row ``k*F + 1``
    stays on as many 128-lane lines as before (22 -> 24 at k = 4: 89
    and 97 columns are one line each), else F."""
    Fp = -(-F // 8) * 8
    return Fp if -(-(k * Fp + 1) // 128) == -(-(k * F + 1) // 128) else F


def _factor_major(F: int, k: int, Fp: int, dtype) -> np.ndarray:
    """The 0/1 matrix ``[F*k+1, k*Fp+1]`` that puts column ``f*k + κ``
    of a table row at ``κ*Fp + f`` and w last; the columns of the
    ``Fp - F`` fields no feature has stay zero."""
    m = np.zeros((F * k + 1, k * Fp + 1), dtype)
    f, kappa = np.divmod(np.arange(F * k), k)
    m[np.arange(F * k), kappa * Fp + f] = 1.0
    m[-1, -1] = 1.0
    return m


def _own_columns(F: int, k: int, fields: jax.Array) -> jax.Array:
    """``[B, L, k*F+1]``: the columns a cell uses against its own field
    (column κ*F+f belongs to field f; the last, w, to none)."""
    return np.append(np.tile(np.arange(F), k), -1) == fields[..., None]


def _ffm_forward(F: int, rows: jax.Array, fields: jax.Array,
                 vals: jax.Array):
    """Scores ``[B]`` and P ``[B, F, D]`` of ``ffm_batch_scores``' maths
    (F the padded field count, ``rows [B, L, D = k*F+1]`` factor-major)."""
    with jax.named_scope("interaction"):
        B, _, D = rows.shape
        k = (D - 1) // F
        xr = rows * vals[..., None]
        # one_hot is exact in one bf16 pass; x rides the rows, whose
        # three bf16 parts the right-hand HIGHEST keeps
        s = jnp.einsum("blg,blm->bgm",
                       jax.nn.one_hot(fields, F, dtype=rows.dtype), xr,
                       precision=(lax.Precision.DEFAULT, _F32))
        slabs = s[:, :, :-1].reshape(B, F, k, F)        # [b, g, κ, f]
        cross = jnp.einsum("bgkf,bfkg->b", slabs, slabs, precision=_F32)
        p = jnp.transpose(slabs, (0, 3, 2, 1)).reshape(B, F, F * k)
        p = jnp.concatenate([p, jnp.ones((B, F, 1), rows.dtype)], axis=-1)
        # the linear term and the i=j diagonal in ONE pass over the rows
        last = np.arange(D) == D - 1
        rest = jnp.sum(jnp.where(_own_columns(F, k, fields),
                                 -0.5 * jnp.square(xr),
                                 jnp.where(last, xr, 0.0)), axis=(1, 2))
        return rest + 0.5 * cross, p


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ffm_interaction(F, rows, fields, vals):
    """FFM scores of whole factor-major rows; differentiable w.r.t.
    ``rows`` alone, by the VJP ``ffm_batch_scores`` describes. Called
    outside ``jax.grad`` (the scorers) it is the forward alone."""
    return _ffm_forward(F, rows, fields, vals)[0]


def _ffm_interaction_fwd(F, rows, fields, vals):
    scores, p = _ffm_forward(F, rows, fields, vals)
    return scores, (p, rows, fields, vals)


def _ffm_interaction_bwd(F, residuals, ds):
    p, rows, fields, vals = residuals
    with jax.named_scope("interaction"):
        k = (rows.shape[-1] - 1) // F
        xd = vals * ds[:, None]
        picked = jnp.einsum("blg,bgm->blm",
                            jax.nn.one_hot(fields, F, dtype=rows.dtype), p,
                            precision=(lax.Precision.DEFAULT, _F32))
        grad = picked * xd[..., None] - jnp.where(
            _own_columns(F, k, fields), rows * (vals * xd)[..., None], 0.0)
        return grad, None, None     # no cotangent for fields and vals


_ffm_interaction.defvjp(_ffm_interaction_fwd, _ffm_interaction_bwd)


def batch_reg(params: jax.Array, uniq_ids: jax.Array, vocabulary_size: int,
              factor_lambda: float, bias_lambda: float) -> jax.Array:
    """L2 over the batch's unique touched rows (SURVEY §3.5): the pipeline
    already deduplicated ids on the host, so this is a masked sum — padding
    slots (id == vocabulary_size) are excluded."""
    mask = (uniq_ids < vocabulary_size).astype(params.dtype)[:, None]
    v, w = params[:, :-1], params[:, -1:]
    return (factor_lambda * jnp.sum(jnp.square(v) * mask)
            + bias_lambda * jnp.sum(jnp.square(w) * mask))
