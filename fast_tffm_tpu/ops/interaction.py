"""FM interaction math as pure-XLA JAX — the ``fm_scorer`` equivalent.

The reference computes, in a multithreaded C++ TF op over a CSR batch
(SURVEY.md §2 ``fm_scorer``, §3.5):

    linear  = sum_j w[id_j] x_j
    pair    = 1/2 sum_f [(sum_j v[id_j,f] x_j)^2 - sum_j v[id_j,f]^2 x_j^2]
    reg     = factor_lambda * sum_unique ||v||^2 + bias_lambda * sum w^2

Here the same math runs on fixed-shape bucketed batches (data/pipeline.py)
as einsums the TPU compiler fuses end-to-end; ``jax.grad`` through these
functions *is* the ``fm_grad`` equivalent (a hand-fused Pallas version with
a custom VJP lives in ops/pallas_fm.py). Padding contributes exactly zero
because padded ``vals`` are 0 and every term carries an ``x_j`` factor.

One rule for both scorers: the expanded rows ``[B, L, D]`` are consumed
whole. A TPU tiles an array's last two dimensions (8, 128), so the
gather lays a row of 9, 17 or 89 columns on a 128-lane line, and an
array with 1 or k as the minor dimension of a ``[B, L, ...]`` shape (the
w column, the factor columns, sliced off the rows) is a full pass over
those padded lines to make, another to re-lay, and pads up to 128-fold
itself. Slices are taken of the per-example sums ``[B, D]``, or where
the column axis is a major one; the row gradient is born ``[B, L, D]``,
the shape ``expand_rows``' segment-sum takes (PERF.md section 6, PR 30
for FFM, PR 42 for FM).

Shapes: ``params`` are the batch's gathered unique rows ``[U, D]``
(D = k+1 for FM, field_num*k+1 for FFM); ``local_idx [B, L]`` indexes
into them; ``vals [B, L]``.
"""

from __future__ import annotations

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
    F = field_num, D = F*k + 1 and the columns taken factor-major,
    column κ*F + f holding v[., f][κ]:

        a[b, l, g] = [fields[b, l] = g] · x[b, l]                [B, L, F]
        S[b, g, :] = Σ_l a[b, l, g] · rows[b, l, :]               [B, F, D]
        Σ_{i,j} <v_i[f_j], v_j[f_i]> x_i x_j
                   = Σ_κ Σ_{g,f} S[b, g, κ*F+f] · S[b, f, κ*F+g]

    (each ordered pair (i, j) lands in the (g, f) = (f_i, f_j) bucket
    exactly once: per κ an [F, F] slab against its own transpose), then
    the i=j diagonal Σ_l x_l²·||v_l[f_l]||², a mask over the columns,
    is subtracted and the sum halved. S is ONE batched matmul over L
    whose last column is the linear term, summed by field, so the rows
    are never sliced and the row gradient is born [B, L, D], the shape
    ``expand_rows``' segment-sum takes: a·dS less the masked diagonal.
    The columns are put factor-major on the U gathered slots, not on
    the B·L expanded rows, by a 0/1 matrix (exact in float32 at
    ``HIGHEST``; its transpose brings the slot gradient back), so the
    table and its checkpoints keep their layout. Nothing here depends
    on which of F, k, L is large: the biggest intermediates are
    [B, L, D] and [B, F, D]. Padded slots have x=0 and contribute
    exactly zero to score and gradient; ``fields`` lie in [0, F) (the
    parsers refuse any other), a feature outside would drop out whole.
    """
    with jax.named_scope("interaction"):
        F, D = field_num, params.shape[-1]
        k = (D - 1) // F
        major = np.arange(F * k).reshape(F, k).T.ravel()   # κ*F+f <- f*k+κ
        params = jnp.dot(
            params, np.eye(D, dtype=params.dtype)[:, np.append(major, D - 1)],
            precision=_F32)
    rows = expand_rows(params, local_idx)          # [B, L, D], factor-major
    with jax.named_scope("interaction"):
        a = jax.nn.one_hot(fields, F, dtype=rows.dtype) * vals[..., None]
        s = jnp.einsum("blg,blm->bgm", a, rows, precision=_F32)
        linear = s[:, :, -1].sum(axis=1)
        slabs = s[:, :, :-1].reshape(-1, F, k, F)  # [b, g, κ, f]
        cross = jnp.einsum("bgkf,bfkg->b", slabs, slabs, precision=_F32)
        # i=j diagonal: the columns each feature uses against its own
        # field (column κ*F+f belongs to field f; the last to none).
        own = np.append(np.tile(np.arange(F), k), -1) == fields[..., None]
        diag = jnp.sum(jnp.where(own, jnp.square(rows * vals[..., None]),
                                 0.0), axis=(1, 2))
        return linear + 0.5 * (cross - diag)


def batch_reg(params: jax.Array, uniq_ids: jax.Array, vocabulary_size: int,
              factor_lambda: float, bias_lambda: float) -> jax.Array:
    """L2 over the batch's unique touched rows (SURVEY §3.5): the pipeline
    already deduplicated ids on the host, so this is a masked sum — padding
    slots (id == vocabulary_size) are excluded."""
    mask = (uniq_ids < vocabulary_size).astype(params.dtype)[:, None]
    v, w = params[:, :-1], params[:, -1:]
    return (factor_lambda * jnp.sum(jnp.square(v) * mask)
            + bias_lambda * jnp.sum(jnp.square(w) * mask))
