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

Shapes: ``params`` are the batch's gathered unique rows ``[U, D]``
(D = k+1 for FM, field_num*k+1 for FFM); ``local_idx [B, L]`` indexes
into them; ``vals [B, L]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
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
    adds ANOVA-kernel terms of degree 2..order (BASELINE config #4)."""
    rows = expand_rows(params, local_idx)         # [B, L, k+1]
    with jax.named_scope("interaction"):
        v, w = rows[..., :-1], rows[..., -1]
        linear = jnp.einsum("bl,bl->b", w, vals, precision=_F32)
        z = v * vals[..., None]                   # [B, L, k]
        if order == 2:
            s = z.sum(axis=1)                     # [B, k]
            q = jnp.square(z).sum(axis=1)
            return linear + 0.5 * (jnp.square(s) - q).sum(axis=-1)
        return linear + _anova_terms(z, order)


def _anova_terms(z: jax.Array, order: int) -> jax.Array:
    """Sum of ANOVA kernels of degree 2..order, all latent dims.

    Classic DP (a_new[t] = a[t] + a[t-1]*z_j) run as a ``lax.scan`` over
    the L feature slots — static trip count, TPU-friendly; padded slots
    have z_j = 0 and leave the state unchanged. O(L * order * k).
    """
    B, L, k = z.shape
    a0 = jnp.zeros((B, order + 1, k), dtype=z.dtype).at[:, 0].set(1.0)

    def step(a, z_j):                              # z_j: [B, k]
        return a.at[:, 1:].add(a[:, :-1] * z_j[:, None, :]), None

    a, _ = lax.scan(step, a0, jnp.moveaxis(z, 1, 0))
    return a[:, 2:].sum(axis=(1, 2))


def ffm_batch_scores(params: jax.Array, field_num: int,
                     local_idx: jax.Array, fields: jax.Array,
                     vals: jax.Array) -> jax.Array:
    """Field-aware FM (BASELINE config #3): row layout [U, field_num*k+1];
    v[i, f] is the latent vector row i uses against field f.

        score = Σ_j w_j x_j + Σ_{i<j} <v[i, f_j], v[j, f_i]> x_i x_j

    Computed by bucketing features by field instead of forming the
    [B, L, L, k] pair tensor (which is ~2.7 GB at L=256/B=1024):

        S[b, f, g, :] = Σ_{l : fields[b,l]=g} x_l · v[b, l, f, :]
        Σ_{i,j} <v_i[f_j], v_j[f_i]> x_i x_j = Σ_{f,g} <S[f,g], S[g,f]>

    (each ordered pair (i, j) lands in the (f, g) = (f_j, f_i) bucket
    exactly once), then the i=j diagonal Σ_l x_l²·||v_l[f_l]||² is
    subtracted and the sum halved. The biggest intermediate is
    [B, F, F, k] — bounded by the field count, not the feature bucket —
    and the L-contraction is a plain matmul the MXU tiles. Padded slots
    have x=0 and contribute zero everywhere.
    """
    rows = expand_rows(params, local_idx)          # [B, L, F*k+1]
    with jax.named_scope("interaction"):
        B, L = local_idx.shape
        w = rows[..., -1]
        k = (rows.shape[-1] - 1) // field_num
        v = rows[..., :-1].reshape(B, L, field_num, k)
        linear = jnp.einsum("bl,bl->b", w, vals, precision=_F32)
        onehot = jax.nn.one_hot(fields, field_num,
                                dtype=v.dtype)                 # [B, L, F]
        # S[b,f,g,:] = Σ_l onehot[b,l,g] · x[b,l] · v[b,l,f,:]
        s = jnp.einsum("blfk,blg,bl->bfgk", v, onehot, vals,
                       precision=_F32)
        cross = jnp.einsum("bfgk,bgfk->b", s, s, precision=_F32)
        # i=j diagonal: v each feature uses against its own field.
        v_self = jnp.take_along_axis(
            v, fields[:, :, None, None], axis=2)[:, :, 0, :]   # [B, L, k]
        diag = jnp.einsum("blk,blk,bl->b", v_self, v_self,
                          jnp.square(vals), precision=_F32)
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
