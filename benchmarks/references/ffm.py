"""Field-aware FM (Juan et al. 2016): rows ``[v[field 0] (k) | ... |
v[field F-1] (k) | w]``,

    score = sum_l w_l x_l + sum_{i<j} <v_i[f_j], v_j[f_i]> x_i x_j.

Arithmetic copied from data/synth.numpy_*_train_predict (sound; PERF.md
lists the original for a later PR to fold), vectorised over the batch."""

from __future__ import annotations

import numpy as np

from benchmarks.reference import quantize, scatter_rows


def row_dim(model: dict) -> int:
    return int(model["factor_num"]) * int(model["field_num"]) + 1


def scores_and_row_grads(model, P, inv, x, fields, quant=None):
    B, L = inv.shape
    U, D = P.shape
    rows = quantize(P, quant)[inv]                    # [B, L, D]
    xq = quantize(x, quant)
    w = rows[..., -1]
    F = int(model["field_num"])
    k = (D - 1) // F
    v = rows[..., :-1].reshape(B, L, F, k)
    f = np.broadcast_to(np.asarray(fields), (B, L))
    # a[b, i, j, :] = x_i * v_i[field_j]
    a = quantize(np.take_along_axis(
        v, np.broadcast_to(f[:, None, :, None], (B, L, L, 1)), axis=2)
        * xq[:, :, None, None], quant)
    pair = np.einsum("bijk,bjik->bij", a, a)
    off = ~np.eye(L, dtype=bool)
    score = (w * xq).sum(axis=1) + 0.5 * (pair * off).sum(axis=(1, 2))

    def backward(ds):
        # d score / d v_i[g] = x_i * sum_{j != i, field_j = g} a[j, i]
        at = np.swapaxes(a, 1, 2) * off[None, :, :, None]   # [b, i, j, k]
        onehot = (f[:, :, None] == np.arange(F)[None, None, :]
                  ).astype(np.float64)                      # [b, j, g]
        gv = np.einsum("bijk,bjg->bigk", at, onehot)
        g = np.empty((B, L, D))
        g[..., -1] = ds[:, None] * xq
        g[..., :-1] = (ds[:, None, None, None] * xq[:, :, None, None]
                       * gv).reshape(B, L, F * k)
        return scatter_rows(inv, g, U)
    return score, backward
