"""Second-order FM (Rendle 2010): rows ``[v (k) | w]``,

    score = sum_l w_l x_l + 1/2 sum_k ((sum_l z_lk)^2 - sum_l z_lk^2),
    z_l = x_l v_l.

Arithmetic copied from fast_tffm_tpu/models/oracle.py (sound; PERF.md
lists the original for a later PR to fold), vectorised over the batch."""

from __future__ import annotations

import numpy as np

from benchmarks.reference import quantize, scatter_rows


def row_dim(model: dict) -> int:
    return int(model["factor_num"]) + 1


def scores_and_row_grads(model, P, inv, x, fields, quant=None):
    B, L = inv.shape
    U, D = P.shape
    rows = quantize(P, quant)[inv]                    # [B, L, D]
    xq = quantize(x, quant)
    w = rows[..., -1]
    v = rows[..., :-1]
    z = quantize(v * xq[..., None], quant)            # [B, L, k]
    s = quantize(z.sum(axis=1), quant)                # [B, k]
    score = (w * xq).sum(axis=1) + 0.5 * (
        np.square(s) - np.square(z).sum(axis=1)).sum(axis=-1)

    def backward(ds):
        g = np.empty((B, L, D))
        g[..., -1] = ds[:, None] * xq
        g[..., :-1] = (ds[:, None, None] * xq[..., None]
                       * (s[:, None, :] - z))
        return scatter_rows(inv, g, U)
    return score, backward
