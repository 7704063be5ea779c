"""The plain reference: FM / FFM forward, logistic or squared loss,
hand-derived gradients and sparse Adagrad in NumPy float64. It imports
nothing of the program and takes nothing the program made: examples
come from the benchmark's corpus, weights from benchmarks/weights.py.

Arithmetic copied from fast_tffm_tpu/models/oracle.py and
data/synth.numpy_*_train_predict (sound; listed in PERF.md for a later
PR to fold), vectorised over the batch.

``quant="bf16"`` is the control of "How correct is decided": the same
mathematics with the gathered rows, the values and the interaction's
intermediate products rounded to bfloat16, the nearest precision below
the float32 the configurations state."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round-to-nearest-even to bfloat16, returned as float64."""
    f = np.ascontiguousarray(x, dtype=np.float32)
    u = f.view(np.uint32).astype(np.uint64)
    u = (u + np.uint64(0x7FFF) + ((u >> np.uint64(16)) & np.uint64(1))) \
        & np.uint64(0xFFFF0000)
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def _q(x, quant):
    return to_bf16(x) if quant == "bf16" else x


def scores_and_row_grads(model: dict, P: np.ndarray, inv: np.ndarray,
                         x: np.ndarray, fields: np.ndarray,
                         quant: Optional[str] = None
                         ) -> Tuple[np.ndarray, "callable"]:
    """Scores [B] of a batch whose feature (b, l) reads row
    ``P[inv[b, l]]`` with value ``x[b, l]``, and a function mapping
    dLoss/dscore [B] to the gradient w.r.t. ``P`` ([U, D])."""
    B, L = inv.shape
    U, D = P.shape
    rows = _q(P, quant)[inv]                          # [B, L, D]
    xq = _q(x, quant)
    w = rows[..., -1]
    flat = inv.ravel()

    def scatter(g_rows):                              # [B, L, D] -> [U, D]
        out = np.empty((U, D))
        g2 = g_rows.reshape(B * L, D)
        for c in range(D):
            out[:, c] = np.bincount(flat, weights=g2[:, c], minlength=U)
        return out

    if model["model_type"] == "fm":
        v = rows[..., :-1]
        z = _q(v * xq[..., None], quant)              # [B, L, k]
        s = _q(z.sum(axis=1), quant)                  # [B, k]
        score = (w * xq).sum(axis=1) + 0.5 * (
            np.square(s) - np.square(z).sum(axis=1)).sum(axis=-1)

        def backward(ds):
            g = np.empty((B, L, D))
            g[..., -1] = ds[:, None] * xq
            g[..., :-1] = (ds[:, None, None] * xq[..., None]
                           * (s[:, None, :] - z))
            return scatter(g)
        return score, backward

    F = int(model["field_num"])
    k = (D - 1) // F
    v = rows[..., :-1].reshape(B, L, F, k)
    f = np.broadcast_to(np.asarray(fields), (B, L))
    # a[b, i, j, :] = x_i * v_i[field_j]
    a = _q(np.take_along_axis(
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
        return scatter(g)
    return score, backward


def per_example_loss(model: dict, score, y):
    """(loss, dloss/dscore) per example."""
    if model["loss_type"] == "logistic":
        loss = (np.maximum(score, 0.0) - score * y
                + np.log1p(np.exp(-np.abs(score))))
        return loss, 1.0 / (1.0 + np.exp(-score)) - y
    return np.square(score - y), 2.0 * (score - y)


class ReferenceTrainer:
    """Sparse-Adagrad training of the rows a few batches touch."""

    def __init__(self, model: dict, row_ids: np.ndarray,
                 table_rows: np.ndarray, quant: Optional[str] = None):
        self.model = model
        self.row_ids = np.asarray(row_ids)            # sorted, unique
        self.table = np.asarray(table_rows, dtype=np.float64).copy()
        self.table0 = self.table.copy()
        self.acc = np.full_like(self.table, float(model["adagrad_init"]))
        self.quant = quant
        self.last_grad = None
        self.last_touched = None

    def step(self, rows, x, y, weights, fields) -> float:
        """One step on a batch given as table rows [B, L] (padding
        cells carry value 0); returns the loss before the update."""
        m = self.model
        idx = np.searchsorted(self.row_ids, rows)
        live = np.asarray(x) != 0
        uniq, inv = np.unique(idx, return_inverse=True)
        inv = inv.reshape(idx.shape)
        P = self.table[uniq]
        score, backward = scores_and_row_grads(m, P, inv, x, fields,
                                               self.quant)
        per, dper = per_example_loss(m, score, y)
        wsum = weights.sum()
        touched = np.zeros(len(uniq), dtype=bool)
        touched[inv[live]] = True
        Pq = _q(P, self.quant)
        reg = (m["factor_lambda"] * np.square(Pq[touched, :-1]).sum()
               + m["bias_lambda"] * np.square(Pq[touched, -1]).sum())
        loss = float((per * weights).sum() / wsum + reg)
        g = backward(dper * weights / wsum)
        g[:, :-1] += 2.0 * m["factor_lambda"] * Pq[:, :-1] * touched[:, None]
        g[:, -1] += 2.0 * m["bias_lambda"] * Pq[:, -1] * touched
        g *= touched[:, None]
        self.acc[uniq] += np.square(g)
        self.table[uniq] -= m["learning_rate"] * g / np.sqrt(self.acc[uniq])
        self.last_grad, self.last_touched = g, uniq
        return loss


def predict_scores(model: dict, table_rows: np.ndarray, inv: np.ndarray,
                   x: np.ndarray, fields, quant: Optional[str] = None):
    """What predict() writes: sigmoid(score) for logistic loss."""
    score, _ = scores_and_row_grads(model, np.asarray(table_rows,
                                                      np.float64),
                                    inv, x, fields, quant)
    if model["loss_type"] == "logistic":
        return 1.0 / (1.0 + np.exp(-score))
    return score


def leaf_norm_gaps(prog: np.ndarray, ref: np.ndarray) -> Dict[str, float]:
    """Worst-leaf gap between the program's norm and the reference's.
    A leaf is one column of the table (the bias, each latent factor),
    over the rows compared. The gap |‖p‖-‖r‖| is measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some leaves' gradients are all but zero)."""
    pn = np.sqrt(np.square(np.asarray(prog, np.float64)).sum(axis=0))
    rn = np.sqrt(np.square(np.asarray(ref, np.float64)).sum(axis=0))
    den = np.maximum(rn, np.median(rn))
    if not np.all(den > 0):
        return {"worst": float("inf"), "leaf": -1}
    gaps = np.abs(pn - rn) / den
    return {"worst": float(gaps.max()), "leaf": int(gaps.argmax())}
