"""The plain reference: forward, logistic or squared loss, hand-derived
gradients and sparse Adagrad in NumPy float64. It imports nothing of
the program and takes nothing the program made: examples come from the
benchmark's corpus, weights from benchmarks/weights.py.

What differs between model families is one function and the row width,
and lives in a file of its own, ``references/<family>.py``, which a
configuration names (``reference_family``; README "Adding ... a model
family"). ``family_of`` is the one seam to it. What the families share
is here, once: the loss, the regulariser, Adagrad over the touched
rows, what predict() writes, the worst-leaf gap, bfloat16 rounding.

``quant="bf16"`` is the control of "How correct is decided": the same
mathematics with the gathered rows, the values and the interaction's
intermediate products rounded to bfloat16, the nearest precision below
the float32 the configurations state."""

from __future__ import annotations

import importlib
import os
from typing import Dict, Optional, Tuple

import numpy as np

REFERENCES_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "references")


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round-to-nearest-even to bfloat16, returned as float64."""
    f = np.ascontiguousarray(x, dtype=np.float32)
    u = f.view(np.uint32).astype(np.uint64)
    u = (u + np.uint64(0x7FFF) + ((u >> np.uint64(16)) & np.uint64(1))) \
        & np.uint64(0xFFFF0000)
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def quantize(x, quant):
    return to_bf16(x) if quant == "bf16" else x


def scatter_rows(inv: np.ndarray, g_rows: np.ndarray, U: int) -> np.ndarray:
    """Sum the per-cell row gradients [B, L, D] into the rows they
    were read from: [U, D]."""
    D = g_rows.shape[-1]
    flat = inv.ravel()
    g2 = g_rows.reshape(flat.size, D)
    out = np.empty((U, D))
    for c in range(D):
        out[:, c] = np.bincount(flat, weights=g2[:, c], minlength=U)
    return out


def families() -> list:
    return sorted(f[:-3] for f in os.listdir(REFERENCES_DIR)
                  if f.endswith(".py") and not f.startswith("_"))


def family_of(model: dict):
    """The module ``references/<model["reference_family"]>.py``: its
    ``scores_and_row_grads`` and ``row_dim``. A description without the
    key, a name with no file, or a family whose row is not as wide as
    the program's is an error, never a default."""
    name = model.get("reference_family")
    if not name:
        raise KeyError(
            "the configuration names no reference_family: its file must "
            "say which benchmarks/references/<family>.py is the plain "
            f"reference of its model (there: {families()})")
    try:
        mod = importlib.import_module("benchmarks.references." + name)
    except ModuleNotFoundError as e:
        if e.name != "benchmarks.references." + name:
            raise
        raise KeyError(
            f"no reference family {name!r}: add benchmarks/references/"
            f"{name}.py with scores_and_row_grads and row_dim (there: "
            f"{families()})") from None
    if mod.row_dim(model) != model["row_dim"]:
        raise ValueError(
            f"reference family {name!r} has rows of {mod.row_dim(model)} "
            f"columns and the program's configuration {model['row_dim']}: "
            "it is the reference of another model")
    return mod


def scores_and_row_grads(model: dict, P: np.ndarray, inv: np.ndarray,
                         x: np.ndarray, fields: np.ndarray,
                         quant: Optional[str] = None
                         ) -> Tuple[np.ndarray, "callable"]:
    """Scores [B] of a batch whose feature (b, l) reads row
    ``P[inv[b, l]]`` with value ``x[b, l]``, and a function mapping
    dLoss/dscore [B] to the gradient w.r.t. ``P`` ([U, D]): the
    model's family computes both."""
    return family_of(model).scores_and_row_grads(model, P, inv, x, fields,
                                                 quant)


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
        Pq = quantize(P, self.quant)
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
