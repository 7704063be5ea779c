"""The plain reference of the AUC a validation sweep logs: the exact
rank statistic (Mann-Whitney U over the pairs of one positive and one
negative example) in NumPy float64, a tie counting one half, by
midranks. Nothing of the program is imported, and nothing of
``fast_tffm_tpu.metrics`` (the program's binned estimator and its own
test oracle walk tie groups in a loop; this ranks)."""

from __future__ import annotations

import numpy as np


def exact_auc(scores, labels) -> float:
    """P(score of a positive > score of a negative) + P(equal) / 2.
    ``labels``: 1 (or anything >= 0.5) a positive. nan with no positive
    or no negative."""
    s = np.asarray(scores, dtype=np.float64).ravel()
    pos = np.asarray(labels).ravel() >= 0.5
    if s.shape != pos.shape:
        raise ValueError(f"{s.size} scores and {pos.size} labels")
    if np.isnan(s).any():
        raise ValueError("a score is NaN: it has no rank")
    n_pos = int(pos.sum())
    n_neg = s.size - n_pos
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    order = np.argsort(s, kind="stable")
    ranked = s[order]
    # A tie group occupies positions start+1 .. end (1-based) and each
    # of its members gets their mean.
    start = np.flatnonzero(np.concatenate(([True],
                                           ranked[1:] != ranked[:-1])))
    end = np.concatenate((start[1:], [s.size]))
    rank = np.empty(s.size, dtype=np.float64)
    rank[order] = np.repeat((start + end + 1) / 2.0, end - start)
    u = rank[pos].sum() - n_pos * (n_pos + 1) / 2.0
    return float(u / (float(n_pos) * float(n_neg)))
