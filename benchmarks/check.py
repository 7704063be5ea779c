"""The comparisons that decide ``correct`` (README "correct";
PERF.md section 2 gives the readings each limit was set from).

Train: what the first steps of the timed step object produced — each
step's loss, the first gradient as the optimizer got it (worked out
from the state after one step), the parameters' change after the
checked steps — against the NumPy float64 reference following the same
examples from its own copy of the corpus and of the weights. ``model``
is ``harness.model_of``'s description; the reference reaches the
model's family through it (``reference.family_of``).
Predict: a seeded sample of the scores the window's last sweep wrote,
against the reference's."""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks import reference, weights
from benchmarks.corpus import Corpus, example_signatures


def match_feed_to_corpus(sorted_sigs, order, labels, rows, vals, weights_):
    """Corpus line of each fed example (-1: none): the feed is the
    program's parse of the benchmark's text, so every real example
    must be a corpus line as the generator knows it (label, hashed
    rows, values). ``sorted_sigs`` / ``order``: the corpus lines'
    signatures, sorted, and the lines in that order."""
    millis = np.rint(np.asarray(vals, np.float64) * 1000).astype(np.int64)
    sig = example_signatures(np.asarray(labels).astype(np.int64), rows,
                             millis)
    pos = np.minimum(np.searchsorted(sorted_sigs, sig), len(order) - 1)
    line = np.where(sorted_sigs[pos] == sig, order[pos], -1)
    line[np.asarray(weights_) == 0] = -2          # padding examples
    return line


def feed_rows(feed: Dict[str, np.ndarray]) -> np.ndarray:
    """Table row of every (example, slot) of a fed batch, for the
    layouts the program ships: raw ids (device dedup) or a unique-id
    table plus indices into it (host dedup, mesh)."""
    if "local_idx" not in feed or "vals" not in feed:
        raise ValueError(
            "the step's feed has no padded rectangles (keys: "
            f"{sorted(feed)}): the output check cannot read this wire "
            "format yet (PERF.md, Open questions)")
    if feed.get("uniq_ids") is None:
        return np.asarray(feed["local_idx"]).astype(np.int64)
    return np.asarray(feed["uniq_ids"])[
        np.asarray(feed["local_idx"])].astype(np.int64)


def train_checks(model: dict, cfg_rows: int, value_range: float,
                 seed: int, corpus: Corpus, probe, limits: dict,
                 batch_size: int) -> List[dict]:
    n = len(probe.feeds)
    batches = []
    unmatched = short = 0
    csig = corpus.signatures()
    order = np.argsort(csig, kind="stable")
    csig = csig[order]
    for feed in probe.feeds:
        rows = feed_rows(feed)
        line = match_feed_to_corpus(csig, order, feed["labels"], rows,
                                    feed["vals"], feed["weights"])
        unmatched += int((line == -1).sum())
        real = line >= 0
        # The rate credits batch_size examples a step (the corpus
        # holds whole batches): examples dropped or zero-weighted
        # before the step are work not done.
        short += abs(int(batch_size) - int(real.sum()))
        # The reference follows the corpus's own record of those lines.
        batches.append((corpus.rows[line[real]], corpus.vals[line[real]],
                        corpus.labels[line[real]].astype(np.float64),
                        np.asarray(feed["weights"], np.float64)[real]))
    checks = [{"name": "feed_examples_not_in_corpus", "value": unmatched,
               "limit": 0},
              {"name": "feed_examples_short_of_batch", "value": short,
               "limit": 0}]
    if unmatched or any(len(b[0]) == 0 for b in batches):
        return checks
    pad = cfg_rows - 1
    rows_all = np.unique(np.concatenate(
        [b[0].ravel() for b in batches] + [np.array([pad])]))
    t0 = weights.table_rows_numpy(rows_all, model["row_dim"], seed,
                                  value_range, cfg_rows)
    ref = reference.ReferenceTrainer(model, rows_all, t0)
    losses, g1, rows1 = [], None, None
    for i, (r, x, y, w) in enumerate(batches):
        losses.append(ref.step(r, x, y, w, corpus.fields))
        if i == 0:
            g1, rows1 = ref.last_grad, rows_all[ref.last_touched]
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(probe.losses, losses))
    checks.append({"name": "loss_rel_gap_max", "value": float(loss_gap),
                   "limit": limits["loss_rel_gap_max"]})
    # First gradient as the optimizer got it, from the state after one
    # step: table1 = table0 - lr * g / sqrt(acc1).
    ids1, t1, a1 = probe.after_first
    sel = np.searchsorted(rows_all, ids1)
    g_prog = ((t0[sel].astype(np.float64) - t1.astype(np.float64))
              * np.sqrt(a1.astype(np.float64)) / model["learning_rate"])
    g_ref = np.zeros_like(g_prog)
    g_ref[np.searchsorted(ids1, rows1)] = g1
    gap = reference.leaf_norm_gaps(g_prog, g_ref)
    checks.append({"name": "grad_norm_gap_worst_leaf",
                   "value": gap["worst"],
                   "limit": limits["grad_norm_gap_worst_leaf"]})
    idsn, tn = probe.after_last
    sel = np.searchsorted(rows_all, idsn)
    d_prog = tn.astype(np.float64) - t0[sel].astype(np.float64)
    d_ref = (ref.table - ref.table0)[sel]
    gap = reference.leaf_norm_gaps(d_prog, d_ref)
    checks.append({"name": f"update_norm_gap_worst_leaf_{n}_steps",
                   "value": gap["worst"],
                   "limit": limits["update_norm_gap_worst_leaf"]})
    return checks


def predict_checks(model: dict, cfg_rows: int, value_range: float,
                   seed: int, corpus: Corpus, scores: np.ndarray,
                   sample: np.ndarray, limits: dict,
                   swept_lines: int) -> List[dict]:
    """``scores``: what one call wrote, in the order of its files: the
    corpus ``swept_lines / len(corpus)`` times over. ``sample``
    indexes the swept lines."""
    checks = [{"name": "score_lines_missing",
               "value": abs(len(scores) - int(swept_lines)),
               "limit": 0}]
    if checks[0]["value"]:
        return checks
    ref = reference_scores(model, cfg_rows, value_range, seed, corpus,
                           sample % len(corpus.labels))
    gap = float(np.abs(scores[sample] - ref).max())
    checks.append({"name": "score_abs_gap_max", "value": gap,
                   "limit": limits["score_abs_gap_max"]})
    return checks


def reference_scores(model, cfg_rows, value_range, seed, corpus, sample,
                     quant=None):
    rows = corpus.rows[sample]
    uniq, inv = np.unique(rows, return_inverse=True)
    t = weights.table_rows_numpy(uniq, model["row_dim"], seed,
                                 value_range, cfg_rows)
    return reference.predict_scores(model, t, inv.reshape(rows.shape),
                                    corpus.vals[sample], corpus.fields,
                                    quant)
