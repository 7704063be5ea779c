"""Driver of ``kind: train_eval`` traffic: ``drivers/train.py``'s run
with a held-out corpus that the program sweeps whole after every epoch
(``validation_files``), so that the job's wall holds the scorer, the
sweep's own data plane (the host unique in its builders, U fitted
slots a batch since PR 45), the chunked fetch and the streaming AUC beside the
train step: training to a quality target. The rate is the TRAINED
examples over all the time of the span, sweeps included.

Nothing of ``train.run`` is copied. For the call, names it looks up are
rebound, as its own ``Seams`` rebinds the program's and as
``train_bags.py`` does: ``harness.program_cfg`` (gains the held-out
corpus, made from seed + 1 by the training corpus's generator, and
``validation_files``), the step's probe (``LastStateProbe``: also
remembers the table the last step returned) and ``harness.finish``
(gains the sweep's checks, lines and what the readers need). Two names
of ``fast_tffm_tpu.train`` are rebound beside the step's seams:
``evaluate`` (every sweep's wall, AUC and examples, as the program got
them) and ``make_batch_scorer`` (the score calls: every call of the
first sweep is recorded, feed and device scores; that sweep lies in the
warm-up, and every later call passes straight through).

What decides ``correct`` beside the three training checks:
``score_abs_gap_max`` (the first ``checked_score_calls`` calls of the
first sweep against ``reference.predict_scores`` in float64, on the
rows read from the table the scorer was handed and the generator's own
record of the fed lines), ``auc_binned_abs_gap`` (the AUC that sweep
returned against ``auc_reference.exact_auc`` of its own device scores
under the generator's labels) and four counts that must be 0:
``sweeps_in_span_not_epochs``, ``sweep_examples_short``,
``validation_examples_not_in_corpus``, ``swept_rows_not_of_last_step``.
Two lines say what the controls read, which have to fail: the same
reference in bfloat16 in the program's place, and the generator's
labels in another order under the AUC."""

from __future__ import annotations

import importlib
import os
import time

import numpy as np

from benchmarks import (auc_reference, check, corpus as corpus_mod, harness,
                        reference)
from benchmarks.drivers import train as train_driver
from benchmarks.harness import RunFailed, say
from benchmarks.readers import telemetry_window

PROGRAM = "fast_tffm_tpu.train"
EVAL_SEAMS = ("evaluate", "make_batch_scorer")
# The sweep's leaves on the loop's thread (fast_tffm_tpu/train.py
# evaluate()), as their counters name them.
SWEEP_PHASES = ("open", "first_batch", "input_wait", "score_dispatch",
                "drain", "auc")


class LastStateProbe(train_driver.StepProbe):
    """The step's probe, which also counts every call and keeps hold of
    the table the last one returned (a reference, no copy: the next
    call donates it)."""

    last_table = None
    steps_run = 0

    def wrap(self, step):
        record = super().wrap(step)

        def probed(*args, **kwargs):
            out = record(*args, **kwargs)
            self.steps_run += 1
            self.last_table = out[0]
            return out
        return probed


class SweepProbe:
    """Every sweep's wall, result and score calls; of the first sweep
    every call's feed and device scores, and for its first ``n_check``
    calls the rows they read, from the table the scorer was handed."""

    def __init__(self, n_check: int):
        self.n_check = n_check
        self.steps = None       # the run's LastStateProbe, once made
        self.sweeps = []        # {"t0", "t1", "auc", "n", "calls", "step"}
        self.feeds, self.scores = [], []
        self.rows = []          # (ids, table rows) of each checked call
        self.rows_of_last_step = None

    def wrap_evaluate(self, evaluate):
        def probed(*args, **kwargs):
            rec = {"t0": time.monotonic(), "calls": 0,
                   "step": self.steps.steps_run}
            self.sweeps.append(rec)
            auc, n = evaluate(*args, **kwargs)
            rec.update(t1=time.monotonic(), auc=float(auc), n=int(n))
            return auc, n
        return probed

    def wrap_builder(self, make_batch_scorer):
        def build(*args, **kwargs):
            score = make_batch_scorer(*args, **kwargs)

            def probed(table, batch):
                if not self.sweeps:
                    raise RunFailed("a score call outside evaluate(): the "
                                    "benchmark's seam into the sweep moved")
                rec = self.sweeps[-1]
                if len(self.sweeps) > 1:
                    rec["calls"] += 1
                    return score(table, batch)
                # the scorer may consume its arguments: keep them first
                feed = {k: np.asarray(v) for k, v in batch.items()
                        if v is not None}
                out = score(table, batch)
                self.feeds.append(feed)
                self.scores.append(out)
                if rec["calls"] < self.n_check:
                    ids = np.unique(check.feed_rows(feed))
                    self.rows.append((ids, self.steps._rows_of(table, ids)))
                    if rec["calls"] == 0 and self.steps.last_table is not None:
                        self.rows_of_last_step = self.steps._rows_of(
                            self.steps.last_table, ids)
                rec["calls"] += 1
                return out
            return probed
        return build


def make_heldout(run):
    """The held-out day: the training corpus's generator and
    cardinalities at seed + 1, ``heldout_batches`` batches long."""
    t = time.monotonic()
    tr, conf = run.cell.traffic, run.cell.config
    prog = conf["program"]
    n_lines = int(tr["heldout_batches"]) * int(prog["Train"]["batch_size"])
    c = corpus_mod.generate(
        conf["features"], prog["General"].get("model_type", "fm"),
        int(prog["General"]["vocabulary_size"]), n_lines, int(run.seed) + 1,
        os.path.join(run.work_dir, "heldout"), int(tr["heldout_files"]),
        "heldout")
    run.setup["corpus_s"] = (run.setup.get("corpus_s", 0.0)
                             + time.monotonic() - t)
    return c


def fed_lines(heldout, feeds) -> np.ndarray:
    """Held-out line of every fed example, in feed order (-1: none;
    -2: a padding example, every cell of it empty). A score call is fed
    no label: lines match by rows and values."""
    no_label = np.zeros(len(heldout.labels), dtype=np.uint8)
    sigs = corpus_mod.example_signatures(no_label, heldout.rows,
                                         heldout.millis)
    order = np.argsort(sigs, kind="stable")
    out = []
    for feed in feeds:
        n = len(feed["vals"])
        out.append(check.match_feed_to_corpus(
            sigs[order], order, np.zeros(n), check.feed_rows(feed),
            feed["vals"], (feed["vals"] != 0).any(axis=1)))
    return np.concatenate(out) if out else np.zeros(0, dtype=np.int64)


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-np.asarray(x, dtype=np.float64)))


def reference_of_calls(model, heldout, probe, lines, quant=None):
    """What the checked calls should have returned (as ``predict()``
    would write them): the reference over the generator's record of
    the lines each was fed, on the rows the scorer's table held."""
    out, at = [], 0
    for feed, (ids, rows) in zip(probe.feeds, probe.rows):
        line = lines[at:at + len(feed["vals"])]
        at += len(feed["vals"])
        cells = heldout.rows[line[line >= 0]]
        line = line[line >= 0]
        uniq, inv = np.unique(cells, return_inverse=True)
        out.append(reference.predict_scores(
            model, rows[np.searchsorted(ids, uniq)],
            inv.reshape(cells.shape), heldout.vals[line], heldout.fields,
            quant))
    return np.concatenate(out)


def sweep_checks(model, heldout, probe, limits, epochs_in_span,
                 counted_sweeps, counted_examples, scores=None,
                 labels=None):
    """The sweep's six numbers beside their limits, and what the lines
    say beside them. ``counted_sweeps``: the sweeps of the span by the
    program's counter (None where it has none) and by the probe's count
    of those that reached the scorer. ``scores``: what stands in the
    program's place for the checked calls (a test's control);
    ``labels``: what stands in the generator's record under the AUC."""
    n_lines = len(heldout.labels)
    done = [s for s in probe.sweeps if "t1" in s]
    lines = fed_lines(heldout, probe.feeds)
    real = lines >= 0
    never_fed = n_lines - len(np.unique(lines[real]))
    checks = [{"name": "validation_examples_not_in_corpus",
               "value": int((lines == -1).sum()) + int(never_fed),
               "limit": 0},
              {"name": "sweeps_in_span_not_epochs",
               "value": int(max(abs(epochs_in_span - x)
                                for x in counted_sweeps if x is not None)),
               "limit": 0}]
    short = [abs(n_lines - s["n"]) for s in done] or [n_lines]
    if counted_examples is not None and counted_sweeps[0] is not None:
        short.append(abs(counted_sweeps[0] * n_lines - counted_examples))
    checks.append({"name": "sweep_examples_short", "value": int(max(short)),
                   "limit": 0})
    if probe.rows and probe.rows_of_last_step is not None:
        differ = int((probe.rows[0][1].view(np.uint32)
                      != probe.rows_of_last_step.view(np.uint32)
                      ).any(axis=1).sum())
    else:           # no sweep, or no step before it: nothing to compare
        differ = n_lines
    checks.append({"name": "swept_rows_not_of_last_step", "value": differ,
                   "limit": 0})
    if checks[0]["value"] or len(probe.rows) < probe.n_check or not done:
        return checks, None
    ref = reference_of_calls(model, heldout, probe, lines)
    swept = np.concatenate([np.asarray(s) for s in probe.scores])
    if scores is None:
        n_checked = sum(len(f["vals"]) for f in probe.feeds[:probe.n_check])
        scores = sigmoid(swept[:n_checked][real[:n_checked]])
    checks.append({"name": "score_abs_gap_max",
                   "value": float(np.abs(scores - ref).max()),
                   "limit": limits["score_abs_gap_max"]})
    swept = swept[real]
    if labels is None:
        labels = heldout.labels[lines[real]]
    exact = auc_reference.exact_auc(swept, labels)
    checks.append({"name": "auc_binned_abs_gap",
                   "value": abs(done[0]["auc"] - exact),
                   "limit": limits["auc_binned_abs_gap_max"]})
    # The two controls, which have to fail: the reference in bfloat16
    # where the program's scores stood, and the generator's labels in
    # another order under the AUC.
    control = float(np.abs(reference_of_calls(
        model, heldout, probe, lines, quant="bf16") - ref).max())
    shuffled = np.asarray(labels)[np.random.default_rng(0).permutation(
        len(labels))]
    return checks, {"exact_auc": exact, "control_bf16": control,
                    "control_labels": abs(done[0]["auc"]
                                          - auc_reference.exact_auc(
                                              swept, shuffled)),
                    "scored": int(real.sum())}


def say_sweeps(ctx, probe, info, limits) -> None:
    done = [s for s in probe.sweeps if "t1" in s]
    walls = [s["t1"] - s["t0"] for s in done]
    if done:
        say(f"sweeps: {len(done)} whole, {done[0]['n']} examples the "
            f"first, wall {min(walls):.3f} to {max(walls):.3f} s (the "
            f"first, which makes the score program ready, {walls[0]:.3f});"
            f" AUC {done[0]['auc']:.6f} the first, {done[-1]['auc']:.6f} "
            "the last")
    if info:
        say(f"first sweep: binned AUC {done[0]['auc']!r}, exact AUC of "
            f"its {info['scored']} device scores {info['exact_auc']!r}")
        for what, name, reading in (
                ("the reference in bfloat16", "score_abs_gap_max",
                 info["control_bf16"]),
                ("the labels in another order", "auc_binned_abs_gap_max",
                 info["control_labels"])):
            say(f"control: {what} reads {name} {reading!r} (limit "
                f"{limits[name]!r}): " + ("it fails, as it must"
                                          if reading > limits[name]
                                          else "IT PASSES"))
    sweeps = telemetry_window.window_delta(ctx, "validation/sweeps")
    wall = telemetry_window.window_delta(ctx, "train/validation_seconds")
    if wall is None:
        return
    said = f"the span's sweeps: {wall:.3f} s inside train/validation"
    if sweeps:
        parts = {p: telemetry_window.window_delta(
            ctx, f"validation/{p}_seconds") for p in SWEEP_PHASES}
        named = sum(v for v in parts.values() if v)
        said += (f", {sweeps:.0f} sweeps, {wall / sweeps:.4f} s a sweep: "
                 + ", ".join(f"{p} {v / sweeps:.4f}"
                             for p, v in parts.items() if v is not None)
                 + f"; under no leaf {(wall - named) / sweeps:.4f}")
    unnamed = telemetry_window.window_delta(ctx, "train/loop_unnamed_seconds")
    if unnamed is not None:
        said += (f"; the loop's wall under no phase "
                 f"{100.0 * unnamed / ctx['window_wall_s']:.3f}% of the span")
    say(said)


def run(run, device, breaker=None, eval_breaker=None) -> str:
    """``eval_breaker`` (tests): wraps ``evaluate`` below the probe."""
    tr = run.cell.traffic
    n_check = int(tr["checked_score_calls"])
    limits = run.cell.config["check_limits"]["train_eval"]
    if (int(tr["warmup_readings"]) * int(tr["steps_per_reading"])
            <= int(tr["corpus_batches"]) * int(tr.get("corpus_passes", 1))):
        raise RunFailed("the warm-up must hold one whole epoch and its "
                        "sweep: the score program is made ready there")
    held = {}
    program = importlib.import_module(PROGRAM)
    kept = {name: getattr(program, name, None) for name in EVAL_SEAMS}
    for name, value in kept.items():
        if value is None:
            raise RunFailed(f"{PROGRAM}.{name} is gone: the benchmark's "
                            "seam into the validation sweep moved")
    probe = SweepProbe(n_check)

    def make_probe(n):
        probe.steps = LastStateProbe(n)
        return probe.steps

    def program_cfg(config, extra, work_dir):
        held["corpus"] = make_heldout(run)
        extra = {sec: dict(kv) for sec, kv in extra.items()}
        extra["Train"]["validation_files"] = list(held["corpus"].files)
        cfg = kept_cfg(config, extra, work_dir)
        held["model"] = harness.model_of(cfg, config)
        return cfg

    def finish(run_, device_, end_to_end, checks, check_seconds, **kw):
        t = time.monotonic()
        ctx = kw["ctx"]
        epochs = telemetry_window.window_delta(ctx, "train/epochs")
        s_first, s_last = ctx["window_steps"]
        reached = sum(1 for s in probe.sweeps
                      if s["calls"] and s_first < s["step"] <= s_last)
        more, info = sweep_checks(
            held["model"], held["corpus"], probe, limits, int(epochs or 0),
            (telemetry_window.window_delta(ctx, "validation/sweeps"),
             reached),
            telemetry_window.window_delta(ctx, "validation/examples"))
        say_sweeps(ctx, probe, info, limits)
        if probe.feeds:
            rows = [len(np.unique(check.feed_rows(f)[f["vals"] != 0]))
                    for f in probe.feeds[:n_check]]
            cells = [int((f["vals"] != 0).sum())
                     for f in probe.feeds[:n_check]]
            ctx["score_call"] = {
                "distinct_rows": float(np.mean(rows)),
                "cells": float(np.mean(cells)),
                "examples": float(len(probe.feeds[0]["vals"]))}
            say(f"score calls: {ctx['score_call']['distinct_rows']:.0f} "
                f"distinct rows, {ctx['score_call']['cells']:.0f} real "
                f"cells of {probe.feeds[0]['vals'].size} slots a call")
        return kept_finish(run_, device_, end_to_end, checks + more,
                           check_seconds + time.monotonic() - t, **kw)

    kept_probe, kept_cfg, kept_finish = (
        train_driver.StepProbe, harness.program_cfg, harness.finish)
    train_driver.StepProbe = make_probe
    harness.program_cfg, harness.finish = program_cfg, finish
    program.evaluate = probe.wrap_evaluate(
        kept["evaluate"] if eval_breaker is None
        else eval_breaker(kept["evaluate"]))
    program.make_batch_scorer = probe.wrap_builder(kept["make_batch_scorer"])
    try:
        return train_driver.run(run, device, breaker)
    finally:
        train_driver.StepProbe = kept_probe
        harness.program_cfg, harness.finish = kept_cfg, kept_finish
        for name, value in kept.items():
            setattr(program, name, value)
