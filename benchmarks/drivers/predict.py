"""Driver of ``kind: predict`` traffic: the program's own
``fast_tffm_tpu.predict.predict`` swept over a seeded corpus again and
again, on a seeded table made on the device (no checkpoint is
loaded); ``calls_per_reading`` consecutive calls are one reading.

A call sweeps the corpus listed ``corpus_passes`` times; a warm-up call
sweeps it listed ``warmup_passes`` times (default: as a measured call).
The same files give the same batches, so one short call makes every
score program ready and fills the page cache: set-up does not pay for
long calls that serve no reading."""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from benchmarks import check, corpus as corpus_mod, harness, weights
from benchmarks.drivers.train import make_corpus
from benchmarks.harness import say

E2E_RATE = "predict_examples_per_s"


def read_scores(paths) -> np.ndarray:
    out = []
    for p in paths:
        with open(p, "r", encoding="ascii") as fh:
            out.append(np.array(fh.read().split(), dtype=np.float64))
    return np.concatenate(out) if out else np.zeros(0)


def run(run, device, breaker=None) -> str:
    tr = run.cell.traffic
    t = time.monotonic()
    import jax
    from fast_tffm_tpu.predict import predict
    run.setup["import_program_s"] = time.monotonic() - t
    harness.enable_cache()
    harness.fresh_dir(run.work_dir)
    corpus = make_corpus(run, "predict")
    passes = int(tr.get("corpus_passes", 1))
    n_lines = len(corpus.labels) * passes       # one call sweeps these
    metrics_path = os.path.join(run.work_dir, "metrics.jsonl")
    cfg = harness.program_cfg(run.cell.config, {
        "General": {"model_file": os.path.join(run.work_dir, "model", "fm")},
        "Train": {"seed": run.program_seed,
                  "metrics_file": metrics_path},
        "Predict": {"predict_files": corpus_mod.listed(corpus.files,
                                                       passes),
                    "score_path": os.path.join(run.work_dir, "score")}},
        run.work_dir)
    model = harness.model_of(cfg, run.cell.config)
    value_range = float(tr["table_value_range"])
    t = time.monotonic()
    table = weights.make_table(cfg.num_rows, cfg.row_dim, run.seed,
                               value_range)
    if breaker is not None:
        table = breaker(table)
    jax.block_until_ready(table)
    run.setup["table_s"] = time.monotonic() - t
    warm = int(tr.get("warmup_passes", passes))
    if not 1 <= warm <= passes:
        raise harness.RunFailed(
            f"warmup_passes {warm} must lie in 1..corpus_passes {passes}")
    # corpus.listed puts the files first and then each pass's links, so
    # a prefix of whole passes is the corpus listed that many times
    warm_cfg = dataclasses.replace(
        cfg, predict_files=cfg.predict_files[:len(corpus.files) * warm])
    t = time.monotonic()
    for _ in range(int(tr["warmup_calls"])):
        predict(warm_cfg, table=table)
    run.setup["warmup_s"] = time.monotonic() - t
    t_start = time.monotonic()
    run.setup["setup_s"] = t_start - run.t0
    tracer = harness.TraceWindow(run) if run.trace else None
    if tracer is not None:
        tracer.start()
    calls = int(tr["calls_per_reading"])
    rd, failed, written = [], 0, []
    while True:
        a = time.monotonic()
        if a >= t_start + run.seconds:
            break
        for _ in range(calls):
            try:
                written = predict(cfg, table=table)
            except Exception as e:  # counted, and the run is not correct
                failed += 1
                say(f"predict() raised: {e!r}")
        rd.append((a, time.monotonic(), float(n_lines * calls)))
        if tracer is not None and tracer.due():
            tracer.stop()
    if tracer is not None:
        tracer.stop()
    rate = harness.window_rate(run, rd, t_start, 1, 1, "examples/s")
    t = time.monotonic()
    rng = np.random.default_rng([int(run.seed), 0x5A3B1E])
    sample = np.unique(np.concatenate(
        [rng.integers(0, n_lines, size=int(tr["checked_lines"])),
         [0, n_lines - 1]]))
    checks = check.predict_checks(
        model, cfg.num_rows, value_range, run.seed, corpus,
        read_scores(written), sample,
        run.cell.config["check_limits"]["predict"], n_lines)
    return harness.finish(
        run, device, {E2E_RATE: rate["rate"]}, checks,
        time.monotonic() - t, attempted=rate["n"] * calls, failed=failed,
        tracer=tracer, ctx={"telemetry_path": metrics_path,
                            "median_reading": rate["median"]})
