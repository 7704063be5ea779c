"""Driver of ``kind: train`` traffic: the program's own
``fast_tffm_tpu.train.train`` in this process, on a seeded corpus, its
loss lines stamped as sync points (README "How a rate is read")."""

from __future__ import annotations

import importlib
import logging
import math
import os
import re
import time

import numpy as np

from benchmarks import check, corpus as corpus_mod, harness, readings, weights
from benchmarks.harness import RunFailed, say
from benchmarks.readers import telemetry_window

E2E_RATE = "train_examples_per_s_per_chip"

# Where the program builds its compiled step and its initial state.
# The benchmark rebinds these names for the run: the step gets a probe
# that records what its first calls were fed and produced and is a
# plain pass-through afterwards; the state comes from
# benchmarks/weights.py so that the reference can make the same rows.
# A seam that moved is an error (PERF.md lists the step-loop function
# that would replace all of this).
STEP_SEAMS = (("fast_tffm_tpu.train", "make_train_step"),
              ("fast_tffm_tpu.models.fm", "make_packed_train_step"),
              ("fast_tffm_tpu.parallel.sharded", "make_sharded_train_step"))
_LOSS_LINE = re.compile(r"^step (\d+) epoch (\d+) loss (\S+) examples/sec")


class WindowClosed(BaseException):
    """Raised out of the loss-line handler when the window has closed:
    it unwinds train() through its own finally blocks (telemetry
    flushed, no final checkpoint, no export)."""


class StepProbe:
    """Wraps the compiled step. Its first ``n_check`` calls — the
    window's own call and feed — are recorded: the feed, the loss, and
    the touched rows of the state they returned."""

    def __init__(self, n_check: int):
        self.n_check = n_check
        self.calls = 0
        self.feeds, self.losses = [], []
        self.after_first = self.after_last = None
        self._gather = None

    def _rows_of(self, arr, ids):
        import jax
        if self._gather is None:
            self._gather = jax.jit(lambda t, i: t[i])
        size = 1 << max(int(len(ids) - 1).bit_length(), 10)
        padded = np.full(size, ids[-1], dtype=np.int32)
        padded[:len(ids)] = ids
        return np.asarray(self._gather(arr, padded))[:len(ids)]

    def wrap(self, step):
        def probed(*args, **kwargs):
            if self.calls >= self.n_check:
                return step(*args, **kwargs)
            i = self.calls
            self.calls += 1
            feed = {k: np.asarray(v) for k, v in kwargs.items()
                    if v is not None}
            out = step(*args, **kwargs)
            table, acc, loss = out[0], out[1], out[2]
            self.feeds.append(feed)
            self.losses.append(float(loss))
            if i == 0:
                ids = np.unique(check.feed_rows(feed))
                self.after_first = (ids, self._rows_of(table, ids),
                                    self._rows_of(acc, ids))
            if i == self.n_check - 1:
                ids = np.unique(np.concatenate(
                    [check.feed_rows(f).ravel() for f in self.feeds]))
                self.after_last = (ids, self._rows_of(table, ids))
            return out
        return probed


class Seams:
    """Rebinds the program's step and state builders for one run."""

    def __init__(self, run, probe, breaker=None):
        self.run, self.probe, self.breaker = run, probe, breaker
        self._saved = []

    def _set(self, modname, name, value):
        mod = importlib.import_module(modname)
        if not hasattr(mod, name):
            raise RunFailed(f"{modname}.{name} is gone: the benchmark's "
                            "seam into the train step moved")
        self._saved.append((mod, name, getattr(mod, name)))
        setattr(mod, name, value)

    def __enter__(self):
        seed = self.run.seed

        def wrap_builder(orig):
            def build(*a, **k):
                step = orig(*a, **k)
                # A test's fault sits below the probe (in the step) or,
                # marked ``above_probe``, above it (in the data plane).
                above = getattr(self.breaker, "above_probe", False)
                if self.breaker is not None and not above:
                    step = self.breaker(step)
                step = self.probe.wrap(step)
                return self.breaker(step) if above else step
            return build

        for modname, name in STEP_SEAMS:
            mod = importlib.import_module(modname)
            self._set(modname, name, wrap_builder(getattr(mod, name)))

        def init_table(cfg, _seed=0):
            return weights.make_table(cfg.num_rows, cfg.row_dim, seed,
                                      cfg.init_value_range)

        def init_sharded_state(cfg, mesh, _seed=0):
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding
            from fast_tffm_tpu.parallel import sharded
            row = NamedSharding(mesh, sharded.ROW_SPEC)
            table = weights.make_table(
                cfg.num_rows, cfg.row_dim, seed, cfg.init_value_range,
                total_rows=cfg.ckpt_rows, sharding=row)
            acc = jax.jit(lambda: jnp.full(
                (cfg.ckpt_rows, cfg.row_dim), cfg.adagrad_init,
                jnp.float32), out_shardings=row)()
            return table, acc

        self._set("fast_tffm_tpu.train", "init_table", init_table)
        self._set("fast_tffm_tpu.parallel.sharded", "init_sharded_state",
                  init_sharded_state)
        return self

    def __exit__(self, *exc):
        for mod, name, value in reversed(self._saved):
            setattr(mod, name, value)


class SyncHandler(logging.Handler):
    """Stamps every loss line — a host event that waited for the
    device — with the benchmark's monotonic clock, opens the window
    after the warm-up steps and closes it ``seconds`` later."""

    def __init__(self, run, warmup_steps: int, tracer=None):
        super().__init__(level=logging.INFO)
        self.run, self.warmup_steps, self.tracer = run, warmup_steps, tracer
        self.syncs = []             # (t, step, loss)
        self.t_start = self.t_close = None

    def emit(self, record):
        msg = record.getMessage()
        if "deferring loss log lines" in msg:
            raise RunFailed(
                "the program deferred its loss lines (slow device link): "
                "they are no sync points, so no reading can be taken")
        m = _LOSS_LINE.match(msg)
        if m is None:
            return
        now = time.monotonic()
        self.syncs.append((now, int(m.group(1)), float(m.group(3))))
        if self.t_start is None:
            if int(m.group(1)) >= self.warmup_steps:
                self.t_start = now
                self.run.setup["setup_s"] = now - self.run.t0
                if self.tracer is not None:
                    self.tracer.start()
            return
        if self.tracer is not None and self.tracer.due():
            self.tracer.stop()
        if now >= self.t_start + self.run.seconds:
            self.t_close = now
            raise WindowClosed()


def make_corpus(run, prefix: str):
    t = time.monotonic()
    tr, conf = run.cell.traffic, run.cell.config
    prog = conf["program"]
    batch = int(prog["Train"]["batch_size"])
    n_lines = int(tr["corpus_batches"]) * batch
    c = corpus_mod.generate(
        conf["features"], prog["General"].get("model_type", "fm"),
        int(prog["General"]["vocabulary_size"]), n_lines, run.seed,
        os.path.join(run.work_dir, "corpus"), int(tr["corpus_files"]),
        prefix)
    run.setup["corpus_s"] = time.monotonic() - t
    return c


def run(run, device, breaker=None) -> str:
    tr = run.cell.traffic
    t = time.monotonic()
    from fast_tffm_tpu.train import train
    run.setup["import_program_s"] = time.monotonic() - t
    harness.enable_cache()
    harness.fresh_dir(run.work_dir)
    corpus = make_corpus(run, "train")
    steps_per_reading = int(tr["steps_per_reading"])
    passes = int(tr.get("corpus_passes", 1))
    metrics_path = os.path.join(run.work_dir, "metrics.jsonl")
    cfg = harness.program_cfg(run.cell.config, {
        "General": {"model_file": os.path.join(run.work_dir, "model", "fm")},
        "Train": {"train_files": corpus_mod.listed(corpus.files, passes),
                  "epoch_num": 1000000,
                  "seed": run.program_seed,
                  "log_steps": steps_per_reading,
                  "metrics_file": metrics_path,
                  "metrics_flush_steps": steps_per_reading}},
        run.work_dir)
    model = harness.model_of(cfg, run.cell.config)
    n_check = int(tr["checked_steps"])
    warmup_steps = int(tr["warmup_readings"]) * steps_per_reading
    if warmup_steps < n_check:
        raise RunFailed("the warm-up must hold the checked steps")
    probe = StepProbe(n_check)
    tracer = harness.TraceWindow(run) if run.trace else None
    handler = SyncHandler(run, warmup_steps, tracer)
    logger = logging.getLogger("fast_tffm_tpu")
    logger.addHandler(handler)
    try:
        with Seams(run, probe, breaker):
            train(cfg)
        raise RunFailed("train() returned before the window closed: the "
                        "epoch budget ran out")
    except WindowClosed:
        pass
    finally:
        logger.removeHandler(handler)
        if tracer is not None:
            tracer.stop()
    global_batch = cfg.batch_size
    epoch_steps = int(tr["corpus_batches"]) * passes
    if epoch_steps % steps_per_reading:
        raise RunFailed("an epoch must hold a whole number of readings")
    cycle = epoch_steps // steps_per_reading
    chips = max(int(device["count"]), 1)
    rd = readings.readings_between_syncs(
        [(t_, step * global_batch) for t_, step, _ in handler.syncs])
    rate = harness.window_rate(run, rd, handler.t_start, cycle, chips,
                               "examples/s/chip")
    in_span = [(t_, s, l) for t_, s, l in handler.syncs
               if rate["span"][0] <= t_ <= rate["span"][1]]
    (t_first, s_first, _), (t_last, s_last, _) = in_span[0], in_span[-1]
    failed = sum(steps_per_reading for _, _, l in in_span[1:]
                 if not math.isfinite(l))
    distinct = float(np.mean([len(np.unique(check.feed_rows(f)))
                              for f in probe.feeds]))
    say(f"distinct table rows per checked step: {distinct:.0f} of "
        f"{probe.feeds[0]['vals'].size} slots")
    ctx = {"telemetry_path": metrics_path,
           "window_steps": (s_first, s_last),
           "window_wall_s": t_last - t_first, "row_dim": cfg.row_dim,
           "distinct_rows_per_step": distinct,
           "median_reading": rate["median"]}
    t = time.monotonic()
    checks = check.train_checks(
        model, cfg.num_rows, cfg.init_value_range, run.seed, corpus, probe,
        run.cell.config["check_limits"]["train"], global_batch)
    # The rate credits steps x batch: the program's own count of the
    # real examples it trained on in the span has to be that many.
    counted = telemetry_window.window_delta(ctx, "train/examples")
    if counted is None:
        raise RunFailed("the telemetry stream has no snapshot at the "
                        f"span's sync points {s_first} and {s_last}")
    checks.append({"name": "span_examples_credited_not_counted",
                   "value": abs((s_last - s_first) * global_batch
                                - int(counted)), "limit": 0})
    return harness.finish(
        run, device, {E2E_RATE: rate["rate"]}, checks,
        time.monotonic() - t, attempted=s_last - s_first, failed=failed,
        tracer=tracer, ctx=ctx)
