"""Driver of ``kind: train_stream`` traffic: the program's own
``fast_tffm_tpu.train.train`` in ``run_mode = stream``, catching up on
a backlog of sealed shards that is all there before it is called. The
shards are ``drivers/train.py``'s seeded corpus, one corpus file a
shard, listed ``backlog_passes`` times as links
``part-<nnnnnn>.libsvm`` (names that sort in pass order) with a
``.done`` marker each, and no ``STOP`` marker: the stream does not
end, the run ends when the window closes.

The step's probe, the seams, the loss-line handler and the corpus are
``drivers/train.py``'s. There is no epoch, so a cycle is one reading:
the rate is all the trained examples over all the time of the whole
readings inside the window. ``correct`` is the three train checks of
``check.train_checks`` on the first ``checked_steps`` stepped batches,
the three exact feed checks, and the stream's four, each 0
(``benchmarks/stream_reference.py`` is the reference):
``stream_batches_not_in_ledger_order`` and
``stream_lines_trained_twice_or_never`` over the first
``checked_stream_batches`` stepped batches (all in the warm-up),
``watermark_lines_off`` (the watermark the loop had adopted once the
last of them was stepped) and ``stream_idle_in_span`` (the program's
count of gets that found the prefetcher empty inside the span). A
backlog that runs dry fails the run: the benchmark, as the feed's
writer, then writes the ``STOP`` marker, ``train()`` returns and no
rate is printed."""

from __future__ import annotations

import logging
import math
import os
import threading
import time

import numpy as np

from benchmarks import check, corpus as corpus_mod, harness, readings
from benchmarks.drivers import train as train_driver
from benchmarks.harness import RunFailed, say
from benchmarks.readers import telemetry_window
from benchmarks.stream_reference import Shard, StreamReference, line_ends

E2E_RATE = train_driver.E2E_RATE
PROGRAM = "fast_tffm_tpu.train"
DRY_AFTER_S = 5.0       # no step for this long, with gets that came back
# empty: the backlog is eaten (a starved loop goes on within a second)


def write_shards(corpus, stream_dir: str, passes: int) -> None:
    """The backlog: the corpus's files listed ``passes`` times over as
    links whose names sort in pass order, a ``.done`` marker beside
    each."""
    os.makedirs(stream_dir)
    n = 0
    for _ in range(int(passes)):
        for path in corpus.files:
            name = os.path.join(stream_dir, f"part-{n:06d}.libsvm")
            os.symlink(os.path.abspath(path), name)
            with open(name + ".done", "w", encoding="utf-8"):
                pass
            n += 1


def reference_of(corpus, stream_dir: str, batch_size: int) -> StreamReference:
    """The reference's view of the directory as it stands: every shard
    there and, from the generator's record, the signature of each line
    of the corpus file whose bytes it holds (a link's target says
    which), with the byte offset past each line of those bytes."""
    sigs = corpus.signatures()
    bounds = np.concatenate([[0], np.cumsum(corpus.lines_per_file)])
    of_file = {}
    for f, path in enumerate(corpus.files):
        with open(path, "rb") as fh:
            ends = line_ends(fh.read())
        if len(ends) != corpus.lines_per_file[f]:
            raise RunFailed(f"{path} holds {len(ends)} lines where the "
                            f"generator wrote {corpus.lines_per_file[f]}")
        of_file[os.path.realpath(path)] = (sigs[bounds[f]:bounds[f + 1]],
                                           ends)
    shards = []
    for name in os.listdir(stream_dir):
        path = os.path.join(stream_dir, name)
        if name.endswith(".done"):
            continue
        held = of_file.get(os.path.realpath(path))
        if held is None:
            raise RunFailed(f"{path} is no link to a corpus file")
        shards.append(Shard(path, *held,
                            sealed=os.path.exists(path + ".done")))
    return StreamReference(shards, batch_size)


class StreamProbe(train_driver.StepProbe):
    """The step's probe, which also keeps the signature of every
    example of the first ``n_stream`` stepped batches (0 where the
    example's weight is 0: not trained on), taken from the feed as the
    step got it."""

    def __init__(self, n_check: int, n_stream: int):
        super().__init__(n_check)
        self.n_stream = n_stream
        self.fed = []

    def wrap(self, step):
        record = super().wrap(step)

        def probed(*args, **kwargs):
            if len(self.fed) < self.n_stream:
                feed = {k: np.asarray(v) for k, v in kwargs.items()
                        if v is not None}
                millis = np.rint(np.asarray(feed["vals"], np.float64)
                                 * 1000).astype(np.int64)
                sig = corpus_mod.example_signatures(
                    feed["labels"].astype(np.int64), check.feed_rows(feed),
                    millis)
                sig[np.asarray(feed["weights"]) == 0] = 0
                self.fed.append(sig)
            return record(*args, **kwargs)
        return probed


class WatermarkSeam:
    """Rebinds the program's ``StepLoop`` for the run with a subclass
    that keeps the watermark the loop adopts at step ``upto``: what a
    save at that step would have recorded."""

    def __init__(self, upto: int):
        self.upto = upto
        self.adopted = None     # the payload adopted at step ``upto``

    def __enter__(self):
        import importlib
        self._mod = importlib.import_module(PROGRAM)
        self._kept = getattr(self._mod, "StepLoop", None)
        if self._kept is None:
            raise RunFailed(f"{PROGRAM}.StepLoop is gone: the benchmark's "
                            "seam into the stream loop moved")
        seam = self

        class Loop(self._kept):
            @property
            def stream_watermark(self):
                return self._adopted

            @stream_watermark.setter
            def stream_watermark(self, payload):
                self._adopted = payload
                if payload is not None and self.global_step == seam.upto:
                    seam.adopted = payload

        self._mod.StepLoop = Loop
        return self

    def __exit__(self, *exc):
        self._mod.StepLoop = self._kept


class DryWatch(threading.Thread):
    """Ends a run whose backlog is eaten: a loop that has stepped
    nothing for ``DRY_AFTER_S`` while its gets came back empty gets the
    writer's ``STOP`` marker, so that ``train()`` returns through its
    own exit and the driver fails the run. Reads the program's own
    counters, touches nothing else."""

    def __init__(self, stream_dir: str):
        super().__init__(name="bench-dry-watch", daemon=True)
        self.stream_dir = stream_dir
        self.stop = threading.Event()
        self.dry = False

    def run(self):
        from fast_tffm_tpu.obs import telemetry
        last, since = None, time.monotonic()
        while not self.stop.wait(0.25):
            tel = telemetry.active()
            if tel is None:
                continue
            c = tel.registry.snapshot()["counters"]
            now = (c.get("train/steps", 0), c.get("stream/gets_idle", 0))
            if last is None or now[0] != last[0]:
                last, since = now, time.monotonic()
            elif (now[1] > last[1]
                  and time.monotonic() - since >= DRY_AFTER_S):
                self.dry = True
                with open(os.path.join(self.stream_dir, "STOP"), "w",
                          encoding="utf-8"):
                    pass
                return


def stream_checks(ref: StreamReference, probe: StreamProbe, adopted,
                  idle_in_span) -> list:
    n = probe.n_stream
    fed = probe.fed[:n]
    return [
        {"name": "stream_batches_not_in_ledger_order",
         "value": ref.not_in_ledger_order(fed) + max(n - len(fed), 0),
         "limit": 0},
        {"name": "stream_lines_trained_twice_or_never",
         "value": ref.twice_or_never(fed), "limit": 0},
        {"name": "watermark_lines_off",
         "value": ref.watermark_off(adopted, n), "limit": 0},
        {"name": "stream_idle_in_span",
         "value": (int(idle_in_span) if idle_in_span is not None
                   else 1), "limit": 0}]


def run(run, device, breaker=None, after_shards=None) -> str:
    """``after_shards(stream_dir)`` (tests): a fault planted in the
    directory behind the reference's back, once it has read the
    backlog as the benchmark wrote it."""
    tr = run.cell.traffic
    t = time.monotonic()
    from fast_tffm_tpu.data import stream as streamlib
    leaves = getattr(streamlib, "PUMP_LEAVES", None)
    if leaves is None:
        raise RunFailed(
            "this program's stream source has no spans or counters on its "
            "read plane (fast_tffm_tpu.data.stream.PUMP_LEAVES): the "
            "cell's stream_idle_in_span and its per-layer metrics have "
            "nothing to read")
    from fast_tffm_tpu.train import train
    run.setup["import_program_s"] = time.monotonic() - t
    harness.enable_cache()
    harness.fresh_dir(run.work_dir)
    corpus = train_driver.make_corpus(run, "train")
    t = time.monotonic()
    stream_dir = os.path.join(run.work_dir, "stream")
    write_shards(corpus, stream_dir, tr["backlog_passes"])
    run.setup["shards_s"] = time.monotonic() - t
    steps_per_reading = int(tr["steps_per_reading"])
    metrics_path = os.path.join(run.work_dir, "metrics.jsonl")
    cfg = harness.program_cfg(run.cell.config, {
        "General": {"model_file": os.path.join(run.work_dir, "model", "fm")},
        "Train": {"stream_dir": stream_dir,
                  "seed": run.program_seed,
                  "log_steps": steps_per_reading,
                  "metrics_file": metrics_path,
                  "metrics_flush_steps": steps_per_reading}},
        run.work_dir)
    if cfg.run_mode != "stream":
        raise RunFailed("a train_stream cell's configuration states "
                        "run_mode = stream")
    model = harness.model_of(cfg, run.cell.config)
    global_batch = cfg.batch_size
    t = time.monotonic()
    ref = reference_of(corpus, stream_dir, global_batch)
    run.setup["reference_s"] = time.monotonic() - t
    if after_shards is not None:
        after_shards(stream_dir)
    n_check, n_stream = int(tr["checked_steps"]), int(
        tr["checked_stream_batches"])
    warmup_steps = int(tr["warmup_readings"]) * steps_per_reading
    if warmup_steps < max(n_check, n_stream):
        raise RunFailed("the warm-up must hold every checked batch")
    probe = StreamProbe(n_check, n_stream)
    tracer = harness.TraceWindow(run) if run.trace else None
    handler = train_driver.SyncHandler(run, warmup_steps, tracer)
    logger = logging.getLogger("fast_tffm_tpu")
    logger.addHandler(handler)
    watch = DryWatch(stream_dir)
    watch.start()
    try:
        with train_driver.Seams(run, probe, breaker), \
                WatermarkSeam(n_stream) as seam:
            train(cfg)
        raise RunFailed(
            "train() returned before the window closed: "
            + ("the backlog ran dry (lengthen backlog_passes)" if watch.dry
               else "the stream ended"))
    except train_driver.WindowClosed:
        pass
    except Exception as e:
        if not watch.dry:
            raise
        # train()'s own exit after the STOP marker: at the cell's size
        # its final save does not fit the chip beside the state
        raise RunFailed(f"the backlog ran dry (lengthen backlog_passes), "
                        f"and train()'s exit failed: {e}") from e
    finally:
        watch.stop.set()
        logger.removeHandler(handler)
        if tracer is not None:
            tracer.stop()
    chips = max(int(device["count"]), 1)
    rd = readings.readings_between_syncs(
        [(t_, step * global_batch) for t_, step, _ in handler.syncs])
    # No epoch: a cycle is one reading, the span every whole reading.
    rate = harness.window_rate(run, rd, handler.t_start, 1, chips,
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
    counted = telemetry_window.window_delta(ctx, "train/examples")
    if counted is None:
        raise RunFailed("the telemetry stream has no snapshot at the "
                        f"span's sync points {s_first} and {s_last}")
    checks.append({"name": "span_examples_credited_not_counted",
                   "value": abs((s_last - s_first) * global_batch
                                - int(counted)), "limit": 0})
    checks += stream_checks(
        ref, probe, seam.adopted,
        telemetry_window.window_delta(ctx, "stream/gets_idle"))
    say_stream(ctx, ref, len(probe.fed), leaves)
    return harness.finish(
        run, device, {E2E_RATE: rate["rate"]}, checks,
        time.monotonic() - t, attempted=s_last - s_first, failed=failed,
        tracer=tracer, ctx=ctx)


def say_stream(ctx, ref: StreamReference, n_fed: int, leaves) -> None:
    """A line of what the span's batches cost the one producer thread,
    from the program's counters (any run, traced or not); ``leaves``:
    the spans under ``stream/pump``, as the program names them."""
    say(f"stream: ledger of {len(ref.ledger)} shards, {ref.lines} lines; "
        f"{n_fed} stepped batches held to the reference")
    batches = telemetry_window.window_delta(ctx, "pipeline/batches")
    pump = telemetry_window.window_delta(ctx, "stream/pump_seconds")
    if not batches or pump is None:
        return
    parts = {p: telemetry_window.window_delta(ctx, p + "_seconds")
             for p in leaves}
    named = sum(v for v in parts.values() if v)
    gets = telemetry_window.window_delta(ctx, "stream/gets")
    said = (f"the span's producer thread: {1e3 * pump / batches:.3f} ms a "
            f"batch inside stream/pump ("
            + ", ".join(f"{p.split('/')[1]} {1e3 * v / batches:.3f}"
                        for p, v in parts.items() if v is not None)
            + f"; under no leaf {1e3 * (pump - named) / batches:.3f}), "
            f"{telemetry_window.window_delta(ctx, 'stream/pumps'):.0f} pumps "
            f"and {(telemetry_window.window_delta(ctx, 'stream/bytes_read') or 0) / 1e6:.1f} MB read "
            f"for {batches:.0f} batches")
    if gets:
        said += (f"; the loop's {gets:.0f} gets, "
                 f"{telemetry_window.window_delta(ctx, 'stream/gets_idle'):.0f}"
                 " came back empty")
    say(said)
    # The loop's own thread, by the leaves that partition its wall.
    from fast_tffm_tpu.obs.telemetry import LOOP_LEAVES, LOOP_UNNAMED
    steps = telemetry_window.window_delta(ctx, "train/steps")
    wall = telemetry_window.window_delta(ctx, "train/loop_seconds")
    if not steps or not wall:
        return
    parts = {p: telemetry_window.window_delta(ctx, p)
             for p in LOOP_LEAVES + (LOOP_UNNAMED,)}
    say(f"the span's loop thread: {1e3 * wall / steps:.3f} ms a step ("
        + ", ".join(f"{p.split('/')[1][:-len('_seconds')]} "
                    f"{1e3 * v / steps:.3f}"
                    for p, v in sorted(parts.items(),
                                       key=lambda kv: -(kv[1] or 0))
                    if v) + ")")
