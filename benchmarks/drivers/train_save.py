"""Driver of ``kind: train_save`` traffic: ``drivers/train.py``'s run
with the program's own periodic save (``save_steps``) falling inside
the window: the snapshot on the loop's thread, the write in the
background while the loop trains (SAVE.md). The rate is all the
trained examples over all the time of the span, the pause and every
reading slowed by the write included.

Nothing of ``train.run`` is copied. For the call, names it looks up are
rebound, as ``train_eval.py`` does: ``harness.program_cfg`` (the
configuration it makes is kept), the step's probe (``SaveStepProbe``: after a
save, fetches the touched rows of the state the NEXT step returned),
``harness.window_rate`` (its readings are kept), ``harness.TraceWindow``
(says when a traced run wrote its trace out) and ``harness.finish``
(gains the save's checks and what the readers need). One name of
``fast_tffm_tpu.train`` is rebound beside the step's seams:
``StepLoop.save`` (every save's step, scalars, call and return, the
device's peak bytes on either side, and, as it returns, the rows the
corpus touches fetched from the state the loop holds: the state of
that step, since no step runs inside a save). Both fetches wait on the
loop's thread, inside the window and outside the pause (two of 0.1 s a
run), in blocks small enough to leave the device's peak alone.

What decides ``correct`` beside the three training checks and the feed's
three: six counts that must be 0, on the first save past the warm-up,
read back from its committed step directory by ``save_reference.read_step``
once the window has closed: ``saved_rows_not_of_step``,
``saved_untouched_rows_off``, ``saved_scalars_off``,
``manifest_mismatches`` (the program's own ``verify_step_dir`` at
``full``, and the manifest must be there), ``saves_in_span_not_one``
(``saves_in_window`` of the traffic file, by the hook's count and by
the program's counter) and ``save_off_schedule`` (the traffic file's
``save_steps`` states the schedule; the program follows its
configuration's). One line says what
the control reads, which has to fail: the same comparison against the
rows one step later.

``<work_dir>/model`` (a save is 9 GB at the cell's size) is removed
before ``run`` returns or raises."""

from __future__ import annotations

import importlib
import os
import shutil
import statistics
import threading
import time

import numpy as np

from benchmarks import harness, save_reference
from benchmarks.drivers import train as train_driver
from benchmarks.harness import RunFailed, say
from benchmarks.readers import telemetry_window

PROGRAM = "fast_tffm_tpu.train"
SAVE_SEAM = ("StepLoop", "save")
COMMIT_POLL_S = 0.02
SAVE_CHECKS = ("saved_rows_not_of_step", "saved_untouched_rows_off",
               "saved_scalars_off", "manifest_mismatches",
               "saves_in_span_not_one", "save_off_schedule")
# A save's phases on the loop's thread, as their counters name them
# (SAVE.md).
SAVE_COUNTERS = ("train/checkpoint_pause_seconds", "checkpoint/save_seconds",
                 "checkpoint/settle_seconds", "checkpoint/snapshot_seconds",
                 "checkpoint/snapshot_bytes")


FETCH_ROWS = 1 << 16    # rows a gather: two alive are 22 MB on the
# device at k = 16, under the train step's own 27.6 MB of temporaries,
# so the probe leaves the device's peak where training put it


def fetch_rows(gather, arrays, ids):
    """Rows ``ids`` of each device array, on the host: gathered
    ``FETCH_ROWS`` at a time, the next gather under way while this one
    lands. The caller's thread waits: nothing is left on the device."""
    out = []
    for arr in arrays:
        rows = np.empty((len(ids), int(arr.shape[1])), np.float32)
        landing = None
        for a in list(range(0, len(ids), FETCH_ROWS)) + [None]:
            ahead = None
            if a is not None:
                chunk = ids[a:a + FETCH_ROWS]
                padded = np.full(FETCH_ROWS, chunk[-1], dtype=np.int32)
                padded[:len(chunk)] = chunk
                ahead = (a, len(chunk), gather(arr, padded))
                ahead[2].copy_to_host_async()
            if landing is not None:
                at, n, got = landing
                rows[at:at + n] = np.asarray(got).reshape(FETCH_ROWS, -1)[:n]
            landing = ahead
        out.append(rows)
    return out


class SaveProbe:
    """Every save of the run, and the rows held against the first."""

    def __init__(self, ids, from_step: int):
        self.ids, self.from_step = ids, from_step
        self.held = None            # the save held to the reference
        self.saves = []             # one record a call of StepLoop.save
        self.owed = None            # the save whose control is not fetched
        self.directory = None       # the checkpoint manager's own
        self._gather = None

    def fetch(self, table, acc):
        """The touched rows of a state, once the step that made it has
        run (its temporaries are gone before a gather's are made)."""
        import jax
        if self._gather is None:
            # flat: row after row is what the copy to the host moves
            # at the link's pace, whatever the table's tiling
            self._gather = jax.jit(lambda t, i: t[i].reshape(-1))
        jax.block_until_ready((table, acc))
        return fetch_rows(self._gather, (table, acc), self.ids)

    def wrap_save(self, save):
        from fast_tffm_tpu.obs.memory import device_memory_stats

        def peak():
            return int((device_memory_stats() or {}).get(
                "peak_bytes_in_use", 0))

        def probed(loop, epoch, wait, **kw):
            rec = {"step": int(loop.global_step), "epoch": int(epoch),
                   "vocab": int(loop.s.cfg.vocabulary_size),
                   "wait": bool(wait), "peak_before": peak(),
                   "t_call": time.monotonic()}
            self.saves.append(rec)
            save(loop, epoch, wait, **kw)
            rec.update(t_return=time.monotonic(), peak_after=peak())
            self.directory = loop.s.ckpt.directory
            if self.held is None and rec["step"] >= self.from_step:
                self.held = self.owed = rec
                self.watch(rec)
                rec["of_step"] = self.fetch(loop.table, loop.acc)
        return probed

    def watch(self, rec) -> None:
        """The driver's clock on the commit: the step's directory
        under its final name."""
        path = os.path.join(self.directory, str(rec["step"]))
        rec["stop_watch"] = threading.Event()

        def poll():
            while not rec["stop_watch"].is_set():
                if os.path.isdir(path):
                    rec["t_commit"] = time.monotonic()
                    return
                time.sleep(COMMIT_POLL_S)
        threading.Thread(target=poll, daemon=True,
                         name="bench-save-watch").start()


class SaveStepProbe(train_driver.StepProbe):
    """The step's probe, which after the first save also fetches the
    touched rows of what the next step returned (the control)."""

    def __init__(self, n_check: int, saves: SaveProbe):
        super().__init__(n_check)
        self.saves, self.warmed = saves, False

    def wrap(self, step):
        record = super().wrap(step)

        def probed(*args, **kwargs):
            out = record(*args, **kwargs)
            if not self.warmed:
                # the gather's program is made ready on the job's first
                # step, in the warm-up: nothing compiles in the window
                self.warmed = True
                self.saves.fetch(out[0], out[1])
            rec, self.saves.owed = self.saves.owed, None
            if rec is not None:
                rec["one_step_later"] = self.saves.fetch(out[0], out[1])
            return out
        return probed


def save_checks(limits, tr, cfg, seed: int, probe: SaveProbe, saved,
                manifest_reason, in_span: int, counted):
    """The save's six numbers beside their limits, and the control's
    reading. ``saved``: the held save's step as read back (None:
    unreadable, or the run made no save past its warm-up);
    ``manifest_reason``: the verdict on its manifest (None: it
    agrees); ``in_span``/``counted``: the saves of the span by the hook
    and by the program's counter (None where it has none)."""
    ids, first = probe.ids, probe.held
    expected, save_steps = int(tr["saves_in_window"]), int(tr["save_steps"])
    if first is None or "of_step" not in first:
        control = None
        not_of_step = len(ids)
    else:
        not_of_step = save_reference.rows_not_of_step(saved, ids,
                                                      *first["of_step"])
        later = first.get("one_step_later")
        control = (save_reference.rows_not_of_step(saved, ids, *later)
                   if later is not None and saved is not None else None)
    counts = [abs(int(x) - expected) for x in (in_span, counted)
              if x is not None]
    values = {
        "saved_rows_not_of_step": not_of_step,
        "saved_untouched_rows_off": save_reference.untouched_rows_off(
            saved, ids, cfg.num_rows, cfg.row_dim, seed,
            cfg.init_value_range, cfg.adagrad_init,
            int(tr["untouched_rows_sampled"])),
        "saved_scalars_off": (save_reference.scalars_off(saved, first)
                              if first is not None
                              else len(save_reference.SCALARS)),
        "manifest_mismatches": int(manifest_reason is not None),
        "saves_in_span_not_one": max(counts),
        "save_off_schedule": (int(first["step"]) % save_steps
                              if first is not None else save_steps)}
    checks = [{"name": name, "value": int(values[name]),
               "limit": limits[name]} for name in SAVE_CHECKS]
    return checks, control


def under_write_share(rd, first, t_start: float, t_end: float,
                      skip=(0.0, 0.0)):
    """The median reading begun between the save's return and its
    commit over the median reading ended before the save's call, in
    percent, both inside the window; where the commit came before the
    next reading began, that next reading stands for the write. A
    reading that overlaps ``skip`` (a traced run's profiler writing its
    trace out: seconds on the loop's thread) is left out."""
    if first is None or "t_return" not in first:
        return None
    rates = [(a, b, w / (b - a)) for a, b, w in rd
             if a >= t_start and b <= t_end
             and not (a < skip[1] and b > skip[0])]
    before = [r for a, b, r in rates if b <= first["t_call"]]
    after = [(a, r) for a, b, r in rates if a >= first["t_return"]]
    if not before or not after:
        return None
    commit = first.get("t_commit", t_end)
    under = [r for a, r in after if a < commit] or [after[0][1]]
    return 100.0 * statistics.median(under) / statistics.median(before)


def say_saves(ctx, probe, control):
    first = probe.held
    if first is None:
        say("saves: none past the warm-up")
        return
    say("every save of the run, seconds inside StepLoop.save by step: "
        + ", ".join(f"{r['step']}: {r['t_return'] - r['t_call']:.3f}"
                    for r in probe.saves if "t_return" in r))
    say(f"saves: the first past the warm-up at step {first['step']} (epoch "
        f"{first['epoch']}, wait {first['wait']}), "
        f"{first['t_return'] - first['t_call']:.3f} s inside "
        f"StepLoop.save; committed "
        + (f"{first['t_commit'] - first['t_return']:.3f} s after it "
           "returned" if "t_commit" in first else "after the window")
        + f"; the device's peak {first['peak_before']} B before, "
        f"{first['peak_after']} B after")
    if control is not None:
        say("control: the state one step later reads "
            f"saved_rows_not_of_step {control!r} (limit 0): "
            + ("it fails, as it must" if control > 0 else "IT PASSES"))
    saves = telemetry_window.window_delta(ctx, "checkpoint/saves")
    if not saves:
        return
    parts = {c: telemetry_window.window_delta(ctx, c) for c in SAVE_COUNTERS}
    say(f"the span's saves: {saves:.0f}; a save: "
        + ", ".join(f"{c.split('/')[1]} {v / saves:.4f}"
                    for c, v in parts.items() if v is not None))
    # The loop's own thread over the span, by the leaves that partition
    # its wall: where the pause and the readings under the write went.
    from fast_tffm_tpu.obs.telemetry import LOOP_LEAVES, LOOP_UNNAMED
    wall = telemetry_window.window_delta(ctx, "train/loop_seconds")
    if not wall:
        return
    parts = {p: telemetry_window.window_delta(ctx, p)
             for p in LOOP_LEAVES + (LOOP_UNNAMED,)}
    say(f"the span's loop thread: {wall:.3f} s ("
        + ", ".join(f"{p.split('/')[1][:-len('_seconds')]} {v:.3f}"
                    for p, v in sorted(parts.items(),
                                       key=lambda kv: -(kv[1] or 0))
                    if v) + ")")


def run(run, device, breaker=None, save_breaker=None,
        after_commit=None) -> str:
    """``save_breaker`` (tests): wraps ``StepLoop.save`` below the
    probe. ``after_commit(directory, step)`` (tests): a fault planted
    in the committed step behind the program's back, before it is read."""
    model_dir = os.path.join(run.work_dir, "model")
    try:
        return _run(run, device, breaker, save_breaker, after_commit)
    except RunFailed:
        raise
    except Exception as e:
        # e.g. a save that does not fit the chip: the failed save's
        # threads may outlive it, so leave as run.py leaves (os._exit)
        raise RunFailed(f"the run raised {type(e).__name__}: "
                        f"{str(e)[:2000]}") from e
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)


def _run(run, device, breaker, save_breaker, after_commit) -> str:
    tr = run.cell.traffic
    limits = run.cell.config["check_limits"]["train_save"]
    program = importlib.import_module(PROGRAM)
    loop_class = getattr(program, SAVE_SEAM[0], None)
    kept_save = getattr(loop_class, SAVE_SEAM[1], None)
    if kept_save is None:
        raise RunFailed(f"{PROGRAM}.{'.'.join(SAVE_SEAM)} is gone: the "
                        "benchmark's seam into the save moved")
    held = {}
    kept_cfg, kept_finish, kept_rate, kept_trace, kept_corpus, kept_probe = (
        harness.program_cfg, harness.finish, harness.window_rate,
        harness.TraceWindow, train_driver.make_corpus,
        train_driver.StepProbe)

    def program_cfg(config, extra, work_dir):
        cfg = held["cfg"] = kept_cfg(config, extra, work_dir)
        if not cfg.save_steps:
            raise RunFailed("a train_save cell's configuration states "
                            "save_steps")
        return cfg

    def make_probe(n):
        corpus = held["corpus"]
        held["probe"] = SaveProbe(
            np.unique(corpus.rows).astype(np.int64),
            int(tr["warmup_readings"]) * int(tr["steps_per_reading"]))
        loop_class.save = held["probe"].wrap_save(
            kept_save if save_breaker is None else save_breaker(kept_save))
        return SaveStepProbe(n, held["probe"])

    def make_corpus(run_, prefix):
        held["corpus"] = kept_corpus(run_, prefix)
        return held["corpus"]

    def window_rate(run_, rd, t_start, *a, **k):
        held["readings"], held["t_start"] = rd, t_start
        return kept_rate(run_, rd, t_start, *a, **k)

    class TimedTrace(kept_trace):
        """The profiler's window, which also says when it wrote its
        trace out (``stop`` of a live window)."""

        def stop(self):
            live, t = self.active, time.monotonic()
            super().stop()
            if live:
                held["trace_written"] = (t, time.monotonic())

    def finish(run_, device_, end_to_end, checks, check_seconds, **kw):
        t = time.monotonic()
        more = after_window(run_, held, kw["ctx"], limits, after_commit)
        return kept_finish(run_, device_, end_to_end, checks + more,
                           check_seconds + time.monotonic() - t, **kw)

    harness.program_cfg, harness.finish = program_cfg, finish
    harness.window_rate, harness.TraceWindow = window_rate, TimedTrace
    train_driver.make_corpus, train_driver.StepProbe = make_corpus, make_probe
    try:
        return train_driver.run(run, device, breaker)
    finally:
        harness.program_cfg, harness.finish = kept_cfg, kept_finish
        harness.window_rate, harness.TraceWindow = kept_rate, kept_trace
        train_driver.make_corpus = kept_corpus
        train_driver.StepProbe = kept_probe
        loop_class.save = kept_save
        for rec in getattr(held.get("probe"), "saves", ()):
            if "stop_watch" in rec:
                rec["stop_watch"].set()


def after_window(run, held, ctx, limits, after_commit):
    """The window has closed and ``train()`` has unwound (its teardown
    waited for the write and wrote the owed manifest): read the first
    save back and hold it to the reference."""
    from fast_tffm_tpu.checkpoint import read_manifest, verify_step_dir
    cfg, probe, tr = held["cfg"], held["probe"], run.cell.traffic
    first = probe.held
    s_first, s_last = ctx["window_steps"]
    # a save of step S follows sync point S: its pause is in the reading
    # that begins there, and the program counts it after that snapshot
    in_span = sum(1 for r in probe.saves if s_first <= r["step"] < s_last)
    saved = reason = None
    if first is not None:
        if after_commit is not None:
            after_commit(probe.directory, first["step"])
        try:
            if read_manifest(probe.directory, first["step"]) is None:
                reason = "the committed step has no manifest"
            else:
                reason = verify_step_dir(probe.directory, first["step"],
                                         "full")
        except (ValueError, OSError) as e:
            reason = f"unreadable manifest: {e}"
        try:
            saved = save_reference.read_step(probe.directory, first["step"])
        except Exception as e:  # noqa: BLE001 - whatever orbax raises on
            say(f"the committed step could not be read: "   # a torn step
                f"{type(e).__name__}: {str(e)[:300]}")
    if reason is not None:
        say(f"manifest: {reason}")
    checks, control = save_checks(
        limits, tr, cfg, run.seed, probe, saved, reason, in_span,
        telemetry_window.window_delta(ctx, "checkpoint/saves"))
    say_saves(ctx, probe, control)
    if first is not None and "t_return" in first:
        if "t_commit" in first:
            ctx["save_commit_s"] = first["t_commit"] - first["t_return"]
        ctx["save_device_bytes_extra"] = (first["peak_after"]
                                          - first["peak_before"])
        share = ctx["rate_under_write_share"] = under_write_share(
            held["readings"], first, held["t_start"],
            held["t_start"] + run.seconds, held.get("trace_written", (0, 0)))
        if share is not None:
            say(f"readings begun under the write: their median is "
                f"{share:.2f}% of the median before the save")
    return checks
