"""Placement a batch ahead, off the loop's thread (ISSUE 46).

Where a batch is final when emitted (one process, no admission, no
offload) the epoch loop's feed ends in ``pipeline.place_ahead``: a
thread of its own, ``fm-place``, encodes and places each batch
(``StepLoop.feed_place``) up to ``prefetch_depth`` ahead, and
``StepLoop.step`` dispatches what it is handed. Everywhere else the
loop places for itself through ``StepLoop.wire_place``, as before.
One device and the four-device CPU mesh, both: the same batches in the
same order, the same losses bit for bit, and nothing left behind when
the loop stops early."""

import gc
import logging
import threading
import time

import jax
import numpy as np
import pytest

from fast_tffm_tpu import train as train_mod
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data import pipeline
from fast_tffm_tpu.obs.sink import read_events
from fast_tffm_tpu.parallel import sharded
from fast_tffm_tpu.wire import WireBatch

from tests.test_e2e import make_dataset

FEED_THREADS = ("fm-place", "prefetch", "fm-build", "fm-scan")
STEPS = 24          # two epochs of 12 batches of 32 lines


def _devices(monkeypatch, n):
    """A session over ``n`` of the suite's eight CPU devices: 1 is the
    plain jitted step, 4 the (4, 1) mesh ``tests/test_sharded.py``
    builds."""
    monkeypatch.setattr(jax, "device_count", lambda: n)
    real = sharded.make_mesh
    monkeypatch.setattr(sharded, "make_mesh",
                        lambda: real(jax.devices()[:n]))


def _cfg(d, **kw):
    if not (d / "train.txt").exists():
        make_dataset(d / "train.txt", 32 * STEPS // 2,
                     np.random.default_rng(46))
    base = dict(vocabulary_size=200, factor_num=4, batch_size=32,
                learning_rate=0.1, epoch_num=2, seed=5,
                train_files=(str(d / "train.txt"),),
                model_file=str(d / "m" / "fm"), metrics_file="auto",
                metrics_flush_steps=4, log_steps=1, trace_spans=True)
    base.update(kw)
    return FmConfig(**base)


def _loop_places(monkeypatch):
    """The same feed with its last stage handing batches over unplaced:
    what a session that must place for itself gets."""
    real = pipeline.place_ahead
    monkeypatch.setattr(pipeline, "place_ahead",
                        lambda it, place, depth, *a: real(it, None, depth,
                                                          *a))


def _run(cfg, monkeypatch):
    """train(cfg) -> (table, [(step, loss)], events): every loss line's
    float as the loop synced it."""
    lines = []
    real = train_mod.StepLoop.log_line

    def log_line(self, step, epoch, val, eps):
        lines.append((step, val))
        real(self, step, epoch, val, eps)

    with monkeypatch.context() as m:
        m.setattr(train_mod.StepLoop, "log_line", log_line)
        table = np.asarray(train_mod.train(cfg))
    return table, lines, list(read_events(cfg.model_file + ".metrics.jsonl"))


def _spans(events, name):
    return [e for e in events if e["event"] == "span" and e["name"] == name]


def _counters(events):
    return [e for e in events if e["event"] == "metrics"][-1]["counters"]


def _feed_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith(FEED_THREADS)]


def _settled():
    """No thread of a feed alive and no encoded batch referenced, once
    the collector has run (a traceback's frames hold the step's)."""
    deadline = time.monotonic() + 5.0
    while _feed_threads() and time.monotonic() < deadline:
        time.sleep(0.02)
    gc.collect()
    return _feed_threads(), [o for o in gc.get_objects()
                             if isinstance(o, WireBatch)]


# ---- the same steps, placed by the feed or by the loop -------------------

@pytest.mark.parametrize("devices", [1, 4])
def test_losses_are_bit_equal_with_the_feed_placing_and_the_loop(
        tmp_path, monkeypatch, devices):
    """24 steps over two shuffled epochs: the loss of every step and the
    table at the end are the same bits whoever placed the batches, so
    they were the same batches in the same order. Where the feed
    places, every step's batch arrives placed, ``train/h2d`` opens on
    no thread and ``feed/place`` on its own; where the loop does,
    today's spans, and nothing counted as placed ahead."""
    _devices(monkeypatch, devices)
    ahead = _run(_cfg(tmp_path), monkeypatch)
    _loop_places(monkeypatch)
    cfg = _cfg(tmp_path, model_file=str(tmp_path / "loop" / "fm"))
    own = _run(cfg, monkeypatch)
    assert [s for s, _ in ahead[1]] == list(range(1, STEPS + 1))
    assert ahead[1] == own[1]
    assert all(np.isfinite(v) for _, v in ahead[1])
    assert len({v for _, v in ahead[1]}) > STEPS // 2   # no constant
    np.testing.assert_array_equal(ahead[0], own[0])

    c = _counters(ahead[2])
    assert c["train/placed_ahead"] == c["train/steps"] == STEPS
    assert c["train/h2d_seconds"] == 0 and c["train/place_seconds"] > 0
    assert not _spans(ahead[2], "train/h2d")
    assert not _spans(ahead[2], "train/encode")
    (loop_tid,) = {s["tid"] for s in _spans(ahead[2], "train/step")}
    place = _spans(ahead[2], "feed/place")
    assert len(place) == STEPS
    assert {s["tid"] for s in place} == {"fm-place"} != {loop_tid}
    # the bytes the wire counts are the feed's batches'
    assert c["train/h2d_bytes"] == _counters(own[2])["train/h2d_bytes"] > 0

    c = _counters(own[2])
    assert c["train/placed_ahead"] == 0 and c["train/steps"] == STEPS
    assert c["train/place_seconds"] == 0 and c["train/h2d_seconds"] > 0
    (loop_tid,) = {s["tid"] for s in _spans(own[2], "train/step")}
    assert {s["tid"] for s in _spans(own[2], "train/h2d")} == {loop_tid}
    assert len(_spans(own[2], "train/h2d")) == STEPS
    assert not _spans(own[2], "feed/place")
    assert _settled() == ([], [])


# ---- where the loop keeps placing for itself -------------------------------

class _Decided(BaseException):
    pass


@pytest.mark.parametrize("case", ["admit", "offload", "two processes"])
def test_the_loop_places_where_the_session_says_it_must(
        tmp_path, monkeypatch, case):
    """``vocab_mode = admit`` (a publish barrier may re-point a queued
    batch), ``lookup = host`` (the offload step takes host arrays) and
    more than one process (exhaustion and preemption are agreed before
    anything is placed): nothing is placed ahead, and the loop's own
    spans are there. Decided by what the session is, by no knob."""
    _devices(monkeypatch, 1)
    if case == "two processes":
        # The session's answer alone: a second process is not started,
        # the flag is what ``_run_epochs`` reads.
        handed = []
        real_run = train_mod._run_epochs

        def as_two(s, loop):
            s.multi_process = True
            try:
                return real_run(s, loop)
            finally:
                s.multi_process = False     # the teardown's is one's

        def spy(it, place, depth, *a):
            handed.append(place)
            it.close()
            raise _Decided()

        monkeypatch.setattr(train_mod, "_run_epochs", as_two)
        monkeypatch.setattr(pipeline, "place_ahead", spy)
        with pytest.raises(_Decided):
            train_mod.train(_cfg(tmp_path))
        assert handed == [None]
        return
    kw = (dict(vocab_mode="admit", hash_feature_id=True)
          if case == "admit" else dict(lookup="host"))
    _, lines, events = _run(_cfg(tmp_path, **kw), monkeypatch)
    assert len(lines) == STEPS
    c = _counters(events)
    assert c["train/placed_ahead"] == 0 and c["train/steps"] == STEPS
    assert c["train/place_seconds"] == 0
    assert not _spans(events, "feed/place")
    (loop_tid,) = {s["tid"] for s in _spans(events, "train/step")}
    # offload's wire_place ends after the encode: its step takes host arrays
    mine = "train/encode" if case == "offload" else "train/h2d"
    assert len(_spans(events, mine)) == STEPS
    assert {s["tid"] for s in _spans(events, mine)} == {loop_tid}
    assert c[mine + "_seconds"] > 0


# ---- a loop that stops early leaves nothing behind ---------------------------

@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("how", ["a step raises", "the log handler raises",
                                 "preempted"])
def test_a_loop_that_stops_mid_epoch_leaves_no_thread_and_no_batch(
        tmp_path, monkeypatch, how, devices):
    """With batches placed ahead and queued: a dispatch that raises, a
    BaseException out of a loss line's handler (how the benchmark ends
    a run) and a preemption each stop the placement thread and the
    stages behind it, and let go of what was placed. The examples
    trained are the first steps' and nothing else: a preempted run
    counts exactly its steps' examples, each at weight 1."""
    _devices(monkeypatch, devices)
    cfg = _cfg(tmp_path, shuffle_threads=4)     # four batches ahead
    stop_at = 5

    class Stop(BaseException):
        pass

    if how == "a step raises":
        real = train_mod.StepLoop.dispatch

        def dispatch(self, wb, args, step):
            if step == stop_at:
                raise RuntimeError("the step broke")
            return real(self, wb, args, step)

        monkeypatch.setattr(train_mod.StepLoop, "dispatch", dispatch)
        with pytest.raises(RuntimeError, match="the step broke"):
            train_mod.train(cfg)
    elif how == "the log handler raises":
        class Closing(logging.Handler):
            def emit(self, record):
                if record.getMessage().startswith(f"step {stop_at} "):
                    raise Stop()

        handler = Closing(level=logging.INFO)
        logger = logging.getLogger("fast_tffm_tpu")
        logger.addHandler(handler)
        try:
            with pytest.raises(Stop):
                train_mod.train(cfg)
        finally:
            logger.removeHandler(handler)
    else:
        real = train_mod._agreed_batch

        def agreed(s, loop, batch, epoch):
            if loop.global_step == stop_at:
                s.preempted.append(15)
            return real(s, loop, batch, epoch)

        monkeypatch.setattr(train_mod, "_agreed_batch", agreed)
        train_mod.train(cfg)
        c = _counters(list(read_events(cfg.model_file + ".metrics.jsonl")))
        assert c["train/steps"] == c["train/placed_ahead"] == stop_at
        assert c["train/examples"] == stop_at * cfg.batch_size
    assert _settled() == ([], [])


# ---- StepLoop.place's refusal reaches the caller -------------------------------

def test_a_batch_cut_for_another_mesh_is_refused_in_the_caller(
        tmp_path, monkeypatch):
    """The refusal is raised on the placement thread and re-raised where
    the loop asks for its next batch, with the same words."""
    _devices(monkeypatch, 4)
    real = train_mod.EpochFeed

    def uncut(*a, **kw):
        kw["row_shards"] = None     # one segment, for a mesh of four
        return real(*a, **kw)

    monkeypatch.setattr(train_mod, "EpochFeed", uncut)
    with pytest.raises(ValueError, match="a batch of 1 segment"
                       r"\(s\) of unique rows fed to a mesh of 4"):
        train_mod.train(_cfg(tmp_path))
    assert _settled() == ([], [])
