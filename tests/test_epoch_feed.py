"""One feed for the job (ISSUE 50).

An epochs-mode ``train`` opens its feed once (``pipeline.EpochFeed``):
an epoch's end comes in band, as an ``EpochMark`` behind its last
batch, and the next epoch's first batches are cut, built and placed
while this one's last steps run. What is pinned here: the batch stream
is, epoch by epoch, ``batch_iterator(..., epochs=1, seed=cfg.seed +
e)``'s; the barrier still runs between an epoch's last step and the
next one's first; where a barrier can change the next epoch's batches
the feed waits at the mark; a loop that stops leaves no thread behind;
and nothing is made per epoch ahead of time."""

import gc
import os
import threading
import time

import jax
import numpy as np
import pytest

from fast_tffm_tpu import train as train_mod
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data import pipeline
from fast_tffm_tpu.data.pipeline import (EpochFeed, EpochMark, SpillStats,
                                         batch_iterator)
from fast_tffm_tpu.obs.sink import read_events

from tests.test_e2e import make_dataset

FEED_THREADS = ("fm-place", "prefetch", "fm-build", "fm-scan")
ARRAYS = ("labels", "weights", "uniq_ids", "local_idx", "vals", "fields")
B, PER_EPOCH = 32, 12       # three files of 4 batches, the last one short


def _files(d):
    out = []
    for i, n in enumerate((B * 4, B * 4, B * 4 - 7)):
        path = d / f"part{i}.txt"
        if not path.exists():
            make_dataset(path, n, np.random.default_rng(50 + i))
        out.append(str(path))
    return tuple(out)


def _cfg(d, **kw):
    base = dict(vocabulary_size=200, factor_num=4, batch_size=B,
                learning_rate=0.1, epoch_num=3, seed=11, queue_size=4 * B,
                train_files=_files(d), model_file=str(d / "m" / "fm"),
                metrics_file="auto", metrics_flush_steps=4, log_steps=0)
    base.update(kw)
    return FmConfig(**base)


def _feed(cfg, epochs, hold=False, bucket=lambda: 0, **kw):
    return EpochFeed(cfg, cfg.train_files, epochs, place=None, hold=hold,
                     uniq_bucket=bucket, **kw)


def _same(got, want):
    for name in ARRAYS:
        a, b = getattr(got, name), getattr(want, name)
        if a is None or b is None:
            assert a is None and b is None, name
        else:
            assert a.dtype == b.dtype and a.shape == b.shape, name
            np.testing.assert_array_equal(a, b, err_msg=name)
    assert (got.num_real, got.row_shards, got.nnz) == (
        want.num_real, want.row_shards, want.nnz)


def _feed_threads():
    return sorted(t.name for t in threading.enumerate()
                  if t.name.startswith(FEED_THREADS))


def _settled():
    """The feed's threads still alive once ``_read_ahead``'s join bound
    has passed (a traceback's frames hold a generator until collected)."""
    deadline = time.monotonic() + 5.0
    while _feed_threads() and time.monotonic() < deadline:
        gc.collect()
        time.sleep(0.02)
    return _feed_threads()


def _counters(cfg):
    events = list(read_events(cfg.model_file + ".metrics.jsonl"))
    return [e for e in events if e["event"] == "metrics"][-1]["counters"]


def _one_device(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 1)


# ---- (a) the stream is the per-epoch iterators', mark by mark ---------------

@pytest.mark.parametrize("route", ["fast", "tolerant"])
@pytest.mark.parametrize("host_threads", [1, 4])
@pytest.mark.parametrize("shuffle", [False, True])
def test_three_epochs_are_the_three_iterators_end_to_end(
        tmp_path, shuffle, host_threads, route):
    """Every batch of every epoch, array for array and in order, and
    each mark's stats that epoch's: the C++ builder's planes (serial and
    ring) and the generic path a tolerant bad-line policy takes."""
    kw = {"bad_line_policy": "skip"} if route == "tolerant" else {}
    cfg = _cfg(tmp_path, shuffle=shuffle, host_threads=host_threads, **kw)
    feed = _feed(cfg, range(0, 3))
    try:
        got = list(feed)
    finally:
        feed.close()
    i = 0
    for epoch in range(3):
        stats = SpillStats()
        want = list(batch_iterator(cfg, cfg.train_files, epochs=1,
                                   seed=cfg.seed + epoch, stats=stats))
        assert len(want) == PER_EPOCH
        for batch in want:
            mine, placed = got[i]
            assert placed is None
            _same(mine, batch)
            i += 1
        mark = got[i]
        i += 1
        assert isinstance(mark, EpochMark) and mark.epoch == epoch
        assert mark.stats == stats and stats.batches == PER_EPOCH
    assert i == len(got)
    assert _settled() == []


def test_a_feed_opened_mid_schedule_starts_at_its_epoch(tmp_path):
    """A resumed job's feed: epochs 2 and 3 alone, with their seeds."""
    cfg = _cfg(tmp_path, host_threads=4)
    feed = _feed(cfg, range(2, 4))
    try:
        got = list(feed)
    finally:
        feed.close()
    assert [m.epoch for m in got if isinstance(m, EpochMark)] == [2, 3]
    want = [b for e in (2, 3) for b in batch_iterator(
        cfg, cfg.train_files, epochs=1, seed=cfg.seed + e)]
    mine = [item[0] for item in got if not isinstance(item, EpochMark)]
    assert len(mine) == len(want)
    for a, b in zip(mine, want):
        _same(a, b)


# ---- (b) the barrier between an epoch's last step and the next one's first --

def test_the_barrier_runs_between_the_epochs_with_the_next_one_fed(
        tmp_path, monkeypatch):
    """A recording barrier sees ``global_step`` at its epoch's last step
    and every dispatch so far that epoch's or an earlier one's, while
    the feed already has the next epoch's first batch out of the
    builders: counted, once a boundary that has a next epoch."""
    _one_device(monkeypatch)
    cfg = _cfg(tmp_path, host_threads=4, trace_spans=True)
    dispatched, seen = [], []
    real_dispatch = train_mod.StepLoop.dispatch
    real_barrier = train_mod._epoch_barrier

    def dispatch(self, wb, args, step):
        dispatched.append(step)
        time.sleep(0.004)       # the device sets the pace, not the host
        return real_dispatch(self, wb, args, step)

    def barrier(s, loop, epoch, stats):
        seen.append((epoch, loop.global_step, len(dispatched),
                     stats.batches))
        threads.append({(t.name, t.ident) for t in threading.enumerate()
                        if t.name.startswith(FEED_THREADS)})
        real_barrier(s, loop, epoch, stats)
        seen.append((epoch, loop.global_step, len(dispatched), None))

    threads = []
    monkeypatch.setattr(train_mod.StepLoop, "dispatch", dispatch)
    monkeypatch.setattr(train_mod, "_epoch_barrier", barrier)
    train_mod.train(cfg)
    steps = [PER_EPOCH * (e + 1) for e in range(3)]
    assert seen == [row for e, n in enumerate(steps) for row in (
        (e, n, n, PER_EPOCH), (e, n, n, None))]
    assert dispatched == list(range(1, 3 * PER_EPOCH + 1))
    c = _counters(cfg)
    assert c["train/epochs"] == 3 and c["train/steps"] == 3 * PER_EPOCH
    assert c["pipeline/epochs_fed_ahead"] == 2      # the last has no next
    assert c["train/placed_ahead"] == 3 * PER_EPOCH
    events = list(read_events(cfg.model_file + ".metrics.jsonl"))
    spans = [e for e in events if e["event"] == "span"]
    for name in ("pipeline/open", "pipeline/first_batch"):
        assert len([s for s in spans if s["name"] == name]) == 3, name
    # one plane: the threads at the second barrier are the first one's
    # (the scanner, an epoch ahead, may have cut the job's last group)
    assert threads[0] >= threads[1] >= {
        t for t in threads[0] if t[0] != "fm-scan"}
    assert {n for n, _ in threads[0]} == {
        "prefetch", "fm-place", "fm-scan", "fm-build-0", "fm-build-1",
        "fm-build-2", "fm-build-3"}
    # the counter is in every snapshot, from the first: a reader that
    # differences two of them always finds it
    assert all("pipeline/epochs_fed_ahead" in e["counters"]
               for e in events if e["event"] == "metrics")
    assert _settled() == []


# ---- (c) the hold ---------------------------------------------------------

def _opened(monkeypatch):
    """Every epoch's file order as it is drawn: ``(seed, thread)``."""
    opened = []
    real = pipeline.epoch_file_order

    def spy(files, shuffle, seed, epoch):
        opened.append(seed)
        return real(files, shuffle, seed, epoch)

    monkeypatch.setattr(pipeline, "epoch_file_order", spy)
    return opened


@pytest.mark.parametrize("kw", [
    dict(vocab_mode="admit", hash_feature_id=True), dict(lookup="host")],
    ids=["admit", "offload"])
@pytest.mark.parametrize("host_threads", [1, 4])
def test_a_session_whose_barrier_can_change_batches_holds_the_feed(
        tmp_path, monkeypatch, kw, host_threads):
    """``vocab_mode = admit`` and ``lookup = host``: when a barrier
    returns, no file of the next epoch has been opened yet, whatever
    time the barrier took; nothing is counted as fed ahead; the steps
    are all there."""
    _one_device(monkeypatch)
    cfg = _cfg(tmp_path, host_threads=host_threads, **kw)
    opened = _opened(monkeypatch)
    at_exit = []
    real_barrier = train_mod._epoch_barrier

    def barrier(s, loop, epoch, stats):
        real_barrier(s, loop, epoch, stats)
        time.sleep(0.15)        # room for a feed that would not wait
        at_exit.append((epoch, sorted(set(opened))))

    monkeypatch.setattr(train_mod, "_epoch_barrier", barrier)
    train_mod.train(cfg)
    assert at_exit == [(e, [cfg.seed + i for i in range(e + 1)])
                       for e in range(3)]
    c = _counters(cfg)
    assert c["train/steps"] == 3 * PER_EPOCH and c["train/epochs"] == 3
    assert c["pipeline/epochs_fed_ahead"] == 0
    assert c["train/placed_ahead"] == 0
    assert _settled() == []


@pytest.mark.parametrize("host_threads", [1, 4])
def test_a_held_feed_builds_the_next_epoch_with_the_bucket_at_release(
        tmp_path, monkeypatch, host_threads):
    """The ``multi_process`` flag's feed (fixed shapes, a unique budget
    the barrier may move): nothing of epoch 1 is cut until the loop
    says the barrier is over, and its batches carry the budget as it
    stood then, as ``batch_iterator`` builds them with it."""
    cfg = _cfg(tmp_path, host_threads=host_threads,
               max_features_per_example=16)
    opened = _opened(monkeypatch)
    bucket = [64]
    feed = _feed(cfg, range(0, 2), hold=True, bucket=lambda: bucket[0],
                 fixed_shape=True)
    try:
        first = []
        for item in feed:
            if isinstance(item, EpochMark):
                break
            first.append(item[0])
        assert item.epoch == 0
        assert {len(b.uniq_ids) for b in first} == {64}
        time.sleep(0.2)
        assert set(opened) == {cfg.seed}
        assert feed.stats(1).batches == 0 and feed._first_out == 0
        bucket[0] = 128             # the barrier's adapt_uniq_bucket
        feed.release(0)
        second = [item[0] for item in feed
                  if not isinstance(item, EpochMark)]
    finally:
        feed.close()
    assert set(opened) == {cfg.seed, cfg.seed + 1}
    want = list(batch_iterator(cfg, cfg.train_files, epochs=1,
                               seed=cfg.seed + 1, fixed_shape=True,
                               uniq_bucket=128))
    assert {len(b.uniq_ids) for b in second} == {128}
    assert len(second) == len(want)
    for a, b in zip(second, want):
        _same(a, b)
    assert _settled() == []


def test_the_two_process_flag_holds_the_feed_and_the_loop_places(
        tmp_path, monkeypatch):
    """What ``_run_epochs`` asks for when the session says ``multi_process``
    (a second process is not started: the flag is what it reads)."""
    _one_device(monkeypatch)
    asked = []

    class Decided(BaseException):
        pass

    def spy(cfg, files, epochs, **kw):
        asked.append((epochs, kw["hold"], kw["place"], kw["fixed_shape"]))
        raise Decided()

    real_run = train_mod._run_epochs

    def as_two(s, loop):
        s.multi_process = True
        try:
            return real_run(s, loop)
        finally:
            s.multi_process = False     # the teardown's is one's

    monkeypatch.setattr(train_mod, "_run_epochs", as_two)
    monkeypatch.setattr(train_mod, "EpochFeed", spy)
    with pytest.raises(Decided):
        train_mod.train(_cfg(tmp_path))
    assert asked == [(range(0, 3), True, None, True)]


# ---- (d) a loop that stops leaves no thread of the feed -----------------------

class _Stop(BaseException):
    pass


@pytest.mark.parametrize("where", [
    "mid-epoch, the feed an epoch ahead", "inside a barrier, the feed held"])
def test_a_base_exception_leaves_no_thread_of_the_feed(
        tmp_path, monkeypatch, where):
    """Out of a step in the job's second epoch, with the third's batches
    queued behind the second's mark; and out of a barrier whose feed
    waits at the mark for the loop's word."""
    _one_device(monkeypatch)
    if where.startswith("mid-epoch"):
        cfg = _cfg(tmp_path, host_threads=4)
        real = train_mod.StepLoop.dispatch

        def dispatch(self, wb, args, step):
            if step == 2 * PER_EPOCH - 1:
                raise _Stop()
            time.sleep(0.004)
            return real(self, wb, args, step)

        monkeypatch.setattr(train_mod.StepLoop, "dispatch", dispatch)
    else:
        cfg = _cfg(tmp_path, host_threads=4, vocab_mode="admit",
                   hash_feature_id=True)
        real = train_mod._epoch_barrier

        def barrier(s, loop, epoch, stats):
            real(s, loop, epoch, stats)
            if epoch == 1:
                time.sleep(0.1)     # the producers are at the mark by now
                raise _Stop()

        monkeypatch.setattr(train_mod, "_epoch_barrier", barrier)
    live = []
    real_close = EpochFeed.close

    def close(self):
        live.append(_feed_threads())
        real_close(self)

    monkeypatch.setattr(EpochFeed, "close", close)
    with pytest.raises(_Stop):
        train_mod.train(cfg)
    (before,) = live
    assert "prefetch" in before and any(
        t.startswith("fm-build") for t in before)
    assert _settled() == []


# ---- (e) nothing per epoch ahead of time ------------------------------------

@pytest.mark.parametrize("host_threads", [1, 4])
def test_a_million_epochs_open_nothing_ahead_of_time(tmp_path, host_threads):
    """``epoch_num = 10^6`` (the benchmark's cells): the threads and the
    open files after epoch 2 are those after every later epoch, and the
    feed keeps the stats of the epochs in flight alone."""
    cfg = _cfg(tmp_path, host_threads=host_threads, epoch_num=10 ** 6)
    feed = _feed(cfg, range(0, cfg.epoch_num))
    readings = []
    try:
        for item in feed:
            if not isinstance(item, EpochMark):
                continue
            readings.append((_feed_threads(),
                             len(os.listdir("/proc/self/fd")),
                             len(feed._stats)))
            if item.epoch == 6:
                break
    finally:
        feed.close()
    threads = [r[0] for r in readings[2:]]
    assert all(t == threads[0] for t in threads), threads
    fds = [r[1] for r in readings[2:]]
    assert max(fds) - min(fds) <= 1, fds    # a file open under the scanner
    assert max(r[2] for r in readings) <= 2
    assert _settled() == []
