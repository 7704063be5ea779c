"""The sweeps' feed (ISSUE 51).

A validating epochs-mode job's sweeps read from ONE feed
(``train.sweep_feed``: ``pipeline.EpochFeed`` told that its plane is a
sweep's): a sweep is an epoch of it, its end the mark behind its last
batch, and the next sweep's first batches are cut, built and placed
while the interval trains. What is pinned here: sweep n's batches are
``batch_iterator(training=False, epochs=1)``'s, bit for bit; a sweep
scores the same bits with and without the feed; the second sweep's
first batch is out of the builders before the loop asks; a held feed
(``vocab_mode = admit``) cuts nothing until the sweep starts; the cap;
and a feed that is closed, or a sweep that raises, leaves nothing
behind. That the sweep's six leaves still partition its wall, with the
feed's placement on none of them, is tests/test_validation_phases.py's.

Since ISSUE 53 a sweep that fits stays on the device and its plane is
closed at the first mark (tests/test_resident_sweep.py). This file is
the plane's: what a held-out set over the budget, an admit-mode job and
a lookup backend still run at every sweep. Its jobs are given no
budget, so that every sweep of them streams."""

import gc
import os
import threading
import time
import weakref

import jax
import numpy as np
import pytest

from fast_tffm_tpu import train as train_mod
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data import pipeline
from fast_tffm_tpu.data.pipeline import (VALIDATION_PLANE, EpochFeed,
                                         EpochMark, batch_iterator)
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.obs.sink import read_events

from tests.test_e2e import make_dataset
from tests.test_epoch_feed import (_counters, _feed_threads, _one_device,
                                   _same, _settled)

B, PER_SWEEP = 32, 12       # three files of 4 batches, the last one short
STEPS = 4                   # an epoch's
CALL = ("uniq_ids", "local_idx", "vals", "fields")


@pytest.fixture(autouse=True)
def _every_sweep_streams(monkeypatch):
    from fast_tffm_tpu.obs import memory
    monkeypatch.setattr(memory, "RESIDENT_SWEEP_UNMEASURED_BYTES", 0)


def _held_out(d):
    out = []
    for i, n in enumerate((B * 4, B * 4, B * 4 - 7)):
        path = d / f"held{i}.txt"
        if not path.exists():
            make_dataset(path, n, np.random.default_rng(510 + i))
        out.append(str(path))
    return tuple(out)


def _cfg(d, **kw):
    train = d / "train.txt"
    if not train.exists():
        make_dataset(train, B * STEPS, np.random.default_rng(51))
    base = dict(vocabulary_size=200, factor_num=4, batch_size=B,
                learning_rate=0.1, epoch_num=3, seed=11, shuffle=True,
                queue_size=4 * B, train_files=(str(train),),
                validation_files=_held_out(d),
                model_file=str(d / "m" / "fm"), metrics_file="auto",
                metrics_flush_steps=2, log_steps=0)
    base.update(kw)
    return FmConfig(**base)


def _plain(cfg, **kw):
    """A sweep's batches as ``evaluate()`` read them until this PR."""
    return list(batch_iterator(cfg, cfg.validation_files, training=False,
                               epochs=1, counters=VALIDATION_PLANE, **kw))


def _taken(feed):
    """Every item of ``feed``, each mark let go as ``evaluate()`` lets
    it go behind a sweep's drain: the feed waits there for that."""
    got = []
    try:
        for item in feed:
            got.append(item)
            if isinstance(item, EpochMark):
                feed.release(item.epoch)
    finally:
        feed.close()
    return got


class _Scores:
    def __init__(self):
        self.chunks = []

    def update(self, s, y, w):
        self.chunks.append(np.array(s, copy=True))

    def bits(self):
        return np.concatenate(self.chunks).view(np.uint32)


# ---- (a) sweep n is the plain iterator's, array for array ------------------

@pytest.mark.parametrize("placed", [True, False],
                         ids=["placed", "a lookup backend's"])
@pytest.mark.parametrize("host_threads", [4, 1], ids=["ring", "chained"])
def test_three_sweeps_running_are_the_plain_iterators_batches(
        tmp_path, host_threads, placed):
    """``shuffle`` is on and ``seed`` set: a sweep's plane takes
    neither. Where the feed places, what it hands the scorer is the
    call's four arrays on the device, equal to the batch's."""
    cfg = _cfg(tmp_path, host_threads=host_threads)
    want = _plain(cfg)
    assert len(want) == PER_SWEEP
    got = _taken(train_mod.sweep_feed(
        cfg, cfg.validation_files, range(3),
        backend=None if placed else object()))
    assert len(got) == 3 * (PER_SWEEP + 1)
    for sweep in range(3):
        items = got[sweep * (PER_SWEEP + 1):(sweep + 1) * (PER_SWEEP + 1)]
        mark = items.pop()
        assert isinstance(mark, EpochMark) and mark.epoch == sweep
        assert mark.stats.batches == PER_SWEEP
        for (batch, args), plain in zip(items, want):
            _same(batch, plain)
            if not placed:
                assert args is None
                continue
            assert set(args) == set(CALL) - {"fields"}
            for name, value in args.items():
                assert isinstance(value, jax.Array), name
                np.testing.assert_array_equal(np.asarray(value),
                                              getattr(plain, name))
    assert _settled() == []


# ---- (b) the same bits with and without the feed ---------------------------

@pytest.mark.parametrize("path", ["one device", "raw ids", "mesh"])
def test_a_sweep_scores_the_same_bits_with_and_without_the_feed(
        tmp_path, path):
    cfg = _cfg(tmp_path, host_threads=4,
               dedup="device" if path == "raw ids" else "auto")
    mesh, table = None, fm.init_table(cfg, 3)
    if path == "mesh":
        from fast_tffm_tpu.parallel.sharded import (init_sharded_state,
                                                    make_mesh)
        mesh = make_mesh(jax.devices()[:4])
        table, _ = init_sharded_state(cfg, mesh, seed=3)
    cold = _Scores()
    auc, n = train_mod.evaluate(cfg, table, cfg.validation_files, mesh=mesh,
                                collect=cold)
    assert n == PER_SWEEP * B - 7 and 0.0 < auc < 1.0
    feed = train_mod.sweep_feed(cfg, cfg.validation_files, range(2),
                                mesh=mesh)
    try:
        for sweep in range(2):
            fed = _Scores()
            assert train_mod.evaluate(
                cfg, table, cfg.validation_files, mesh=mesh, collect=fed,
                feed=feed) == (auc, n)
            assert feed.marked == sweep
            np.testing.assert_array_equal(fed.bits(), cold.bits())
    finally:
        feed.close()
    assert _settled() == []


# ---- (c) ahead of the loop, or held until the sweep starts -----------------

def _paced(monkeypatch):
    """The device sets the pace of an epoch, not the host."""
    real = train_mod.StepLoop.dispatch

    def dispatch(self, wb, args, step):
        time.sleep(0.01)
        return real(self, wb, args, step)

    monkeypatch.setattr(train_mod.StepLoop, "dispatch", dispatch)


def test_the_next_sweeps_first_batch_is_out_before_the_loop_asks(
        tmp_path, monkeypatch):
    """One feed for the job's three sweeps: the second and the third
    find their first batch built (counted as the sweep starts), every
    batch is placed on the feed's thread and scored as device arrays,
    and nothing is built for a sweep that never comes."""
    _one_device(monkeypatch)
    _paced(monkeypatch)
    cfg = _cfg(tmp_path, host_threads=4, trace_spans=True)
    feeds, fed, threads = [], [], []
    real_feed, real_scorer = train_mod.sweep_feed, train_mod.make_batch_scorer

    def sweep_feed(*a, **k):
        feeds.append(real_feed(*a, **k))
        return feeds[-1]

    def scorer(*a, **k):
        score = real_scorer(*a, **k)

        def call(table, args):
            fed.append({k: isinstance(v, jax.Array)
                        for k, v in args.items()})
            threads.append({(t.name, t.ident) for t in threading.enumerate()
                            if t.name.startswith("fm-place")})
            return score(table, args)
        return call

    monkeypatch.setattr(train_mod, "sweep_feed", sweep_feed)
    monkeypatch.setattr(train_mod, "make_batch_scorer", scorer)
    train_mod.train(cfg)
    assert len(feeds) == 1 and feeds[0].marked == 2
    c = _counters(cfg)
    assert c["validation/sweeps"] == 3 == c["train/epochs"]
    assert c["validation_plane/epochs_fed_ahead"] == 2  # the first is cold
    assert c["validation/batches"] == 3 * PER_SWEEP
    assert c["validation_plane/batches"] == 3 * PER_SWEEP
    assert c["validation/place_seconds"] > 0
    assert c["pipeline/epochs_fed_ahead"] == 2 and c["train/steps"] == 12
    # the score call takes device arrays: placement is the feed's
    assert len(fed) == 3 * PER_SWEEP
    assert all(placed for call in fed for placed in call.values())
    # two placing threads, the training feed's and the sweeps': the same
    # two while there is an epoch to come, and none made after them
    assert len(threads[0]) == 2
    assert all(t == threads[0] for t in threads[:2 * PER_SWEEP])
    assert all(t <= threads[0] for t in threads)
    events = list(read_events(cfg.model_file + ".metrics.jsonl"))
    # in every snapshot from the first sweep on
    with_it = ["validation_plane/epochs_fed_ahead" in e["counters"]
               for e in events if e["event"] == "metrics"]
    assert with_it[-1] and with_it == sorted(with_it)
    first = sorted((e for e in events if e["event"] == "span"
                    and e["name"] == "validation/first_batch"),
                   key=lambda e: e["ts"])
    assert len(first) == 3
    assert _settled() == []


def test_a_held_feed_cuts_nothing_until_the_sweep_starts(tmp_path,
                                                         monkeypatch):
    """``vocab_mode = admit``: no file of the held-out day is opened
    between sweeps, each sweep's eval view is taken after its epoch's
    vocab barrier and before its files open, nothing counts as fed
    ahead or is placed ahead, and the AUCs are those of a job whose
    sweeps each make their own plane."""
    _one_device(monkeypatch)
    log = []
    real_order, real_eval = pipeline.epoch_file_order, train_mod.evaluate
    real_barrier = train_mod.StepLoop.vocab_barrier
    from fast_tffm_tpu.vocab.table import VocabMap
    real_view = VocabMap.eval_view

    def order(files, shuffle, seed, epoch):
        if "held0" in files[0]:
            log.append("open")
        return real_order(files, shuffle, seed, epoch)

    def evaluate(*a, **k):
        log.append("sweep")
        try:
            return real_eval(*a, **k)
        finally:
            log.append("swept")
            time.sleep(0.15)    # room for a feed that would not wait

    def barrier(self, where):
        if where.startswith("epoch"):
            log.append("barrier")
        return real_barrier(self, where)

    def view(self):
        log.append("view")
        return real_view(self)

    monkeypatch.setattr(pipeline, "epoch_file_order", order)
    monkeypatch.setattr(train_mod, "evaluate", evaluate)
    monkeypatch.setattr(train_mod.StepLoop, "vocab_barrier", barrier)
    monkeypatch.setattr(VocabMap, "eval_view", view)
    aucs = {}
    for how in ("one feed", "a plane a sweep"):
        (tmp_path / how).mkdir()
        cfg = _cfg(tmp_path / how, host_threads=4, vocab_mode="admit",
                   hash_feature_id=True)
        if how != "one feed":
            monkeypatch.setattr(
                train_mod._Session, "validate",
                lambda self, table, of_epoch=False, _v=train_mod._Session
                .validate, **k: _v(self, table, **k))
        del log[:]
        train_mod.train(cfg)
        assert log == ["barrier", "sweep", "view", "open", "swept"] * 3, how
        c = _counters(cfg)
        assert c["validation/sweeps"] == 3
        assert c["validation_plane/epochs_fed_ahead"] == 0
        assert "validation/place_seconds" not in c  # the loop places
        events = read_events(cfg.model_file + ".metrics.jsonl")
        aucs[how] = [e["value"] for e in events if e["event"] == "scalar"
                     and e["name"] == "validation/auc"]
        assert len(aucs[how]) == 3
    assert aucs["one feed"] == aucs["a plane a sweep"]
    assert _settled() == []


# ---- (d) the cap ----------------------------------------------------------

@pytest.mark.parametrize("host_threads", [4, 1], ids=["ring", "chained"])
def test_a_capped_sweep_stops_at_the_cap_and_the_next_starts_over(
        tmp_path, host_threads):
    cfg = _cfg(tmp_path, host_threads=host_threads)
    want = _plain(cfg)[:5]
    got = _taken(train_mod.sweep_feed(cfg, cfg.validation_files, range(3),
                                      max_batches=5))
    assert [i for i, item in enumerate(got)
            if isinstance(item, EpochMark)] == [5, 11, 17]
    for sweep in range(3):
        for (batch, _), plain in zip(got[6 * sweep:6 * sweep + 5], want):
            _same(batch, plain)
    assert got[5].stats.batches == 5
    assert _settled() == []


def test_validation_max_batches_caps_every_sweep_of_a_job(tmp_path,
                                                          monkeypatch):
    _one_device(monkeypatch)
    cfg = _cfg(tmp_path, host_threads=4, validation_max_batches=5)
    train_mod.train(cfg)
    c = _counters(cfg)
    assert c["validation/sweeps"] == 3
    assert c["validation/batches"] == 15 == c["validation_plane/batches"]
    assert c["validation/examples"] == 15 * B
    with pytest.raises(ValueError, match="capped"):
        EpochFeed(cfg, cfg.validation_files, range(2), place=None,
                  hold=False, uniq_bucket=lambda: 64, fixed_shape=True,
                  max_batches=5)


# ---- (e) nothing left behind ------------------------------------------------

class _Stop(BaseException):
    pass


def test_a_sweep_that_raises_leaves_no_thread_and_no_placed_batch(
        tmp_path, monkeypatch):
    """Out of the third score call of the job's second sweep, with the
    rest of that sweep placed and queued behind it."""
    _one_device(monkeypatch)
    _paced(monkeypatch)
    cfg = _cfg(tmp_path, host_threads=4)
    placed, calls, live = [], [], []
    real_placer, real_scorer = fm.make_score_placer, train_mod.make_batch_scorer

    def placer(*a, **k):
        place = real_placer(*a, **k)

        def spy(batch):
            batch, args = place(batch)
            placed.extend(weakref.ref(v) for v in args.values())
            return batch, args
        return spy

    def scorer(*a, **k):
        score = real_scorer(*a, **k)

        def call(table, args):
            calls.append(1)
            if len(calls) == PER_SWEEP + 3:
                live.append(_feed_threads())
                raise _Stop()
            return score(table, args)
        return call

    monkeypatch.setattr(train_mod, "make_score_placer", placer)
    monkeypatch.setattr(train_mod, "make_batch_scorer", scorer)
    with pytest.raises(_Stop):
        train_mod.train(cfg)
    (before,) = live
    assert before.count("fm-place") == 2 and before.count("prefetch") == 2
    assert _settled() == []
    gc.collect()
    assert len(placed) > 3 * (PER_SWEEP + 3)
    assert not [r for r in placed if r() is not None]


def _resident_kb():
    with open("/proc/self/statm") as fh:
        return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") // 1024


def test_a_thousand_sweeps_leave_threads_files_and_memory_flat(tmp_path):
    """Every twentieth through ``evaluate()`` itself (its fetcher's
    thread counts), the others taken off the feed as it does."""
    make_dataset(tmp_path / "day.txt", 2 * B - 7, np.random.default_rng(5))
    cfg = _cfg(tmp_path, host_threads=4,
               validation_files=(str(tmp_path / "day.txt"),))
    table = fm.init_table(cfg, 3)
    feed = train_mod.sweep_feed(cfg, cfg.validation_files, range(1100))
    readings, results = [], set()
    try:
        for sweep in range(1000):
            if sweep % 20 == 0:
                results.add(train_mod.evaluate(
                    cfg, table, cfg.validation_files, feed=feed))
            else:
                feed.release(feed.marked)
                n = sum(1 for _ in iter(
                    lambda: isinstance(next(feed), EpochMark), True))
                assert n == 2
            assert feed.marked == sweep
            if sweep % 100 == 99:
                gc.collect()
                readings.append((sorted(t.name for t in
                                        threading.enumerate()),
                                 len(os.listdir("/proc/self/fd")),
                                 _resident_kb()))
    finally:
        feed.close()
    assert len(results) == 1 and results.pop()[1] == 2 * B - 7
    threads = [r[0] for r in readings[1:]]
    assert all(t == threads[0] for t in threads), threads
    fds = [r[1] for r in readings[1:]]
    assert max(fds) - min(fds) <= 1, fds
    kb = [r[2] for r in readings[2:]]
    assert max(kb) - min(kb) < 16 * 1024, kb
    assert _settled() == []
