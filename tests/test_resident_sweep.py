"""A sweep that fits stays on the device (ISSUE 53).

A validating job's held-out files are the same at every sweep and the
sweeps' plane has no shuffle, so ``train.sweep_feed`` returns a holder
(``data/resident.py`` ``ResidentSweeps``) in front of the plane: the
first sweep streams and is kept as it passes, its plane is closed at
its mark, and every later sweep scores the kept placed batches again.
What is pinned here: (a) three sweeps score the same bits resident and
streamed, and a sweep leaves the table alone; (b) over the budget the
job streams and keeps nothing; (c) a held-out file rewritten between
sweeps is read again, and that sweep stays in its turn; (d) an
admit-mode view and a lookup backend never stay; (e) the plane's
counters count what a sweep is handed, and what is not done stands
still; (f) no thread of the sweeps' plane outlives the first mark; (g)
``close_sweeps()`` lets the arrays and the ledger's entry go, also out
of a sweep that raised; and the planner's line, which the pre-flight
does not enforce: a sweep is kept out of what the device has left."""

import gc
import logging
import os
import types
import weakref

import jax
import numpy as np
import pytest

from fast_tffm_tpu import train as train_mod
from fast_tffm_tpu.data import resident
from fast_tffm_tpu.data.pipeline import EpochMark
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.obs import memory as mem
from fast_tffm_tpu.obs.sink import read_events
from fast_tffm_tpu.obs.telemetry import active

from tests.test_e2e import make_dataset
from tests.test_epoch_feed import (_counters, _feed_threads, _one_device,
                                   _settled)
from tests.test_sweep_feed import B, PER_SWEEP, _Scores, _cfg, _plain

N = PER_SWEEP * B - 7      # a sweep's examples
OWNER = mem.RESIDENT_SWEEP_OWNER


@pytest.fixture
def said():
    """The program's log lines about the sweeps, as a job says them."""
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            if "validation sweeps" in record.getMessage():
                lines.append(record.getMessage())
    logger, handler = logging.getLogger("fast_tffm_tpu"), Keep()
    logger.addHandler(handler)
    yield lines
    logger.removeHandler(handler)


@pytest.fixture(autouse=True)
def _clean_ledger():
    mem.LEDGER.release(OWNER)
    yield
    assert OWNER not in mem.LEDGER.owners()


def _streamed(monkeypatch):
    """No sweep fits: the parent's path."""
    monkeypatch.setattr(mem, "RESIDENT_SWEEP_UNMEASURED_BYTES", 0)


def _placed_bytes(batch):
    return sum(a.nbytes for a in (batch.uniq_ids, batch.local_idx,
                                  batch.vals, batch.fields)
               if a is not None)


def _sweeps(cfg, table, n, mesh=None, between=None, **kw):
    """``n`` sweeps of one feed through ``evaluate()``: each one's
    result and score bits, and the holder as the last one left it."""
    feed = train_mod.sweep_feed(cfg, cfg.validation_files, range(n),
                                mesh=mesh, **kw)
    out = []
    try:
        for sweep in range(n):
            if between is not None:
                between(sweep, feed)
            got = _Scores()
            result = train_mod.evaluate(cfg, table, cfg.validation_files,
                                        mesh=mesh, collect=got, feed=feed,
                                        **kw)
            assert feed.marked == sweep
            out.append((result, got.bits()))
        kept = feed._kept is not None
    finally:
        feed.close()
    return out, kept


def _table(cfg, path):
    if path != "mesh":
        return None, fm.init_table(cfg, 3)
    from fast_tffm_tpu.parallel.sharded import init_sharded_state, make_mesh
    mesh = make_mesh(jax.devices()[:4])
    return mesh, init_sharded_state(cfg, mesh, seed=3)[0]


# ---- (a) the same bits, and the table left alone ---------------------------

@pytest.mark.parametrize("path", ["one device", "raw ids", "mesh"])
def test_three_sweeps_score_the_same_bits_resident_and_streamed(
        tmp_path, monkeypatch, said, path):
    cfg = _cfg(tmp_path, host_threads=4,
               dedup="device" if path == "raw ids" else "auto")
    mesh, table = _table(cfg, path)
    before = np.asarray(table).view(np.uint32).copy()
    here, kept = _sweeps(cfg, table, 3, mesh=mesh)
    assert kept and said == [f"validation sweeps resident: {PER_SWEEP} "
                             "batches, 0 MB on the device (budget 67 MB)"]
    np.testing.assert_array_equal(np.asarray(table).view(np.uint32), before)
    _streamed(monkeypatch)
    there, kept = _sweeps(cfg, table, 3, mesh=mesh)
    assert not kept and "over the budget of 0 MB" in said[-1]
    (auc, n), bits = there[0]
    assert n == N and 0.0 < auc < 1.0
    for (result, got) in here + there:
        assert result == (auc, n)
        np.testing.assert_array_equal(got, bits)
    assert _settled() == []


@pytest.mark.parametrize("devices", ["one device", "a mesh"])
def test_a_job_validates_and_trains_the_same_resident_and_streamed(
        tmp_path, monkeypatch, devices):
    """Every sweep's AUC and the table the job ends on."""
    if devices == "one device":
        _one_device(monkeypatch)
    ran = {}
    for how in ("resident", "streamed"):
        (tmp_path / how).mkdir()
        cfg = _cfg(tmp_path / how, host_threads=4)
        if how == "streamed":
            _streamed(monkeypatch)
        table = train_mod.train(cfg)
        events = read_events(cfg.model_file + ".metrics.jsonl")
        aucs = [e["value"] for e in events if e["event"] == "scalar"
                and e["name"] == "validation/auc"]
        c = _counters(cfg)
        assert c["validation/sweeps"] == 3 == len(aucs)
        assert c["validation/resident_sweeps"] == (2 if how == "resident"
                                                   else 0)
        ran[how] = (aucs, np.asarray(table).view(np.uint32))
    assert ran["resident"][0] == ran["streamed"][0]
    np.testing.assert_array_equal(ran["resident"][1], ran["streamed"][1])
    assert _settled() == []


# ---- (b) over the budget ----------------------------------------------------

def test_a_sweep_over_the_budget_streams_and_nothing_is_kept(
        tmp_path, monkeypatch, said):
    """Three batches fit, the fourth does not: the three are let go
    there, the plane lives on and cuts every sweep as it did."""
    cfg = _cfg(tmp_path, host_threads=4)
    sizes = [_placed_bytes(b) for b in _plain(cfg)]
    monkeypatch.setattr(mem, "RESIDENT_SWEEP_UNMEASURED_BYTES",
                        sum(sizes[:3]))
    placed, live_at_mark, planes = [], [], []
    real = fm.make_score_placer

    def placer(*a, **k):
        place = real(*a, **k)

        def spy(batch):
            batch, args = place(batch)
            placed.append([weakref.ref(v) for v in args.values()])
            return batch, args
        return spy

    monkeypatch.setattr(train_mod, "make_score_placer", placer)

    def between(sweep, feed):
        planes.append(feed._feed)
        if sweep:   # behind a mark: what the holder still holds
            gc.collect()
            # (a sweep's last batch is the placing stage's until it
            # places the next one, as on the parent)
            live_at_mark.append(sum(r() is not None for refs
                                    in placed[:PER_SWEEP - 1] for r in refs))
        assert feed._kept is None and not feed._taking

    out, kept = _sweeps(cfg, fm.init_table(cfg, 3), 3, between=between)
    assert not kept and live_at_mark == [0, 0]
    assert planes[0] is planes[1] is planes[2] is not None
    assert len(placed) == 3 * PER_SWEEP      # every sweep placed anew
    assert len({r for r, _ in out}) == 1 and out[0][0][1] == N
    want = sum(sizes)
    assert said == [f"validation sweeps streamed: {want / 1e6:.0f} MB over "
                    f"the budget of {sum(sizes[:3]) / 1e6:.0f} MB (a sweep's "
                    "share of the device)"]
    assert _settled() == []


def test_the_budget_is_a_share_of_the_device_and_a_constant_without_one(
        monkeypatch):
    monkeypatch.delenv(mem.FAKE_CAPACITY_ENV, raising=False)
    assert mem.device_capacity_bytes() is None      # the CPU
    assert mem.resident_sweep_budget() == 64 << 20
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, str(16 << 30))
    assert mem.resident_sweep_budget() == (16 << 30) // 32
    # the planner's, from a config: a capacity and what the plan holds
    assert mem.resident_sweep_budget(32 << 30) == 1 << 30
    assert mem.resident_sweep_budget(32 << 30, 31 << 30) == 1 << 29


@pytest.mark.parametrize("stats, want", [
    (dict(bytes_limit=32 << 30, peak_bytes_in_use=24 << 30,
          bytes_in_use=1 << 30), 1 << 30),           # the share: room for it
    (dict(bytes_limit=32 << 30, peak_bytes_in_use=31 << 30,
          bytes_in_use=1 << 30), 1 << 29),           # half of what is left
    (dict(bytes_limit=32 << 30, bytes_in_use=31 << 30), 1 << 29),
    (dict(bytes_limit=32 << 30, peak_bytes_in_use=33 << 30), 0),
    (None, 64 << 20), ({}, 64 << 20)],
    ids=["room", "little left", "no high-water mark", "none left",
         "no stats", "no limit"])
def test_the_budget_is_never_more_than_half_of_what_the_device_has_left(
        monkeypatch, stats, want):
    """Over the runtime's own high-water mark, which holds the train
    step's peak by the time a job's first sweep opens."""
    monkeypatch.setattr(mem, "device_memory_stats", lambda: stats)
    assert mem.resident_sweep_budget() == want


def test_a_sweep_the_device_has_no_room_for_streams_and_says_so(
        tmp_path, monkeypatch, said):
    """The share would admit it; what the state has left does not."""
    cfg = _cfg(tmp_path, host_threads=4)
    want = sum(_placed_bytes(b) for b in _plain(cfg))
    state = 64 * want
    mem.LEDGER.register("test_state", state)
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, str(state + 2 * want - 2))
    try:
        out, kept = _sweeps(cfg, fm.init_table(cfg, 3), 3)
    finally:
        mem.LEDGER.release("test_state")
    assert not kept and len({r for r, _ in out}) == 1
    assert said == [f"validation sweeps streamed: {want / 1e6:.0f} MB over "
                    f"the budget of {(want - 1) / 1e6:.0f} MB (half of what "
                    "the device has left)"]
    assert _settled() == []


# ---- (c) the files change under the job -------------------------------------

@pytest.mark.parametrize("what", ["size", "mtime_ns"])
def test_a_rewritten_file_is_read_again_and_that_sweep_stays(
        tmp_path, monkeypatch, said, what):
    cfg = _cfg(tmp_path, host_threads=4)
    table = fm.init_table(cfg, 3)
    path = cfg.validation_files[1]
    resident_at, opened = [], []
    real = train_mod.EpochFeed

    def plane(cfg, files, sweeps, **kw):
        opened.append(sweeps)
        return real(cfg, files, sweeps, **kw)

    monkeypatch.setattr(train_mod, "EpochFeed", plane)

    def between(sweep, feed):
        resident_at.append(feed._kept is not None)
        if sweep != 2:
            return
        st = os.stat(path)
        if what == "size":     # another day's lines, one batch more
            make_dataset(path, B * 5, np.random.default_rng(77))
        else:                  # the same lines, written again
            os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns + 10 ** 9))

    out, kept = _sweeps(cfg, table, 5, between=between)
    # sweep 2 found the file changed, streamed from a plane of its own
    # and had stayed by its mark
    assert kept and resident_at == [False, True, True, True, True]
    assert opened == [range(0, 5), range(2, 5)]
    assert out[0][0] == out[1][0] and out[2][0] == out[3][0] == out[4][0]
    np.testing.assert_array_equal(out[0][1], out[1][1])
    for later in out[3:]:
        np.testing.assert_array_equal(out[2][1], later[1])
    # what sweep 2 scored is what a cold sweep of the files reads now
    cold = _Scores()
    assert train_mod.evaluate(cfg, table, cfg.validation_files,
                              collect=cold) == out[2][0]
    np.testing.assert_array_equal(cold.bits(), out[2][1])
    assert out[2][0][1] == N + (B if what == "size" else 0)
    assert (out[2][0] == out[0][0]) == (what == "mtime_ns")
    batches = PER_SWEEP + (what == "size")
    assert said == [
        f"validation sweeps resident: {PER_SWEEP} batches, 0 MB on the "
        "device (budget 67 MB)",
        "validation sweeps streamed: files changed",
        f"validation sweeps resident: {batches} batches, 0 MB on the "
        "device (budget 67 MB)"]
    assert _settled() == []


def test_a_file_rewritten_while_it_is_read_is_not_kept(tmp_path, said):
    """The files are looked at before a sweep's read and at its mark:
    a sweep between two different looks is no sweep to keep, the next
    one is."""
    cfg = _cfg(tmp_path, host_threads=4)
    feed = train_mod.sweep_feed(cfg, cfg.validation_files, range(3))
    try:
        st = os.stat(cfg.validation_files[0])
        os.utime(cfg.validation_files[0],
                 ns=(st.st_atime_ns, st.st_mtime_ns + 10 ** 9))
        kept = []
        for sweep in range(3):
            feed.release(feed.marked)
            while not isinstance(next(feed), EpochMark):
                pass
            kept.append(feed._kept is not None)
    finally:
        feed.close()
    assert kept == [False, True, True]
    assert said[0] == "validation sweeps streamed: files changed"
    assert said[1].startswith("validation sweeps resident: ")
    assert _settled() == []


# ---- (d) never resident -----------------------------------------------------

@pytest.mark.parametrize("why", ["admit-mode view", "host lookup"])
def test_an_admit_mode_job_and_a_lookup_backend_never_stay(
        tmp_path, monkeypatch, said, why):
    """The parent's path, sweep for sweep: the same AUCs as a job whose
    sweeps could not stay for their size, the plane fed ahead where it
    was (a vocab's is held until the sweep starts), nothing kept."""
    _one_device(monkeypatch)
    kw = (dict(vocab_mode="admit", hash_feature_id=True)
          if why == "admit-mode view" else dict(lookup="host"))
    feeds, aucs = [], {}
    real = train_mod.sweep_feed

    def sweep_feed(*a, **k):
        feeds.append(real(*a, **k))
        return feeds[-1]

    monkeypatch.setattr(train_mod, "sweep_feed", sweep_feed)
    for how in ("as it is", "no budget"):
        (tmp_path / how).mkdir()
        cfg = _cfg(tmp_path / how, host_threads=4, **kw)
        if how == "no budget":
            _streamed(monkeypatch)
        train_mod.train(cfg)
        c = _counters(cfg)
        assert c["validation/sweeps"] == 3
        assert c["validation/resident_sweeps"] == 0
        assert c["validation_plane/batches"] == 3 * PER_SWEEP
        assert c["validation_plane/epochs_fed_ahead"] == (
            0 if why == "admit-mode view" else 2)
        assert "validation/place_seconds" not in c   # the loop places
        events = read_events(cfg.model_file + ".metrics.jsonl")
        gauges = [e for e in events if e["event"] == "metrics"][-1]["gauges"]
        assert "validation/resident_bytes" not in gauges
        assert f"mem/{OWNER}_bytes" not in gauges
        aucs[how] = [e["value"] for e in events if e["event"] == "scalar"
                     and e["name"] == "validation/auc"]
    assert len(feeds) == 2 and aucs["as it is"] == aucs["no budget"]
    assert all(f._kept is None and f._taking is None for f in feeds)
    assert said == [f"validation sweeps streamed: {why}"] * 2
    assert _settled() == []


# ---- (e) the counters -------------------------------------------------------

HANDED = ("batches", "examples", "uniq_rows", "uniq_slots", "shard_rows_max",
          "shard_slots", "feature_slots", "feature_nnz", "truncated_cells")


def test_the_planes_counters_count_what_a_sweep_is_handed(tmp_path,
                                                          monkeypatch):
    """Sweep by sweep, a resident job's beside a streamed one's: every
    count of ``pipeline_batch`` advances by the same on a replay, the
    build and placement seconds and the builders' stand still on one,
    and ``epochs_fed_ahead`` stays the count of sweeps the PLANE fed
    ahead: none of a job whose plane went at its first mark."""
    _one_device(monkeypatch)
    real = train_mod.evaluate
    after = {}

    def evaluate(*a, **k):
        out = real(*a, **k)
        after[how].append(dict(active().registry.snapshot()["counters"]))
        return out

    monkeypatch.setattr(train_mod, "evaluate", evaluate)
    for how in ("resident", "streamed"):
        (tmp_path / how).mkdir()
        after[how] = []
        if how == "streamed":
            _streamed(monkeypatch)
        cfg = _cfg(tmp_path / how, host_threads=4)
        train_mod.train(cfg)
    here, there = after["resident"], after["streamed"]
    assert len(here) == len(there) == 3
    for sweep in range(3):
        assert here[sweep]["validation/resident_sweeps"] == sweep
        assert there[sweep]["validation/resident_sweeps"] == 0
        for name in HANDED:
            name = "validation_plane/" + name
            # (the streamed job's plane runs ahead of its sweeps: the
            # job's last is where it has built three and no more)
            assert here[sweep][name] == here[0][name] * (sweep + 1), name
            assert there[2][name] == here[0][name] * 3, name
        assert here[sweep]["validation_plane/batches"] == (
            PER_SWEEP * (sweep + 1))
        assert here[sweep]["validation_plane/uniq_rows"] > 0
        assert here[sweep]["validation_plane/epochs_fed_ahead"] == 0
        assert there[sweep]["validation_plane/epochs_fed_ahead"] == sweep
        assert here[sweep]["validation/batches"] == PER_SWEEP * (sweep + 1)
    for name in ("validation/place_seconds", "validation_plane/build_seconds",
                 "validation_plane/worker_build_seconds"):
        assert here[0][name] > 0, name
        assert here[0][name] == here[1][name] == here[2][name], name
        assert there[2][name] > there[0][name], name
    assert _settled() == []


def test_what_is_kept_is_in_the_ledger_the_gauges_and_the_stream(tmp_path,
                                                                 monkeypatch):
    _one_device(monkeypatch)
    cfg = _cfg(tmp_path, host_threads=4)
    want = sum(_placed_bytes(b) for b in _plain(cfg))
    seen = []
    real = train_mod.evaluate

    def evaluate(*a, **k):
        out = real(*a, **k)
        seen.append(mem.LEDGER.owners().get(OWNER))
        return out

    monkeypatch.setattr(train_mod, "evaluate", evaluate)
    train_mod.train(cfg)
    assert seen == [want] * 3
    events = [e for e in read_events(cfg.model_file + ".metrics.jsonl")
              if e["event"] == "metrics"]
    kept = [e["gauges"].get("validation/resident_bytes") for e in events]
    assert want in kept and kept[-1] == 0.0        # let go at the close
    assert want in [e["gauges"].get(f"mem/{OWNER}_bytes") for e in events]
    # in every snapshot from the first sweep on, as its neighbours are
    with_it = ["validation/resident_sweeps" in e["counters"] for e in events]
    assert with_it[-1] and with_it == sorted(with_it)
    assert _settled() == []


# ---- (f) the plane's threads end at the first mark --------------------------

@pytest.mark.parametrize("host_threads", [4, 1], ids=["ring", "chained"])
def test_no_thread_of_the_sweeps_plane_outlives_the_first_mark(
        tmp_path, host_threads):
    cfg = _cfg(tmp_path, host_threads=host_threads)
    feed = train_mod.sweep_feed(cfg, cfg.validation_files, range(3))
    live = []
    try:
        for sweep in range(3):
            for n, item in enumerate(feed):
                if n == 0 or isinstance(item, EpochMark):
                    live.append(_feed_threads())
                if isinstance(item, EpochMark):
                    break
            assert (feed._feed is None) and feed.marked == sweep
    finally:
        feed.close()
    # closed on the loop's thread at the mark, and joined there
    assert "fm-place" in live[0] and "prefetch" in live[0]
    assert ("fm-scan" in live[0]) == (host_threads == 4)
    assert live[1:] == [[]] * 5


# ---- (g) the close ----------------------------------------------------------

def _resident_feed(cfg):
    feed = train_mod.sweep_feed(cfg, cfg.validation_files, range(4))
    refs = []
    for item in feed:
        if isinstance(item, EpochMark):
            break
        refs.extend(weakref.ref(v) for v in item[1].values())
    item = None
    assert feed._kept is not None and len(refs) == 3 * PER_SWEEP
    return feed, refs


@pytest.mark.parametrize("how", ["close_sweeps", "close", "twice"])
def test_the_close_lets_the_arrays_and_the_ledgers_entry_go(tmp_path, how):
    cfg = _cfg(tmp_path, host_threads=4)
    feed, refs = _resident_feed(cfg)
    gc.collect()
    assert all(r() is not None for r in refs)
    assert mem.LEDGER.owners()[OWNER] > 0
    if how == "close_sweeps":
        session = types.SimpleNamespace(sweeps=feed)
        train_mod._Session.close_sweeps(session)
        assert session.sweeps is None
    else:
        feed.close()
    if how == "twice":
        feed.close()
    gc.collect()
    assert not [r for r in refs if r() is not None]
    assert OWNER not in mem.LEDGER.owners()
    with pytest.raises(StopIteration):
        next(feed)
    assert _settled() == []


def test_a_feed_past_its_last_sweep_hands_out_no_more(tmp_path):
    cfg = _cfg(tmp_path, host_threads=4)
    feed = train_mod.sweep_feed(cfg, cfg.validation_files, range(5, 7))
    try:
        marks = [item.epoch for item in feed if isinstance(item, EpochMark)]
    finally:
        feed.close()
    assert marks == [5, 6] and feed.marked == 6


@pytest.mark.parametrize("kw", [{}, {"backend": object()}],
                         ids=["placed", "a lookup backend's"])
def test_a_feed_of_one_sweep_keeps_nothing_and_says_nothing(
        tmp_path, monkeypatch, said, kw):
    """``evaluate()`` with no ``feed`` (a stream job's publish gate,
    ``_finish``'s last sweep) and a job's last epoch: nothing comes
    behind to score it again."""
    cfg = _cfg(tmp_path, host_threads=4)
    monkeypatch.setattr(resident, "files_signature", None)  # nor looked at
    feed = train_mod.sweep_feed(cfg, cfg.validation_files, range(2, 3), **kw)
    try:
        n = sum(1 for item in feed if not isinstance(item, EpochMark))
        assert feed._kept is None and feed._taking is None
    finally:
        feed.close()
    assert n == PER_SWEEP and feed.marked == 2 and said == []
    assert _settled() == []


class _Stop(BaseException):
    pass


@pytest.mark.parametrize("sweep", [0, 1, 2],
                         ids=["the first", "the second", "the third"])
def test_a_sweep_that_raises_leaves_nothing_on_the_device(
        tmp_path, monkeypatch, sweep):
    """Out of the job's ``finally`` (``close_sweeps``): while the first
    sweep streams and is being kept, and out of a replay."""
    _one_device(monkeypatch)
    cfg = _cfg(tmp_path, host_threads=4)
    placed, calls, ledger = [], [], []
    real_placer = fm.make_score_placer
    real_scorer = train_mod.make_batch_scorer

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
            if len(calls) == sweep * PER_SWEEP + 5:
                ledger.append(mem.LEDGER.owners().get(OWNER))
                raise _Stop()
            return score(table, args)
        return call

    monkeypatch.setattr(train_mod, "make_score_placer", placer)
    monkeypatch.setattr(train_mod, "make_batch_scorer", scorer)
    with pytest.raises(_Stop):
        train_mod.train(cfg)
    assert (ledger[0] is None) == (sweep == 0)
    assert OWNER not in mem.LEDGER.owners()
    assert _settled() == []
    gc.collect()
    assert len(placed) >= 5 * 3
    assert not [r for r in placed if r() is not None]


# ---- the planner's line -----------------------------------------------------

KEY = OWNER + "_bytes"


@pytest.mark.parametrize("case, want", [
    ("no validation_files", 0), ("host lookup", 0), ("admit-mode view", 0),
    ("uncapped", 64 << 20), ("capped", 5 * 32 * 16 * 12),
    ("capped, ffm", 5 * 32 * 16 * 16),
    ("capped past the budget", 64 << 20)])
def test_the_plan_has_a_line_for_the_resident_sweep(tmp_path, monkeypatch,
                                                    case, want):
    """Beside the total and not in it."""
    monkeypatch.delenv(mem.FAKE_CAPACITY_ENV, raising=False)
    kw = {"no validation_files": dict(validation_files=()),
          "host lookup": dict(lookup="host"),
          "admit-mode view": dict(vocab_mode="admit", hash_feature_id=True),
          "uncapped": {}, "capped": dict(validation_max_batches=5),
          "capped, ffm": dict(validation_max_batches=5, model_type="ffm",
                              field_num=3),
          "capped past the budget": dict(validation_max_batches=10 ** 6)}
    cfg = _cfg(tmp_path, max_features_per_example=16, **kw[case])
    p = mem.plan(cfg, "train")
    assert p[KEY] == want and OWNER not in p["owners"]
    assert p["total_bytes"] == sum(p["owners"].values())
    line = next(l for l in mem.render_plan(p).splitlines() if OWNER in l)
    assert f"{want:,} B" in line and ("not in the total" in line) == (want > 0)
    serve = mem.plan(cfg, "serve")
    assert KEY not in serve and OWNER not in mem.render_plan(serve)
    # sized for another chip (``fmstat capacity --capacity-bytes``): the
    # share of it, out of what the plan leaves of it
    there = mem.plan(cfg, "train", capacity=16 << 30)
    assert there[KEY] == (min(want, (16 << 30) // 32)
                          if "capped" == case or "ffm" in case or not want
                          else (16 << 30) // 32)
    assert there["verdict"] == "FITS"
    tight = mem.plan(cfg, "train", capacity=p["total_bytes"] + 1000)
    assert tight[KEY] == min(want, 500) and tight["verdict"] == "FITS"


def test_a_job_the_parent_admitted_is_not_refused_for_its_sweep(
        tmp_path, monkeypatch, said):
    """A validating job whose predicted bytes are the device's to the
    byte: the pre-flight counts the same with and without
    ``validation_files`` and lets it start; the holder keeps the sweep
    or streams it by what is left, and the job ends either way."""
    _one_device(monkeypatch)
    cfg = _cfg(tmp_path, host_threads=4)
    (tmp_path / "bare").mkdir()
    bare = _cfg(tmp_path / "bare", host_threads=4, validation_files=())
    total = mem.plan(cfg, "train")["total_bytes"]
    assert total == mem.plan(bare, "train")["total_bytes"]
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, str(total))
    p = mem.plan(cfg, "train")
    assert p["verdict"] == "FITS" and p[KEY] == 0
    mem.preflight_capacity(cfg, "train")
    train_mod.train(cfg)
    assert _counters(cfg)["validation/sweeps"] == 3
    assert len(said) == 1
    assert _settled() == []
