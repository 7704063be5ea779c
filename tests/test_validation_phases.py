"""A validation sweep inside ``train()`` (ISSUE 44): its wall on the
loop's thread is a partition of six leaves under the enclosure
``train/validation``, it counts its sweeps, batches and examples, its
data plane counts under names of its own, a barrier that holds a
sweep is no slow step for that, and the placement of its batches
(ISSUE 51) is the feed's thread's and no leaf. Since ISSUE 53 the job's
second sweep scores what the first placed (``swept``'s "resident"
case); a job whose sweep may not stay runs the plane at every sweep as
before (its "streamed" case: no budget)."""

import time

import numpy as np
import pytest

from fast_tffm_tpu import train as train_mod
from fast_tffm_tpu.data.pipeline import TRAIN_PLANE, VALIDATION_PLANE
from fast_tffm_tpu.obs.sink import read_events
from fast_tffm_tpu.obs.telemetry import (ANATOMY_PHASES, LOOP_LEAVES,
                                         LOOP_UNNAMED)

from tests.test_health_trace import _train_cfg
from tests.test_e2e import make_dataset

LEAVES = ("validation/open", "validation/first_batch",
          "validation/input_wait", "validation/score_dispatch",
          "validation/drain", "validation/auc")
COUNTS = ("validation/sweeps", "validation/batches", "validation/examples")
VAL_LINES, BATCH, EPOCHS = 1600, 32, 2
CLOCKS = 2e-5


def _events(cfg):
    return list(read_events(cfg.model_file + ".metrics.jsonl"))


def _last_counters(events):
    return [e for e in events if e["event"] == "metrics"][-1]["counters"]


def _spans(events, *names):
    return [e for e in events if e["event"] == "span"
            and e["name"] in names]


def _sweeping_run(d, **kw):
    """2 epochs of 4 steps, each followed by a sweep of 50 batches."""
    cfg = _train_cfg(d, np.random.default_rng(0), epoch_num=EPOCHS,
                     batch_size=BATCH, **kw)
    make_dataset(d / "val.txt", VAL_LINES, np.random.default_rng(1))
    was, train_mod.SLOW_STEP_SECONDS = train_mod.SLOW_STEP_SECONDS, 30.0
    try:
        train_mod.train(cfg)
    finally:
        train_mod.SLOW_STEP_SECONDS = was
    return _events(cfg)


def _chip_paced(monkeypatch, seconds):
    """A score call held to what one takes on the chip and more (1.44
    ms there; this machine's dispatch alone is a third of that, beside
    which the writing of a sweep's span events is no residue but a
    share)."""
    real = train_mod.make_batch_scorer

    def chip_paced(*a, **k):
        score = real(*a, **k)

        def paced(table, args):
            time.sleep(seconds)
            return score(table, args)
        return paced

    monkeypatch.setattr(train_mod, "make_batch_scorer", chip_paced)


@pytest.fixture(scope="module", params=["streamed", "resident"])
def swept(request, tmp_path_factory):
    """The run's events, and how many of its sweeps were served from
    the device."""
    with pytest.MonkeyPatch.context() as mp:
        _chip_paced(mp, 0.005)
        if request.param == "streamed":
            from fast_tffm_tpu.obs import memory
            mp.setattr(memory, "RESIDENT_SWEEP_UNMEASURED_BYTES", 0)
        events = _sweeping_run(tmp_path_factory.mktemp("sweep"),
                               trace_spans=True)
    c = _last_counters(events)
    assert c["validation/resident_sweeps"] == (
        EPOCHS - 1 if request.param == "resident" else 0)
    return events


def test_the_leaves_are_leaves_of_the_one_list_and_the_enclosure_is_none():
    by_span = {name: p for p in ANATOMY_PHASES.values() for name in p.spans}
    for name in LEAVES + ("validation/lockstep",):
        assert by_span[name].leaf, name
        assert by_span[name].counter == name + "_seconds"
        assert by_span[name].counter in LOOP_LEAVES
    assert not by_span["train/validation"].leaf
    assert "train/validation_seconds" not in LOOP_LEAVES


def test_the_leaves_partition_the_sweeps_wall(swept):
    sweeps = sorted(_spans(swept, "train/validation"), key=lambda s: s["ts"])
    assert len(sweeps) == EPOCHS
    (tid,) = {s["tid"] for s in sweeps}
    leaves = sorted(_spans(swept, *LEAVES), key=lambda s: s["ts"])
    assert {s["tid"] for s in leaves} == {tid}
    assert {s["name"] for s in leaves} == set(LEAVES)
    # no nested pair: one leaf ends before the next begins
    for a, b in zip(leaves, leaves[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + CLOCKS, (a, b)
    for sweep in sweeps:
        lo, hi = sweep["ts"], sweep["ts"] + sweep["dur"]
        inside = [s for s in leaves if lo - CLOCKS <= s["ts"]
                  and s["ts"] + s["dur"] <= hi + CLOCKS]
        names = [s["name"] for s in inside]
        n_batches = -(-VAL_LINES // BATCH)
        assert names[:2] == ["validation/open", "validation/first_batch"]
        assert names[-2:] == ["validation/drain", "validation/auc"]
        assert names.count("validation/score_dispatch") == n_batches
        # every later next(), the one that finds the plane exhausted too
        assert names.count("validation/input_wait") == n_batches
        # what is left is the writing of these 102 events, which a
        # span does once its clock has stopped (the counted run below
        # holds the residue to 1%): some 3 ms, the same for a sweep
        # served from the device, whose wall is the calls' alone
        named = sum(s["dur"] for s in inside)
        assert 0 <= sweep["dur"] - named < 0.1 * sweep["dur"], (sweep, named)
    assert all("step" in s for s in leaves)
    # the counters say what the spans say
    c = _last_counters(swept)
    assert c["train/validation_seconds"] == pytest.approx(
        sum(s["dur"] for s in sweeps), rel=1e-9)
    for name in LEAVES:
        assert c[name + "_seconds"] == pytest.approx(
            sum(s["dur"] for s in leaves if s["name"] == name), rel=1e-9)
    # and the loop's residue is still a residue
    assert 0 <= c[LOOP_UNNAMED] < c["train/loop_seconds"]


def test_the_sweeps_placement_is_off_the_loop_and_on_no_leaf_list(swept):
    """Since the sweeps read from a feed of the job's (ISSUE 51),
    ``validation/score_dispatch`` holds the call alone: a held-out
    batch is placed under ``feed/place`` [``validation/place_seconds``]
    on the feed's own thread, once a batch the plane hands out (a sweep
    served from the device places nothing: ISSUE 53), and neither the
    span nor its counter is a leaf's (tests/test_sweep_feed.py has the
    rest)."""
    (tid,) = {s["tid"] for s in _spans(swept, "train/validation")}
    place = _spans(swept, "feed/place")
    assert {s["tid"] for s in place} == {"fm-place"} != {tid}
    c = _last_counters(swept)
    streamed = EPOCHS - c["validation/resident_sweeps"]
    assert len(place) == c["train/steps"] + (
        c["validation/batches"] / EPOCHS * streamed)
    assert c["validation/place_seconds"] > 0
    assert c["validation/place_seconds"] + c["train/place_seconds"] == (
        pytest.approx(sum(s["dur"] for s in place), rel=1e-9))
    by_span = {name for p in ANATOMY_PHASES.values() for name in p.spans}
    assert "feed/place" not in by_span
    assert "validation/place_seconds" not in LOOP_LEAVES
    # the sweeps the PLANE fed ahead: every one behind the first it made
    assert c["validation_plane/epochs_fed_ahead"] == streamed - 1


def test_no_residue_over_one_percent_of_a_sweep(tmp_path, monkeypatch):
    """Counters alone, as a run without ``trace_spans`` keeps them:
    the sweeps' wall under no leaf is under 1% of it. What lies under
    none is the making of each span before its clock starts, some
    microseconds; a score call here is held to the 5 ms one takes on
    the chip and more, where this machine's takes 2."""
    _chip_paced(monkeypatch, 0.005)
    c = _last_counters(_sweeping_run(tmp_path))
    named = sum(c[name + "_seconds"] for name in LEAVES)
    assert 0 <= c["train/validation_seconds"] - named < (
        0.01 * c["train/validation_seconds"]), (c, named)


def test_the_three_counters_count_sweeps_batches_and_examples(swept):
    c = _last_counters(swept)
    assert c["validation/sweeps"] == EPOCHS == c["train/epochs"]
    assert c["validation/batches"] == EPOCHS * -(-VAL_LINES // BATCH)
    assert c["validation/examples"] == EPOCHS * VAL_LINES


def test_the_two_planes_count_apart(swept):
    """``pipeline/*`` is the training plane's alone: what reads it
    (cell_fill, host_build_s_per_batch, fmstat's rows) sees no
    validation batch."""
    c = _last_counters(swept)
    assert (TRAIN_PLANE, VALIDATION_PLANE) == ("pipeline",
                                               "validation_plane")
    assert c["pipeline/batches"] == c["train/steps"] == 8
    assert c["pipeline/examples"] == c["train/examples"] == 256
    assert c["validation_plane/batches"] == c["validation/batches"]
    assert c["validation_plane/examples"] == c["validation/examples"]
    for name in ("feature_nnz", "feature_slots", "build_seconds"):
        assert c["pipeline/" + name] > 0
        assert c["validation_plane/" + name] > 0
    # a sweep's cells are many times an epoch's: they would have shown
    assert c["validation_plane/feature_slots"] > 5 * c[
        "pipeline/feature_slots"]
    # the train step rides the host unique's slots, the scorer none or
    # its own: neither plane's count holds the other's
    assert c["pipeline/uniq_slots"] > 0


def test_parallel_builders_count_their_seconds_under_their_plane(tmp_path):
    cfg = _train_cfg(tmp_path, np.random.default_rng(3), epoch_num=1,
                     host_threads=2)
    train_mod.train(cfg)
    c = _last_counters(_events(cfg))
    assert c["pipeline/worker_build_seconds"] > 0
    assert c["validation_plane/worker_build_seconds"] > 0
    assert c["pipeline/batches"] == 4 and c["validation_plane/batches"] == 2


def test_a_run_without_validation_files_emits_none_of_it(tmp_path):
    cfg = _train_cfg(tmp_path, np.random.default_rng(2), trace_spans=True,
                     validation_files=())
    train_mod.train(cfg)
    events = _events(cfg)
    assert not _spans(events, "train/validation", "validation/lockstep",
                      *LEAVES)
    for c in (e["counters"] for e in events if e["event"] == "metrics"):
        assert not [k for k in c if k in COUNTS
                    or k.startswith("validation_plane/")]
        # the partition's counters are all there, at nothing
        assert all(c.get(name + "_seconds", 0) == 0 for name in LEAVES)
    assert not [e for e in events if e["event"] == "scalars"
                and "validation/auc" in str(e)]


def test_a_barrier_that_holds_a_sweep_is_no_slow_step_for_that(
        tmp_path, monkeypatch):
    """The sweep sleeps 0.4 s; the barrier around it is slow only by
    what it holds beside the sweep."""
    cfg = _train_cfg(tmp_path, np.random.default_rng(4), epoch_num=2)
    real = train_mod.evaluate

    def slow_sweep(*a, **k):
        time.sleep(0.4)
        return real(*a, **k)

    monkeypatch.setattr(train_mod, "evaluate", slow_sweep)
    monkeypatch.setattr(train_mod, "SLOW_STEP_SECONDS", 0.35)
    train_mod.train(cfg)
    events = _events(cfg)
    c = _last_counters(events)
    assert c["train/validation_seconds"] >= 0.8
    assert c["train/epoch_barrier_seconds"] >= 0.4  # it holds the first sweep
    assert not [e for e in events if e["event"] == "slow_step"
                and e["what"] == "barrier"]
    # and a barrier slow beside its sweep still says so
    (tmp_path / "b").mkdir()
    cfg2 = _train_cfg(tmp_path / "b", np.random.default_rng(4), epoch_num=2)
    real_release = train_mod.EpochFeed.release

    def slow_release(self, epoch):  # the feed is the job's: no epoch opens
        time.sleep(0.4)             # one; the loop tells it to go on
        return real_release(self, epoch)

    monkeypatch.setattr(train_mod, "evaluate", real)
    monkeypatch.setattr(train_mod.EpochFeed, "release", slow_release)
    train_mod.train(cfg2)
    slow = [e for e in _events(cfg2) if e["event"] == "slow_step"
            and e["what"] == "barrier"]
    assert len(slow) == 1 and 0.4 <= slow[0]["wall"] < 0.4 + c[
        "train/validation_seconds"]


def test_a_sweep_inside_a_callers_own_leaf_counts_no_leaf(tmp_path):
    """``phases=False`` (the stream loop's publish, the pass after the
    loop has stopped): the spans are there, the sweep is counted, and
    no second leaf counts the interval."""
    from fast_tffm_tpu.models.fm import init_table
    from fast_tffm_tpu.obs.telemetry import activate, make_telemetry
    cfg = _train_cfg(tmp_path, np.random.default_rng(5), trace_spans=True)
    tel = make_telemetry(cfg, "train")
    with activate(tel):
        tel.loop_start()
        auc, n = train_mod.evaluate(cfg, init_table(cfg),
                                    cfg.validation_files, phases=False)
        tel.maybe_flush(1)
        c = dict(tel.registry.snapshot()["counters"])
        auc2, n2 = train_mod.evaluate(cfg, init_table(cfg),
                                      cfg.validation_files)
        tel.close()
    assert (auc, n) == (auc2, n2) and n == 64
    assert c["validation/sweeps"] == 1 and c["validation/examples"] == 64
    assert all(c[name + "_seconds"] == 0 for name in LEAVES)
    events = _events(cfg)
    assert {s["name"] for s in _spans(events, *LEAVES)} == set(LEAVES)
    last = _last_counters(events)
    assert last["validation/sweeps"] == 2
    assert all(last[name + "_seconds"] > 0 for name in LEAVES)
