"""The host loop's wall as a partition of leaf phases (ISSUE 37).

Between a run's first step and its last, every instant of the thread
that drives ``StepLoop.step`` lies in exactly one leaf span or in the
residue, and the residue is counted: ``train/loop_seconds`` = the sum of
the leaves' counters (``telemetry.LOOP_LEAVES``, read off the one list
``ANATOMY_PHASES``) + ``train/loop_unnamed_seconds`` in every snapshot.
A step whose wall reaches ``train.SLOW_STEP_SECONDS`` says where it was
slow, and fmstat's rate divides by the loop's wall."""

import os
import time

import numpy as np
import pytest

from fast_tffm_tpu import train as train_mod
from fast_tffm_tpu.data import pipeline
from fast_tffm_tpu.obs.attribution import attribution, render, summarize
from fast_tffm_tpu.obs.sink import read_events
from fast_tffm_tpu.obs.telemetry import (ANATOMY_PHASES, FEED_PLACE,
                                         LOOP_LEAVES, LOOP_UNNAMED,
                                         RunTelemetry, loop_partition)

from tests.test_health_trace import _train_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LEAF_SPANS = {name for p in ANATOMY_PHASES.values() if p.leaf
              for name in p.spans}
# time.time() stamps a span's start and perf_counter its length
CLOCKS = 2e-5


@pytest.fixture(scope="module")
def phased_run(tmp_path_factory):
    """2 epochs of 4 steps with validation, a loss line and a flush
    every 2, spans on. A CPU's first compile and its validation sweep
    are no stall: the constant stands at 30 s for this run."""
    d = tmp_path_factory.mktemp("phases")
    cfg = _train_cfg(d, np.random.default_rng(0), trace_spans=True,
                     log_steps=2)
    was, train_mod.SLOW_STEP_SECONDS = train_mod.SLOW_STEP_SECONDS, 30.0
    try:
        train_mod.train(cfg)
    finally:
        train_mod.SLOW_STEP_SECONDS = was
    path = cfg.model_file + ".metrics.jsonl"
    return path, list(read_events(path))


def _spans(events, *names):
    return [e for e in events if e["event"] == "span"
            and (not names or e["name"] in names)]


def _loop_thread(events):
    (tid,) = {s["tid"] for s in _spans(events, "train/step")}
    return tid


# ---- the identity --------------------------------------------------------

def test_every_snapshot_splits_the_loops_wall(phased_run):
    _, events = phased_run
    snaps = [e["counters"] for e in events if e["event"] == "metrics"
             and "train/loop_seconds" in e["counters"]]
    # 4 step flushes, 2 barriers, the teardown's, the close
    assert len(snaps) >= 8
    assert snaps[-1] is [e for e in events
                         if e["event"] == "metrics"][-1]["counters"]
    walls = [c["train/loop_seconds"] for c in snaps]
    assert walls == sorted(walls) and walls[-1] > 0
    for c in snaps:
        assert set(LOOP_LEAVES) <= set(c)
        named = sum(c[name] for name in LOOP_LEAVES)
        assert c["train/loop_seconds"] == pytest.approx(
            named + c[LOOP_UNNAMED], rel=1e-12, abs=1e-12)
        # a nested pair of leaves would count an interval twice and
        # drive this under zero
        assert c[LOOP_UNNAMED] >= 0.0
    assert snaps[-1][LOOP_UNNAMED] < snaps[-1]["train/loop_seconds"]
    assert snaps[-1]["train/slow_steps"] == 0


def test_a_nested_pair_of_leaves_would_show_as_a_negative_residue():
    leaves = dict.fromkeys(LOOP_LEAVES, 0.0)
    leaves.update({"train/bookkeeping_seconds": 0.5,
                   "train/loss_sync_seconds": 0.4,
                   "train/loop_seconds": 1.0})
    assert loop_partition(leaves)[LOOP_UNNAMED] == pytest.approx(0.1)
    leaves["train/log_line_seconds"] = 0.4      # "inside" bookkeeping
    assert loop_partition(leaves)[LOOP_UNNAMED] < 0


def test_the_loops_wall_stops_with_the_loop(phased_run):
    """What follows the last step (final save, export, the teardown's
    flush) is no part of the wall, and the teardown's
    ``obs/barrier_flush`` is not counted as a leaf of it."""
    _, events = phased_run
    metrics = [e for e in events if e["event"] == "metrics"]
    assert (metrics[-1]["counters"]["train/loop_seconds"]
            == metrics[-2]["counters"]["train/loop_seconds"])
    flushes = sorted(_spans(events, "obs/barrier_flush"),
                     key=lambda s: s["ts"])
    assert len(flushes) == 3                    # two epochs, one teardown
    assert metrics[-1]["counters"]["obs/barrier_flush_seconds"] == (
        pytest.approx(sum(s["dur"] for s in flushes[:2]), rel=1e-9))


# ---- the spans -----------------------------------------------------------

def test_no_two_leaves_of_the_loop_thread_overlap(phased_run):
    _, events = phased_run
    tid = _loop_thread(events)
    leaves = sorted((s for s in _spans(events, *LEAF_SPANS)
                     if s["tid"] == tid), key=lambda s: s["ts"])
    assert {s["name"] for s in leaves} >= {
        "train/bookkeeping", "train/batch_checks", "train/log_line",
        "train/barrier_reports", "pipeline/open", "pipeline/first_batch",
        "obs/barrier_flush", "train/loss_sync",
        # the sweep's leaves (ISSUE 44); train/validation encloses them
        "validation/open", "validation/first_batch",
        "validation/score_dispatch", "validation/drain", "validation/auc"}
    assert "train/validation" not in LEAF_SPANS
    for a, b in zip(leaves, leaves[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + CLOCKS, (a, b)
    # every leaf says which step or epoch it belongs to
    assert all("step" in s or "epoch" in s for s in leaves)
    # and no other thread counts into a leaf's counter
    assert not [s for s in _spans(events, *LEAF_SPANS) if s["tid"] != tid]


def test_feed_place_is_off_the_loop_and_on_no_leaf_list(phased_run):
    """The feed's placement (ISSUE 46) runs on a thread of its own: its
    span is on that thread's track and on no leaf list, its seconds
    count beside the partition and not into it, every step's batch
    reaches the loop placed, and ``train/h2d`` (placement ON the loop's
    thread) stays 0 in a run whose feed places."""
    _, events = phased_run
    place = _spans(events, "feed/place")
    c = [e for e in events if e["event"] == "metrics"][-1]["counters"]
    # the sweeps' feed places under the same name, on an ``fm-place`` of
    # its own and into a counter of its own (ISSUE 51): the first
    # sweep's batches, which the later ones score again (ISSUE 53)
    assert c["validation/sweeps"] == 1 + c["validation/resident_sweeps"]
    assert len(place) == 8 + c["validation/batches"] / c["validation/sweeps"]
    assert {s["tid"] for s in place} == {"fm-place"}
    assert _loop_thread(events) != "fm-place"
    assert "feed/place" not in LEAF_SPANS
    assert not set(FEED_PLACE) & set(LOOP_LEAVES)
    assert not _spans(events, "train/h2d", "train/encode")
    assert c["train/placed_ahead"] == c["train/steps"] == 8
    assert c["train/h2d_seconds"] == c["train/encode_seconds"] == 0
    assert c["train/place_seconds"] > 0 < c["validation/place_seconds"]
    assert (c["train/place_seconds"] + c["validation/place_seconds"]
            == pytest.approx(sum(s["dur"] for s in place), rel=1e-9))
    # the emitting thread's own seconds, a batch: counted where it emits
    emit = _spans(events, "pipeline/emit")
    assert len(emit) == 8 and _loop_thread(events) not in {
        s["tid"] for s in emit}
    assert c["pipeline/emit_seconds"] == pytest.approx(
        sum(s["dur"] for s in emit), rel=1e-9)


def test_the_loop_threads_spans_nest(phased_run):
    """Enclosures too: a span of the loop's thread lies inside the one
    open when it began or after it (the epoch barrier ends before the
    bookkeeping of the step that ends it begins), which is what lets a
    reader of a trace take the innermost span open at each instant."""
    _, events = phased_run
    tid = _loop_thread(events)
    stack = []
    for s in sorted((s for s in _spans(events) if s["tid"] == tid),
                    key=lambda s: (s["ts"], -s["dur"])):
        while stack and stack[-1] <= s["ts"] + CLOCKS:
            stack.pop()
        end = s["ts"] + s["dur"]
        assert not stack or end <= stack[-1] + CLOCKS, s
        stack.append(end)


def test_the_barriers_parts_lie_inside_the_barrier(phased_run):
    _, events = phased_run
    barrier = min(_spans(events, "train/epoch_barrier"),
                  key=lambda s: s["ts"])
    lo, hi = barrier["ts"], barrier["ts"] + barrier["dur"]
    parts = ("train/barrier_reports", "train/validation",
             "obs/barrier_flush", "pipeline/open", "pipeline/first_batch")
    inside = [s for s in _spans(events, *parts)
              if lo - CLOCKS <= s["ts"] and s["ts"] + s["dur"] <= hi + CLOCKS]
    # an enclosure sorts ahead of what it holds; none of these holds another
    assert [s["name"] for s in sorted(inside, key=lambda s: s["ts"])] == list(
        parts)
    # what is left of the barrier is the next epoch's first step
    first = [s for s in _spans(events, "train/step") if s["step"] == 5][0]
    assert lo <= first["ts"] and first["ts"] + first["dur"] <= hi + CLOCKS


def test_input_wait_counts_both_names_and_first_batch_its_own(phased_run):
    _, events = phased_run
    c = [e for e in events if e["event"] == "metrics"][-1]["counters"]
    first = _spans(events, "pipeline/first_batch")
    assert [s["step"] for s in first] == [1, 5]
    assert c["train/input_wait_seconds"] == pytest.approx(
        sum(s["dur"] for s in _spans(events, "train/input_wait")
            + first), rel=1e-9)
    assert c["pipeline/first_batch_seconds"] == pytest.approx(
        sum(s["dur"] for s in first), rel=1e-9)


def test_the_readme_lists_every_leaf_of_the_one_list():
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as fh:
        readme = fh.read()
    for p in ANATOMY_PHASES.values():
        if p.leaf:
            assert f"[`{p.counter}`" in readme, p.counter
            for name in p.spans:
                assert f"`{name}`" in readme, name
    for name in ("train/loop_seconds", LOOP_UNNAMED, "slow_step",
                 "train/slow_steps", "pipeline/first_batch_seconds"):
        assert name in readme, name


# ---- a slow step says where it was slow ------------------------------------

@pytest.mark.parametrize("stage,kw", [
    # the feed places ahead: its last stage is what the loop waits for
    ("place_ahead", {}),
    # the loop places for itself (admit): the prefetch is
    ("prefetch", {"vocab_mode": "admit", "hash_feature_id": True}),
])
def test_a_stalled_next_yields_one_slow_step_naming_input_wait(
        tmp_path, monkeypatch, capsys, stage, kw):
    cfg = _train_cfg(tmp_path, np.random.default_rng(1), epoch_num=1,
                     validation_files=(), **kw)
    real = getattr(pipeline, stage)
    # The constant comes down around the stall only: a CPU's first
    # compile (step 1) is slow too, and is not what is tested.
    monkeypatch.setattr(train_mod, "SLOW_STEP_SECONDS", 1e9)

    def stalled(it, *a, **kw):
        for i, batch in enumerate(real(it, *a, **kw)):
            if i == 2:
                monkeypatch.setattr(train_mod, "SLOW_STEP_SECONDS", 0.25)
                time.sleep(0.6)
            elif i == 3:
                monkeypatch.setattr(train_mod, "SLOW_STEP_SECONDS", 1e9)
            yield batch

    monkeypatch.setattr(pipeline, stage, stalled)
    train_mod.train(cfg)
    path = cfg.model_file + ".metrics.jsonl"
    events = list(read_events(path))
    (slow,) = [e for e in events if e["event"] == "slow_step"]
    assert slow["step"] == 3 and slow["what"] == "step"
    assert 0.6 <= slow["wall"] < 5.0
    largest, seconds = next(iter(slow["phases"].items()))
    assert largest == "train/input_wait_seconds" and seconds >= 0.6
    assert set(slow["phases"]) <= set(LOOP_LEAVES) | {LOOP_UNNAMED}
    last = [e for e in events if e["event"] == "metrics"][-1]["counters"]
    assert last["train/slow_steps"] == 1
    # fmstat lists it under the health line
    from tools.fmstat import main as fmstat_main
    assert fmstat_main([path]) == 0
    out = capsys.readouterr().out
    assert "slow step at step 3" in out
    assert "train/input_wait_seconds" in out.split("slow step at step 3")[1]


def test_a_steady_run_yields_no_slow_step(phased_run):
    _, events = phased_run
    assert not [e for e in events if e["event"] == "slow_step"]


def test_slow_step_differences_against_the_last_flush(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={}, flush_steps=1)
    tel.loop_start()
    tel.count("train/input_wait_seconds", 0.5)      # before the flush
    tel.maybe_flush(1)
    tel.count("train/input_wait_seconds", 2.0)
    tel.count("train/dispatch_seconds", 0.01)
    tel.slow_step(2, 2.02, epoch=0)
    tel.slow_step(9, 1.5, what="barrier")
    tel.close()
    step, barrier = [e for e in read_events(path)
                     if e["event"] == "slow_step"]
    assert list(step["phases"])[:2] == ["train/input_wait_seconds",
                                        "train/dispatch_seconds"]
    assert step["phases"]["train/input_wait_seconds"] == pytest.approx(2.0)
    assert (step["step"], step["epoch"], step["wall"]) == (2, 0, 2.02)
    assert (barrier["what"], barrier["step"]) == ("barrier", 9)
    last = [e for e in read_events(path) if e["event"] == "metrics"][-1]
    assert last["counters"]["train/slow_steps"] == 2


# ---- the contract the spans keep -----------------------------------------

@pytest.mark.parametrize("kw,absent", [
    # the feed places: encode and h2d open on no thread, feed/place does
    ({}, {"train/encode", "train/h2d"}),
    # the loop places for itself (admit): today's sequence
    ({"vocab_mode": "admit", "hash_feature_id": True}, {"feed/place"}),
])
def test_zero_midstream_fetches_with_every_new_span_on(tmp_path, monkeypatch,
                                                       kw, absent):
    """Spans, their JSONL events, the residue and the loop's clock are
    host values: with all of it on at a flush every step, bulk_fetch
    still runs only at the two epoch barriers."""
    import fast_tffm_tpu.utils.fetch as fetch
    calls = []
    real = fetch.bulk_fetch

    def counting(pairs, consume):
        calls.append(len(pairs))
        return real(pairs, consume)

    monkeypatch.setattr(fetch, "bulk_fetch", counting)
    monkeypatch.setattr(train_mod, "SLOW_STEP_SECONDS", 0.0)  # every step
    cfg = _train_cfg(tmp_path, np.random.default_rng(2), trace_spans=True,
                     metrics_flush_steps=1, **kw)
    train_mod.train(cfg)
    assert calls == [5, 5]      # loss x4 + AUC, one call a barrier
    events = list(read_events(cfg.model_file + ".metrics.jsonl"))
    names = {s["name"] for s in _spans(events)}
    assert names >= (LEAF_SPANS | {"feed/place"}) - absent - {
        "train/step_flags", "stream/step_flags", "train/checkpoint_pause",
        "checkpoint/publish", "train/summary_flush", "train/loss_sync",
        "train/log_line", "validation/lockstep"}
    assert not names & absent
    assert len([e for e in events if e["event"] == "slow_step"]) >= 8


# ---- fmstat ----------------------------------------------------------------

def test_fmstat_rate_is_examples_over_the_loops_wall(phased_run, capsys):
    path, events = phased_run
    summary = summarize([path])
    c = [e for e in events if e["event"] == "metrics"][-1]["counters"]
    att = attribution(summary)
    assert c["train/examples"] == 256
    assert att["examples_per_sec"] == pytest.approx(
        256 / c["train/loop_seconds"])
    assert att["loop_wall_seconds"] == c["train/loop_seconds"]
    # the step histogram's per-epoch anchor leaves the barriers out
    assert att["loop_seconds"] < c["train/loop_seconds"]
    assert att["examples_per_sec"] < att["loop_examples_per_sec"]
    # a stream from before the counter reads as it did
    old = dict(summary, counters={k: v for k, v in summary["counters"].items()
                                  if not k.startswith("train/loop_")})
    was = attribution(old)
    assert was["loop_wall_seconds"] is None
    assert was["examples_per_sec"] == pytest.approx(
        256 / (was["loop_seconds"] + was["pause_seconds"]))
    # one process gets the phase table a cluster gets, unnamed included
    out = render(summary)
    assert "EFFICIENCY (step anatomy):" in out
    assert "no cross-rank wait; largest phase:" in out
    table = out.split("EFFICIENCY (step anatomy):")[1]
    for label in ("dispatch", "bookkeeping", "pipeline open", "unnamed"):
        assert f"      {label} " in table, label
