"""``readers/feed_idle_by_stage.py`` and the fourteen per-layer metrics
of ISSUE 54: of a chip's idle instants while the loop waits for its
feed, each goes to the stage nearest the loop whose work span is open,
or to none, and the five shares sum to ``loop_idle_by_phase``'s shares
of ``train/input_wait`` and ``pipeline/first_batch``. On synthetic
``Trace`` objects and on the traces recorded before the scanner had a
span; the nine counter metrics on a tiny run's stream."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmarks import trace_reduce
from benchmarks.readers import (feed_idle_by_stage, loop_idle_by_phase,
                                telemetry_window)
from benchmarks.trace_reduce import DeviceTrace, Op, Trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TESTDATA = os.path.join(REPO, "benchmarks", "testdata")
PHASED = os.path.join(TESTDATA, "tiny_train_tpu_phased.xplane.pb")
X4 = os.path.join(TESTDATA, "tiny_train_tpu_x4.xplane.pb")
RING_CELLS = ["fm16-train-zipf", "ffm4-train-zipf", "fm16x4-train-zipf",
              "fm8-train-bags", "fm3-train-bags", "fm16-train-eval",
              "fm16-train-save"]
# name -> (counter, over): nine data files on telemetry_window
COUNTED = {
    "scan_s_per_batch": ("pipeline/scan_seconds", "pipeline/batches"),
    "scan_read_s_per_batch": ("pipeline/scan_read_seconds",
                              "pipeline/batches"),
    "group_wait_s_per_batch": ("pipeline/fm_scan_get_wait_seconds",
                               "pipeline/batches"),
    "ring_wait_s_per_batch": ("pipeline/ring_wait_seconds",
                              "pipeline/batches"),
    "build_idle_s_per_batch": ("pipeline/worker_idle_seconds",
                               "pipeline/batches"),
    "scan_blocked_share": ("pipeline/fm_scan_put_wait_seconds", "wall_pct"),
    "build_blocked_share": ("pipeline/prefetch_put_wait_seconds",
                            "wall_pct"),
    "place_starved_share": ("pipeline/prefetch_get_wait_seconds",
                            "wall_pct"),
    "feed_stall_share": ("train/feed_stall_seconds", "wall_pct"),
}
# name -> stage: five on the new reader
TRACED = {"idle_waiting_on_place": "place", "idle_waiting_on_emit": "emit",
          "idle_waiting_on_build": "build", "idle_waiting_on_scan": "scan",
          "idle_waiting_on_no_stage": "none"}
NEW_METRICS = list(COUNTED) + list(TRACED)


def _chip(name, *busy):
    return DeviceTrace(name, [Op("op", a, b, {}) for a, b in busy], [])


def _line(line, *spans):
    return [(line, Op(name, a, b, {})) for name, a, b in spans]


def _feed_trace(wait="train/input_wait", chips=None):
    """A window of 10 s. The loop waits for its feed from 1 to 9 and
    syncs a loss from 9 to 9.5; the feed's threads work as the five
    rules need them to: place 1-2, emit 1.5-3, the coordinator at the
    ring's head 2.5-4, a builder 3.5-5, the scanner 4-7 with its read
    inside, nobody 7-9, and the placer again under the loss sync."""
    host = (
        _line("loop", ("train/step", 0.0, 1.0), (wait, 1.0, 9.0),
              ("train/loss_sync", 9.0, 9.5), ("train/step", 9.5, 10.0))
        + _line("fm-place", ("feed/place", 1.0, 2.0),
                ("feed/place", 9.0, 9.5))
        + _line("prefetch", ("pipeline/build", 0.5, 9.8),
                ("pipeline/emit", 1.5, 3.0), ("pipeline/ring_wait", 2.5, 4.0))
        + _line("fm-build-0", ("pipeline/build_worker", 3.5, 5.0))
        + _line("fm-scan", ("pipeline/scan", 4.0, 7.0),
                ("pipeline/scan_read", 4.5, 5.0)))
    chips = chips or [_chip("/device:TPU:0", (0, 1), (9.5, 10))]
    return Trace(chips, host, 0.0, 10.0)


# ---- the five rules --------------------------------------------------------

@pytest.mark.parametrize("stage,share", [
    ("place", 10.0),    # 1-2: feed/place open, whatever else is
    ("emit", 10.0),     # 2-3: of 1.5-3 the placer took the first half
    ("build", 20.0),    # 3-4 the ring's head waited for, 4-5 a builder
    ("scan", 20.0),     # 5-7: of 4-7 the builder took the first second
    ("none", 20.0),     # 7-9: every thread of the feed between spans
])
def test_an_idle_instant_goes_to_the_stage_nearest_the_loop(stage, share):
    trace = _feed_trace()
    assert feed_idle_by_stage.read({"trace": trace}, stage) == pytest.approx(
        share)
    # the idle under the loss sync is no wait for the feed, though the
    # placer works there; the enclosing pipeline/build is no stage
    assert sum(feed_idle_by_stage.idle_by_stage(trace).values()
               ) == pytest.approx(8.0)


def test_an_epochs_first_wait_counts_like_any_other():
    first = feed_idle_by_stage.idle_by_stage(
        _feed_trace(wait="pipeline/first_batch"))
    assert first == pytest.approx(
        feed_idle_by_stage.idle_by_stage(_feed_trace()))
    assert first == pytest.approx({"place": 1.0, "emit": 1.0, "build": 2.0,
                                   "scan": 2.0, "none": 2.0})


def test_only_idle_instants_count_and_the_chips_are_averaged():
    """Chip 0 idles 1 to 9.5, chip 1 only 2.5 to 6: it is busy while
    the placer works and while nobody does."""
    trace = _feed_trace(chips=[
        _chip("/device:TPU:0", (0, 1), (9.5, 10)),
        _chip("/device:TPU:1", (0, 2.5), (6, 10))])
    got = feed_idle_by_stage.idle_by_stage(trace)
    assert got == pytest.approx({"place": 0.5, "emit": 0.75, "build": 2.0,
                                 "scan": 1.5, "none": 1.0})


@pytest.mark.parametrize("trace", [
    _feed_trace(),
    _feed_trace(wait="pipeline/first_batch"),
    _feed_trace(chips=[_chip("/device:TPU:0", (0, 1.2), (9.5, 10)),
                       _chip("/device:TPU:1", (0, 2.5), (6, 6.5), (8, 10)),
                       _chip("/device:TPU:2", (0, 10))]),
], ids=["one_chip", "first_batch", "three_chips"])
def test_the_five_sum_to_loop_idle_by_phases_two_shares(trace):
    ctx = {"trace": trace}
    five = [feed_idle_by_stage.read(ctx, stage)
            for stage in ("place", "emit", "build", "scan", "none")]
    assert all(v is not None and v >= 0 for v in five)
    two = [loop_idle_by_phase.read(ctx, phase) or 0.0
           for phase in feed_idle_by_stage.WAITS]
    assert sum(five) == pytest.approx(sum(two), abs=1e-9)
    assert sum(two) > 0


def test_a_span_inside_the_wait_keeps_its_part():
    """What the loop's thread does inside its wait under a span of its
    own (a flush from 7 to 8) is that span's, here as in
    ``loop_idle_by_phase``."""
    base = _feed_trace()
    host = (_line("loop", ("train/step", 0.0, 1.0),
                  ("train/input_wait", 1.0, 9.0), ("obs/flush", 7.0, 8.0),
                  ("train/step", 9.5, 10.0))
            + [x for x in base.host if x[0] != "loop"])
    got = feed_idle_by_stage.idle_by_stage(
        Trace(base.devices, host, 0.0, 10.0))
    assert got["none"] == pytest.approx(1.0)
    assert got["scan"] == pytest.approx(2.0)


def test_a_trace_without_the_scanners_span_reads_nothing():
    """A program from before ISSUE 54 (the parent, which the driver
    runs this reader on too): nothing, and nothing raised."""
    old = _feed_trace()
    old = Trace(old.devices, [x for x in old.host
                              if not x[1].name.startswith("pipeline/scan")],
                0.0, 10.0)
    no_loop = Trace(old.devices, _line("w", ("pipeline/scan", 1.0, 2.0)),
                    0.0, 10.0)
    for trace in (old, no_loop, trace_reduce.reduce(PHASED),
                  trace_reduce.reduce(X4)):
        ctx = {"trace": trace}
        for stage in TRACED.values():
            assert feed_idle_by_stage.read(ctx, stage) is None
        assert ctx["feed_idle_by_stage"] is None


def test_the_table_is_printed_once_a_run(capsys):
    ctx = {"trace": _feed_trace()}
    for stage in TRACED.values():
        feed_idle_by_stage.read(ctx, stage)
    out = capsys.readouterr().out
    assert out.count("by the stage waited for") == 1
    assert ("place 10.000, emit 10.000, build 20.000, scan 20.000, "
            "none 20.000; sum 80.000") in out
    assert feed_idle_by_stage.read(ctx, "no-such-stage") is None


def test_it_imports_loop_idle_by_phases_functions_and_copies_none():
    src = open(feed_idle_by_stage.__file__).read()
    for name in ("loop_spans", "innermost", "idle_of"):
        assert f"def {name}" not in src and f"by_phase.{name}(" in src
    assert feed_idle_by_stage.by_phase is loop_idle_by_phase


# ---- the fourteen metric files ---------------------------------------------

@pytest.fixture(scope="module")
def ring_stream(tmp_path_factory):
    """Three epochs of four steps on the ring's route, a snapshot every
    two steps."""
    from fast_tffm_tpu.train import train
    from tests.test_health_trace import _train_cfg
    cfg = _train_cfg(tmp_path_factory.mktemp("ring"),
                     np.random.default_rng(0), epoch_num=3, log_steps=2,
                     validation_files=(), host_threads=2)
    train(cfg)
    return cfg.model_file + ".metrics.jsonl"


def _spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_file_says_what_its_entry_says(name):
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           name + ".json")) as fh:
        own = json.load(fh)
    entry = next(m for m in _spec()["per_layer"] if m["name"] == name)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        k: own[k] for k in entry if k != "workloads"}
    assert set(own) == set(entry) - {"workloads"} | {"reader", "args"}
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        "host parse + build (data/)", "train_examples_per_s_per_chip",
        "lower")
    if name in COUNTED:
        counter, over = COUNTED[name]
        assert own["reader"] == "telemetry_window"
        assert own["args"] == {"counter": counter, "over": over}
        assert entry["source"] == "program_counter"
        assert entry["unit"] == ("%" if over == "wall_pct" else "s/batch")
        # (a later PR may append the cells it adds, and nothing else)
        assert entry["workloads"][:len(RING_CELLS)] == RING_CELLS
    else:
        assert own["reader"] == "feed_idle_by_stage"
        assert own["args"] == {"stage": TRACED[name]}
        assert (entry["source"], entry["unit"]) == ("device_trace", "%")
        assert entry["workloads"][0] == "fm16x4-train-zipf"
    # the stream's source has its own leaves
    assert "fm16-stream-catchup" not in entry["workloads"]


def test_the_entries_stand_together_in_the_issues_order():
    """Present, side by side and in order, wherever in ``per_layer``:
    what a later PR appends behind them is that PR's (PERF.md B11(d):
    a case that holds a list's LAST entries goes red with the next
    append, and a PR of another kind may not mend it)."""
    names = [m["name"] for m in _spec()["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == NEW_METRICS
    assert [names.count(name) for name in NEW_METRICS] == [1] * len(
        NEW_METRICS)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_file_names_a_reader_that_reads_a_number(
        name, ring_stream):
    """The counted ones over the window from step 2 to step 10 of the
    tiny run (0.0 and not nothing where no stage waited); the traced
    ones on the synthetic trace."""
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           name + ".json")) as fh:
        own = json.load(fh)
    reader = importlib.import_module("benchmarks.readers." + own["reader"])
    if name in COUNTED:
        assert reader is telemetry_window
        ctx = {"telemetry_path": ring_stream, "window_steps": (2, 10),
               "window_wall_s": 1.0}
    else:
        assert reader is feed_idle_by_stage
        ctx = {"trace": _feed_trace()}
    value = reader.read(ctx, **own["args"])
    assert value is not None and value >= 0
    if name in ("scan_s_per_batch", "scan_read_s_per_batch",
                "build_idle_s_per_batch"):
        assert 0 < value < 60


def test_scan_holds_its_read_in_the_window_too(ring_stream):
    ctx = {"telemetry_path": ring_stream, "window_steps": (2, 10),
           "window_wall_s": 1.0}
    scan, read = (telemetry_window.read(ctx, *COUNTED[name])
                  for name in ("scan_s_per_batch", "scan_read_s_per_batch"))
    assert scan >= read > 0


def test_a_stream_from_before_the_counters_reads_nothing(tmp_path):
    """The parent's stream has none of the nine: each is left out of
    the line, and nothing is raised."""
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as fh:
        for step in (2, 10):
            fh.write(json.dumps({"event": "metrics", "step": step,
                                 "counters": {"pipeline/batches": step,
                                              "train/steps": step}}) + "\n")
    ctx = {"telemetry_path": path, "window_steps": (2, 10),
           "window_wall_s": 1.0}
    for counter, over in COUNTED.values():
        assert telemetry_window.read(ctx, counter, over) is None
