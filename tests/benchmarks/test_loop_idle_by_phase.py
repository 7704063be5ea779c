"""``readers/loop_idle_by_phase.py`` and the ten per-layer metrics of
ISSUE 37: every idle instant of every chip goes to the innermost span
open on the loop's thread, or to none; the shares sum to the idle
share. On synthetic ``Trace`` objects, on a trace recorded on the chip
(``testdata/tiny_train_tpu_phased.xplane.pb``: the tiny FM on a TPU v5
lite with the loop's leaf phases, my chip run, PR 37, cut by
``xplane_meta.cut``) and on the traces recorded before those phases."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmarks import trace_reduce
from benchmarks.readers import loop_idle_by_phase, telemetry_window
from benchmarks.trace_reduce import DeviceTrace, Op, Trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TESTDATA = os.path.join(REPO, "benchmarks", "testdata")
PHASED = os.path.join(TESTDATA, "tiny_train_tpu_phased.xplane.pb")
SCOPED = os.path.join(TESTDATA, "tiny_train_tpu_scoped.xplane.pb")
X4 = os.path.join(TESTDATA, "tiny_train_tpu_x4.xplane.pb")
COUNTED = ("bookkeeping_s_per_step", "loop_unnamed_share",
           "barrier_flush_s", "pipeline_open_s", "first_batch_s")
TRACED = {"idle_unnamed": None,
          "idle_in_bookkeeping": "train/bookkeeping",
          "idle_in_barrier_flush": "obs/barrier_flush",
          "idle_in_pipeline_open": "pipeline/open",
          "idle_in_first_batch": "pipeline/first_batch"}


def _metric_file(name):
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           name + ".json")) as fh:
        return json.load(fh)


def _chip(name, *busy):
    return DeviceTrace(name, [Op("op", a, b, {}) for a, b in busy], [])


def _loop(*spans, line="python3"):
    return [(line, Op(name, a, b, {})) for name, a, b in spans]


def _shares(trace):
    """phase -> percent of the window, through the reader."""
    ctx = {"trace": trace}
    table = loop_idle_by_phase.idle_by_phase(trace)
    return {name: loop_idle_by_phase.read(ctx, name) for name in table}


# ---- synthetic traces ----------------------------------------------------

def test_an_enclosure_keeps_only_what_is_outside_its_parts():
    """A barrier from 1 to 9 of a window of 10 with a flush and a
    pipeline open inside it, the chip idle from 1 to 9: each part gets
    its own seconds, the enclosure the rest, and ``idle_gaps`` gives
    the whole gap to the enclosure."""
    trace = Trace(
        [_chip("/device:TPU:0", (0, 1), (9, 10))],
        _loop(("train/step", 0.5, 1.0), ("train/epoch_barrier", 1.0, 9.0),
              ("obs/barrier_flush", 2.0, 4.0), ("pipeline/open", 5.0, 6.0),
              ("train/step", 9.0, 9.5)), 0.0, 10.0)
    assert _shares(trace) == pytest.approx({
        "train/epoch_barrier": 50.0, "obs/barrier_flush": 20.0,
        "pipeline/open": 10.0, "train/step": 0.0, None: 0.0})
    assert dict(trace.idle_gaps()) == {"train/epoch_barrier": 8.0}


def test_a_gap_of_three_short_phases_gives_each_its_part():
    trace = Trace(
        [_chip("/device:TPU:0", (0, 1), (4.25, 5))],
        _loop(("train/step", 0.0, 1.0), ("train/loss_sync", 1.0, 2.0),
              ("train/log_line", 2.0, 3.0), ("train/input_wait", 3.0, 4.0),
              ("train/step", 4.0, 4.5)), 0.0, 5.0)
    got = _shares(trace)
    assert got == pytest.approx({
        "train/loss_sync": 20.0, "train/log_line": 20.0,
        "train/input_wait": 20.0, "train/step": 5.0, None: 0.0})
    # no one event covers half of it
    assert dict(trace.idle_gaps()) == {"python_between_runtime_calls": 3.25}


def test_a_worker_threads_span_and_the_runtimes_events_are_not_read():
    """The gap from 1 to 4 lies under a build worker's span on another
    thread and a runtime call on the loop's: the loop's own span gets
    its second and the rest is under no span of the program."""
    host = (_loop(("pipeline/build_worker", 0.5, 4.5), line="worker")
            + _loop(("train/step", 0.0, 1.0), ("train/bookkeeping", 1.0, 2.0),
                    ("PjitFunction(fm_train_step)", 2.0, 4.0),
                    ("train/step", 4.0, 5.0), line="loop"))
    trace = Trace([_chip("/device:TPU:0", (0, 1), (4, 5))], host, 0.0, 5.0)
    assert _shares(trace) == pytest.approx({
        "train/bookkeeping": 20.0, "train/step": 0.0, None: 40.0})
    assert dict(trace.idle_gaps()) == {"pipeline/build_worker": 3.0}


def test_threads_of_one_name_are_told_apart_by_the_clock_stepping_back():
    """Every thread's line of a Python program is called after the
    process; ``reduce()`` keeps the name only. A line's events are
    sorted by start, so the next line begins where the start steps
    back."""
    host = (_loop(("pipeline/build_worker", 0.5, 4.5),
                  ("pipeline/build_worker", 4.6, 4.9))
            + _loop(("train/step", 0.0, 1.0), ("train/h2d", 1.0, 3.0),
                    ("train/step", 4.0, 5.0))
            + _loop(("pipeline/build", 0.2, 4.8)))
    trace = Trace([_chip("/device:TPU:0", (0, 1), (4, 5))], host, 0.0, 5.0)
    assert [len(line) for line in loop_idle_by_phase.host_lines(trace)] == [
        2, 3, 1]
    assert _shares(trace) == pytest.approx({
        "train/h2d": 40.0, "train/step": 0.0, None: 20.0})


def test_two_chips_with_different_gaps_are_averaged():
    trace = Trace(
        [_chip("/device:TPU:0", (0, 1), (3, 10)),
         _chip("/device:TPU:1", (0, 1), (7, 10))],
        _loop(("train/step", 0.0, 1.0), ("train/loss_sync", 1.0, 5.0),
              ("train/step", 9.0, 10.0)), 0.0, 10.0)
    got = _shares(trace)
    # chip 0 idles 1 to 3, chip 1 idles 1 to 7; the sync is open 1 to 5
    assert got == pytest.approx({"train/loss_sync": 30.0, "train/step": 0.0,
                                 None: 10.0})
    assert sum(got.values()) == pytest.approx(
        100.0 * (1 - trace.busy_s / trace.window_s))


def test_nothing_to_read_reads_nothing():
    chip = _chip("/device:TPU:0", (0, 1), (4, 5))
    no_loop = Trace([chip], _loop(("predict/input_wait", 1.0, 4.0)), 0, 5)
    assert loop_idle_by_phase.read({"trace": no_loop}, None) is None
    assert loop_idle_by_phase.read({"trace": no_loop},
                                   "predict/input_wait") is None
    # a program from before the phase had a span: the others read
    old = Trace([chip], _loop(("train/step", 0.0, 1.0),
                              ("train/input_wait", 1.0, 3.0)), 0.0, 5.0)
    ctx = {"trace": old}
    assert loop_idle_by_phase.read(ctx, "train/bookkeeping") is None
    assert loop_idle_by_phase.read(ctx, "train/input_wait") == 40.0
    assert loop_idle_by_phase.read(ctx, None) == 20.0


def test_the_table_is_printed_once_a_run(capsys):
    trace = Trace(
        [_chip("/device:TPU:0", (0, 1), (4, 5))],
        _loop(("train/step", 0.0, 1.0), ("train/bookkeeping", 1.0, 2.0),
              ("train/step", 4.0, 5.0)), 0.0, 5.0)
    ctx = {"trace": trace}
    for phase in TRACED.values():
        loop_idle_by_phase.read(ctx, phase)
    out = capsys.readouterr().out
    assert out.count("idle by loop phase") == 1
    assert "train/bookkeeping 20.000" in out and "unnamed 40.000" in out
    assert "sum 60.000 of an idle share 60.000" in out


# ---- recorded traces -------------------------------------------------------

@pytest.mark.parametrize("path,chips", [(PHASED, 1), (SCOPED, 1), (X4, 4)],
                         ids=["phased", "scoped", "x4"])
def test_the_shares_sum_to_the_idle_share_of_a_recorded_trace(path, chips):
    trace = trace_reduce.reduce(path)
    assert len(trace.devices) == chips
    got = _shares(trace)
    assert all(v >= 0 for v in got.values())
    assert sum(got.values()) == pytest.approx(
        100.0 * (1 - trace.busy_s / trace.window_s), abs=1e-6)


def test_the_phased_trace_has_the_leaves_and_little_left_unnamed():
    """The loop's thread is one line of the recorded trace, the leaf
    phases of ISSUE 37 are on it, and what no span of the program
    covers is a small part of the chip's idle time (on the trace from
    before them, bookkeeping and the rest read nothing)."""
    trace = trace_reduce.reduce(PHASED)
    names = {name for _, _, name in loop_idle_by_phase.loop_spans(trace)}
    assert names >= {"train/step", "train/bookkeeping", "train/batch_checks",
                     "train/log_line", "train/input_wait", "train/encode",
                     "train/h2d", "train/loss_sync", "obs/flush"}
    assert "pipeline/build_worker" not in names
    assert "pipeline/start" not in names
    got = _shares(trace)
    idle = 100.0 * (1 - trace.busy_s / trace.window_s)
    assert got[None] < 0.05 * idle
    old = {"trace": trace_reduce.reduce(SCOPED)}
    assert loop_idle_by_phase.read(old, "train/bookkeeping") is None
    assert loop_idle_by_phase.read(old, "train/input_wait") > 0


# ---- the ten metric files ----------------------------------------------------

@pytest.fixture(scope="module")
def tiny_stream(tmp_path_factory):
    from fast_tffm_tpu.train import train
    from tests.test_health_trace import _train_cfg
    cfg = _train_cfg(tmp_path_factory.mktemp("stream"),
                     np.random.default_rng(0), epoch_num=3, log_steps=2,
                     validation_files=())
    train(cfg)
    return cfg.model_file + ".metrics.jsonl"


@pytest.mark.parametrize("name", COUNTED + tuple(TRACED))
def test_a_new_metric_file_names_a_reader_that_reads_a_number(
        name, tiny_stream):
    """Epochs of 4 steps, a snapshot every 2: the window from step 2 to
    step 10 holds two epoch barriers. The traced ones read the
    recorded trace."""
    spec = _metric_file(name)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        entry = next(m for m in json.load(fh)["per_layer"]
                     if m["name"] == name)
    assert entry["workloads"] == ["fm16-train-zipf", "ffm4-train-zipf",
                                  "fm16x4-train-zipf", "fm8-train-bags"]
    assert entry["moves"] == "train_examples_per_s_per_chip"
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    if name in COUNTED:
        assert reader is telemetry_window
        ctx = {"telemetry_path": tiny_stream, "window_steps": (2, 10),
               "window_wall_s": 1.0}
    else:
        assert reader is loop_idle_by_phase
        assert spec["args"] == {"phase": TRACED[name]}
        ctx = {"trace": trace_reduce.reduce(PHASED)}
    value = reader.read(ctx, **spec["args"])
    assert value is not None and value >= 0
    if name in ("bookkeeping_s_per_step", "pipeline_open_s", "first_batch_s",
                "barrier_flush_s"):
        assert 0 < value < 60
