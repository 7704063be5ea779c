"""Timeline/health layer (ISSUE 3): span tracing semantics and cost
discipline, watchdog stall detection (fake clock AND a real stalled
CPU train run), non-finite-loss detection at the barrier fetch, crash
forensics, fmstat's health verdict, and the JSONL -> Perfetto
round-trip."""

import json
import os
import time

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.obs.health import Watchdog
from fast_tffm_tpu.obs.sink import JsonlSink, read_events
from fast_tffm_tpu.obs.telemetry import (RunTelemetry, activate, active,
                                         make_telemetry)
from fast_tffm_tpu.obs.trace import span

from tests.test_e2e import make_dataset


# ------------------------------------------------------------------ spans

def test_span_is_noop_without_active_run():
    import contextlib
    cm = span("anything", step=1)
    assert isinstance(cm, contextlib.nullcontext)
    with cm:
        pass  # and it is actually enterable


def test_span_is_noop_when_run_does_not_trace(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={}, trace_spans=False)
    with activate(tel):
        with span("train/step", step=1):
            pass
    tel.close()
    assert [e for e in read_events(path) if e["event"] == "span"] == []


def test_spans_emit_and_nest_by_containment(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={}, trace_spans=True)
    with activate(tel):
        with span("outer", step=3):
            with span("inner"):
                time.sleep(0.01)
    tel.close()
    spans = [e for e in read_events(path) if e["event"] == "span"]
    # inner exits first, so it lands first in the stream
    assert [s["name"] for s in spans] == ["inner", "outer"]
    inner, outer = spans
    assert outer["step"] == 3
    assert inner["tid"] == outer["tid"]  # same thread = same track
    # time containment is what makes Perfetto nest them
    assert outer["ts"] <= inner["ts"]
    assert (inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1e-6)
    assert inner["dur"] >= 0.01


def test_span_records_exception_and_propagates(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={}, trace_spans=True)
    with activate(tel):
        with pytest.raises(RuntimeError):
            with span("doomed"):
                raise RuntimeError("boom")
    tel.close()
    s = [e for e in read_events(path) if e["event"] == "span"][0]
    assert s["error"] == "RuntimeError"


# --------------------------------------------------------------- watchdog

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_watchdog_stall_and_recovery_under_fake_clock(tmp_path):
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path, meta={})
    clock = FakeClock()
    w = Watchdog(sink, stall_seconds=10.0,
                 stacks_path=path + ".stacks", clock=clock)
    w.beat(5)
    clock.t += 9.0
    assert w.check() is None          # within budget: armed, silent
    clock.t += 2.0
    assert w.check() == "stalled"     # 11s since the beat
    assert w.check() is None          # one event per episode, no spam
    clock.t += 50.0
    assert w.check() is None
    w.beat(6)                          # progress resumes
    assert w.check() == "recovered"
    sink.close()
    health = [e for e in read_events(path) if e["event"] == "health"]
    assert [h["status"] for h in health] == ["stalled", "recovered"]
    st = health[0]
    assert st["last_step"] == 5
    assert st["stalled_seconds"] == pytest.approx(11.0)
    assert st["stacks_file"] == path + ".stacks"
    # the all-thread stack dump reached disk while still stalled
    dump = open(path + ".stacks").read()
    assert "stall after" in dump and "Current thread" in dump
    assert health[1]["outage_seconds"] == pytest.approx(61.0)


def test_watchdog_arms_from_construction(tmp_path):
    """A run wedged in SETUP (restore against dead storage) has never
    beaten; the watchdog must still fire."""
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path, meta={})
    clock = FakeClock()
    w = Watchdog(sink, stall_seconds=5.0,
                 stacks_path=path + ".stacks", clock=clock)
    clock.t += 6.0
    assert w.check() == "stalled"
    assert w.stall_events == 1
    sink.close()


# ---------------------------------------------------- non-finite detection

def test_nonfinite_loss_detected_at_barrier(tmp_path):
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path, meta={})
    sink.add_scalar("train/loss", 3, 0.5)
    sink.add_scalar("train/loss", 4, float("nan"))
    sink.add_scalar("train/loss", 6, float("inf"))
    sink.add_scalar("validation/auc", 6, 0.9)
    sink.barrier()
    sink.close()
    evs = list(read_events(path))
    health = [e for e in evs if e["event"] == "health"]
    assert len(health) == 1
    h = health[0]
    assert h["status"] == "nonfinite_loss"
    assert h["name"] == "train/loss"
    assert (h["step_first"], h["step_last"], h["count"]) == (4, 6, 2)
    # the scalar events themselves still land (forensics wants the raw
    # series too)
    assert len([e for e in evs if e["event"] == "scalar"]) == 4


def test_nonfinite_device_scalar_detected(tmp_path):
    """The real train shape: the loss is a DEVICE scalar, fetched only
    at the barrier — detection must ride that same fetch."""
    import jax.numpy as jnp
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path, meta={})
    sink.add_scalar("train/loss", 1, jnp.float32(jnp.nan))
    sink.barrier()
    sink.close()
    health = [e for e in read_events(path) if e["event"] == "health"]
    assert [h["status"] for h in health] == ["nonfinite_loss"]


# -------------------------------------------------------- crash forensics

def test_crash_event_carries_traceback_and_ring(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={})
    tel.sink.emit("span", {"name": "pipeline/build"})
    tel.count("train/steps", 3)
    try:
        raise ValueError("table exploded")
    except ValueError as e:
        tel.record_crash(e, step=7)
    tel.close(7)
    evs = list(read_events(path))
    assert evs[-1]["event"] == "run_end"  # sink still closes cleanly
    crash = [e for e in evs if e["event"] == "crash"][0]
    assert crash["step"] == 7
    assert "ValueError: table exploded" in crash["traceback"]
    names = [r.get("event") for r in crash["recent_events"]]
    assert "span" in names and "run_start" in names


def _train_cfg(tmp_path, rng, **kw):
    make_dataset(tmp_path / "train.txt", 128, rng)
    make_dataset(tmp_path / "val.txt", 64, rng)
    base = dict(vocabulary_size=200, factor_num=4, batch_size=32,
                learning_rate=0.1, epoch_num=2, shuffle=False,
                train_files=(str(tmp_path / "train.txt"),),
                validation_files=(str(tmp_path / "val.txt"),),
                model_file=str(tmp_path / "m" / "fm"),
                metrics_file="auto", metrics_flush_steps=2, log_steps=0)
    base.update(kw)
    return FmConfig(**base)


def test_train_crash_writes_crash_event_and_fmstat_verdict(
        tmp_path, rng, monkeypatch, capsys):
    cfg = _train_cfg(tmp_path, rng)
    from fast_tffm_tpu import train as train_mod

    def boom(*a, **k):
        raise RuntimeError("mid-epoch crash")

    monkeypatch.setattr(train_mod, "evaluate", boom)
    with pytest.raises(RuntimeError, match="mid-epoch crash"):
        train_mod.train(cfg)
    assert active() is None
    path = cfg.model_file + ".metrics.jsonl"
    evs = list(read_events(path))
    crash = [e for e in evs if e["event"] == "crash"]
    assert len(crash) == 1
    assert "mid-epoch crash" in crash[0]["traceback"]
    assert crash[0]["recent_events"]
    assert evs[-1]["event"] == "run_end"
    # fmstat health verdict: CRASHED, naming the error
    from tools.fmstat import main as fmstat_main
    assert fmstat_main([path]) == 0
    out = capsys.readouterr().out
    assert "health: CRASHED" in out
    assert "mid-epoch crash" in out


# --------------------------------------- acceptance: stalled CPU train run

def test_stalled_train_run_emits_health_and_stacks(tmp_path, rng,
                                                   monkeypatch, capsys):
    """ISSUE 3 acceptance: a deliberately stalled CPU train run (input
    iterator sleeps past watchdog_stall_seconds) produces a
    `health: stalled` event plus a .stacks all-thread dump, and fmstat
    reports STALLED."""
    cfg = _train_cfg(tmp_path, rng, watchdog_stall_seconds=0.25,
                     epoch_num=1)
    from fast_tffm_tpu import train as train_mod
    from fast_tffm_tpu.data import pipeline
    real_prefetch = pipeline.prefetch

    def stalling_prefetch(it, **kw):
        inner = real_prefetch(it, **kw)

        def gen():
            for i, batch in enumerate(inner):
                if i == 2:
                    time.sleep(1.0)  # 4x the stall budget
                yield batch
        return gen()

    monkeypatch.setattr(pipeline, "prefetch", stalling_prefetch)
    train_mod.train(cfg)
    path = cfg.model_file + ".metrics.jsonl"
    health = [e for e in read_events(path) if e["event"] == "health"]
    stalls = [h for h in health if h["status"] == "stalled"]
    assert stalls, f"no stall event in {health}"
    assert stalls[0]["stalled_seconds"] >= 0.25
    stacks = path + ".stacks"
    assert os.path.exists(stacks)
    dump = open(stacks).read()
    assert "Current thread" in dump  # faulthandler's all-thread format
    # the run RECOVERED after the sleep and finished; fmstat still
    # surfaces the episode.  A slow first jit compile can trip an extra
    # stalled/recovered pair at last_step == -1 before any step runs, so
    # pin the injected mid-run episode rather than the episode count.
    assert [h["status"] for h in health].count("recovered") >= 1
    mid_run = [h for h in stalls if h.get("last_step", -1) >= 0]
    assert mid_run, f"no mid-run stall episode in {health}"
    from tools.fmstat import main as fmstat_main
    assert fmstat_main([path]) == 0
    assert "health: STALLED" in capsys.readouterr().out


# ------------------------------------------- zero-fetch cost discipline

def test_watchdog_and_spans_add_zero_midstream_fetches(tmp_path, rng,
                                                       monkeypatch):
    """ISSUE 3 acceptance: enabling the watchdog + span tracing must
    not add a single mid-stream device fetch — bulk_fetch still runs
    ONLY at the two epoch barriers, same as with them off
    (test_obs.test_train_metrics_zero_midstream_fetches)."""
    import fast_tffm_tpu.utils.fetch as fetch
    calls = []
    real = fetch.bulk_fetch

    def counting(pairs, consume):
        calls.append(len(pairs))
        return real(pairs, consume)

    monkeypatch.setattr(fetch, "bulk_fetch", counting)
    cfg = _train_cfg(tmp_path, rng, metrics_flush_steps=1,
                     trace_spans=True, watchdog_stall_seconds=30.0)
    from fast_tffm_tpu.train import train
    train(cfg)
    # 2 epochs: each barrier drains (loss x4/epoch + auc x1) in ONE call
    assert calls == [5, 5]
    # and the stream actually carries spans (tracing was on)
    spans = [e for e in read_events(cfg.model_file + ".metrics.jsonl")
             if e["event"] == "span"]
    assert {s["name"] for s in spans} >= {
        "pipeline/build", "train/step", "train/validation",
        "checkpoint/save", "obs/barrier_flush", "fetch/bulk"}


# -------------------------------------------------- fmstat health verdicts

def test_clean_run_health_ok(tmp_path, rng, capsys):
    cfg = _train_cfg(tmp_path, rng)
    from fast_tffm_tpu.train import train
    train(cfg)
    from tools.fmstat import main as fmstat_main
    assert fmstat_main([cfg.model_file + ".metrics.jsonl"]) == 0
    assert "health: OK" in capsys.readouterr().out


def test_nonfinite_verdict_and_hard_kill_detail(tmp_path, capsys):
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path, meta={})
    sink.add_scalar("train/loss", 9, float("nan"))
    sink.barrier()   # writes health + scalars ... but no run_end:
    del sink         # emulate a hard-killed process (no close())
    from tools.fmstat import main as fmstat_main
    assert fmstat_main([path]) == 0
    out = capsys.readouterr().out
    assert "health: NONFINITE" in out
    assert "no run_end" in out
    # --json carries the verdict for scripting
    assert fmstat_main(["--json", path]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["health"]["verdict"] == "NONFINITE"


# -------------------------------------------------- JSONL -> Perfetto

def test_fmtrace_roundtrip_multiworker(tmp_path):
    """Spans + gauges + health from two worker shard files convert to
    trace-event JSON: one pid per process, one named tid per thread,
    X slices with microsecond ts/dur."""
    chief = str(tmp_path / "m.jsonl")
    shard = chief + ".p1"
    for p, path in ((0, chief), (1, shard)):
        tel = RunTelemetry(path, meta={"kind": "train",
                                       "process_index": p},
                           trace_spans=True)
        with activate(tel):
            with span("train/step", step=1):
                time.sleep(0.002)
            with span("checkpoint/save"):
                pass
        tel.set("train/examples_per_sec_window", 1000.0 + p)
        tel.close(1)
    out_path = str(tmp_path / "out.trace.json")
    from tools.fmtrace import main as fmtrace_main
    assert fmtrace_main([chief, shard, "-o", out_path]) == 0
    doc = json.load(open(out_path))
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    assert {e["name"] for e in xs} == {"train/step", "checkpoint/save"}
    step0 = [e for e in xs if e["name"] == "train/step"
             and e["pid"] == 0][0]
    assert step0["dur"] >= 2000  # microseconds
    assert step0["args"]["step"] == 1
    # process/thread naming metadata present
    pn = [e for e in evs if e["ph"] == "M"
          and e["name"] == "process_name"]
    assert {e["pid"] for e in pn} == {0, 1}
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in evs)
    # gauges became counter tracks, unit-labeled (PR 17), with their
    # last value re-emitted at run_end so short runs render
    cs = [e for e in evs if e["ph"] == "C"
          and e["name"] == "train/examples_per_sec_window [1/s]"]
    assert {e["args"]["value"] for e in cs} == {1000.0, 1001.0}
    # run_start/run_end instants frame each track
    assert any(e["ph"] == "i" and e["name"] == "run_end" for e in evs)


def test_fmtrace_covers_real_train_run(tmp_path, rng):
    """ISSUE 3 acceptance: a normal CPU run with trace_spans on yields
    a JSONL that fmtrace converts with pipeline/step/checkpoint spans
    present."""
    cfg = _train_cfg(tmp_path, rng, trace_spans=True, save_steps=4)
    from fast_tffm_tpu.train import train
    train(cfg)
    out_path = str(tmp_path / "t.json")
    from tools.fmtrace import convert
    n = convert([cfg.model_file + ".metrics.jsonl"], out_path)
    assert n > 0
    evs = json.load(open(out_path))["traceEvents"]
    names = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"pipeline/build", "train/step", "train/validation",
            "checkpoint/save", "checkpoint/restore"} <= names
    # the pipeline spans ran on their own (prefetch) track
    tid_by_name = {}
    for e in evs:
        if e["ph"] == "M" and e["name"] == "thread_name":
            tid_by_name[e["args"]["name"]] = e["tid"]
    assert "prefetch" in tid_by_name
    build = [e for e in evs if e["ph"] == "X"
             and e["name"] == "pipeline/build"][0]
    assert build["tid"] == tid_by_name["prefetch"]


# ------------------------------------------------------------ knobs

def test_config_knobs_parse_and_validate(tmp_path):
    import textwrap
    cfg_path = tmp_path / "c.cfg"
    cfg_path.write_text(textwrap.dedent("""\
        [General]
        vocabulary_size = 100
        [Train]
        train_files = x.txt
        trace_spans = true
        watchdog_stall_seconds = 42.5
    """))
    from fast_tffm_tpu.config import load_config
    cfg = load_config(str(cfg_path))
    assert cfg.trace_spans is True
    assert cfg.watchdog_stall_seconds == 42.5
    with pytest.raises(ValueError, match="watchdog_stall_seconds"):
        FmConfig(watchdog_stall_seconds=-1.0)


def test_make_telemetry_wires_watchdog_and_spans(tmp_path):
    cfg = FmConfig(metrics_file=str(tmp_path / "m.jsonl"),
                   trace_spans=True, watchdog_stall_seconds=30.0)
    tel = make_telemetry(cfg, "train")
    try:
        assert tel.trace_spans is True
        assert tel.watchdog is not None
        assert tel.watchdog.stacks_path == str(
            tmp_path / "m.jsonl") + ".stacks"
        t0 = tel.watchdog._beat
        tel.heartbeat(12)
        assert tel.watchdog._beat[1] == 12 and tel.watchdog._beat != t0
    finally:
        tel.close()
    # close() stopped the thread
    assert tel.watchdog._thread is None


def test_health_verdict_scopes_to_latest_run(tmp_path, capsys):
    """The sink appends, so a fixed metrics path accumulates runs: an
    old crash must not brand a later clean rerun CRASHED."""
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={"kind": "train"})
    try:
        raise RuntimeError("old bug")
    except RuntimeError as e:
        tel.record_crash(e)
    tel.close()
    # rerun appends a clean run to the same file
    tel2 = RunTelemetry(path, meta={"kind": "train"})
    tel2.count("train/steps", 5)
    tel2.close(5)
    from tools.fmstat import main as fmstat_main
    assert fmstat_main([path]) == 0
    assert "health: OK" in capsys.readouterr().out


def test_nonfinite_nonloss_scalar_is_not_a_health_event(tmp_path):
    """A NaN validation AUC is a legitimate value (a shard with no
    positives/negatives); only LOSS scalars escalate to health."""
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path, meta={})
    sink.add_scalar("validation/auc", 4, float("nan"))
    sink.barrier()
    sink.close()
    evs = list(read_events(path))
    assert [e for e in evs if e["event"] == "health"] == []
    assert [e for e in evs if e["event"] == "scalar"]  # still recorded


def test_watchdog_stop_emits_pending_recovery(tmp_path):
    """A stall that recovers within the final poll interval still gets
    its 'recovered' event at stop() — a clean finish must not read as
    'NOT recovered'."""
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path, meta={})
    clock = FakeClock()
    w = Watchdog(sink, stall_seconds=5.0,
                 stacks_path=path + ".stacks", clock=clock)
    clock.t += 6.0
    assert w.check() == "stalled"
    w.beat(9)        # recovery lands after the last poll...
    w.stop()         # ...and stop()'s final check records it
    sink.close()
    health = [e for e in read_events(path) if e["event"] == "health"]
    assert [h["status"] for h in health] == ["stalled", "recovered"]
