"""The one tracing seam of the host loop (ISSUE 25): ``obs/trace.span``
feeds a ``*_seconds`` counter, a JSONL span event and a profiler
annotation from one clock pair; ``begin`` holds a phase open across
functions; the train and predict loops name their phases through it;
``RunTelemetry`` counts what jax compiles."""

import contextlib
import glob
import os
import re
import time

import numpy as np
import pytest

from fast_tffm_tpu.obs.sink import read_events
from fast_tffm_tpu.obs.telemetry import RunTelemetry, activate
from fast_tffm_tpu.obs.trace import begin, span

from tests.test_health_trace import _train_cfg

PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fast_tffm_tpu")


def _events(path, kind):
    return [e for e in read_events(path) if e["event"] == kind]


def _last_counters(path):
    return _events(path, "metrics")[-1]["counters"]


# ---- span(seconds=...) -------------------------------------------------

def test_nothing_listening_is_the_shared_noop():
    for cm in (span("train/step"), span("train/step", seconds="x/y_seconds"),
               span("predict/sweep", leaf=False, files=3)):
        assert isinstance(cm, contextlib.nullcontext)
    assert span("a") is span("b")


def test_seconds_counts_with_tracing_off_and_emits_no_event(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={}, trace_spans=False)
    with activate(tel):
        for _ in range(3):
            with span("train/h2d", seconds="train/h2d_seconds", bytes=9):
                time.sleep(0.01)
        # no counter asked for, nothing traces: the shared no-op
        assert isinstance(span("predict/input_wait"),
                          contextlib.nullcontext)
    tel.close()
    assert _events(path, "span") == []
    assert 0.03 <= _last_counters(path)["train/h2d_seconds"] < 1.0


def test_counter_event_and_dur_are_one_interval(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={}, trace_spans=True)
    with activate(tel):
        with span("train/checkpoint_pause",
                  seconds="train/checkpoint_pause_seconds", step=4) as sp:
            time.sleep(0.01)
    tel.close()
    (ev,) = _events(path, "span")
    assert ev["name"] == "train/checkpoint_pause" and ev["step"] == 4
    assert "seconds" not in ev and "leaf" not in ev
    counted = _last_counters(path)["train/checkpoint_pause_seconds"]
    assert ev["dur"] == counted == sp.dur >= 0.01


def test_a_span_cut_by_an_exception_still_counts(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={}, trace_spans=True)
    with activate(tel), pytest.raises(ValueError):
        with span("predict/write", seconds="predict/write_seconds"):
            raise ValueError("disk full")
    tel.close()
    assert _events(path, "span")[0]["error"] == "ValueError"
    assert _last_counters(path)["predict/write_seconds"] > 0


def test_a_span_never_fetches_from_the_device(tmp_path, monkeypatch):
    """Fields and counters are host values: opening, closing and
    flushing spans may not materialise a device array."""
    import jax
    import jax.numpy as jnp
    import fast_tffm_tpu.utils.fetch as fetch
    x = jnp.ones(4) * 2          # a device array alive across the spans
    fetched = []
    monkeypatch.setattr(jax, "device_get",
                        lambda *a, **k: fetched.append("device_get"))
    monkeypatch.setattr(fetch, "bulk_fetch",
                        lambda *a, **k: fetched.append("bulk_fetch"))
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={}, trace_spans=True, flush_steps=1)
    with activate(tel):
        for step in range(1, 4):
            with span("train/step", seconds="train/dispatch_seconds",
                      step=step):
                x = x + 1
            tel.maybe_flush(step)
    assert fetched == []
    monkeypatch.undo()
    tel.close()
    assert len(_events(path, "span")) == 3


def test_begin_holds_a_phase_open_and_ends_it_once(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={}, trace_spans=True)
    with activate(tel):
        phase = begin("train/epoch_barrier",
                      seconds="train/epoch_barrier_seconds")
        time.sleep(0.01)
        phase.end()
        phase.end()             # the holder's finally may call it again
    tel.close()
    assert len(_events(path, "span")) == 1
    assert _last_counters(path)["train/epoch_barrier_seconds"] >= 0.01
    begin("predict/setup", seconds="predict/setup_seconds").end()  # no run


# ---- the profiler's clock ----------------------------------------------

def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_a_leaf_span_lands_in_the_host_plane_of_an_outside_trace(tmp_path):
    """Whoever started the profiler session: here the test, as the
    benchmark does, with no telemetry active at all. The span's plain
    name is the annotation's (fields stay out, so names group); a span
    that encloses a loop stays out of the trace."""
    import jax
    from jax.profiler import TraceAnnotation
    assert not TraceAnnotation.is_enabled()
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        assert TraceAnnotation.is_enabled()
        with span("predict/sweep", leaf=False, files=2):
            for step in range(3):
                with span("train/loss_sync",
                          seconds="train/loss_sync_seconds", step=step):
                    time.sleep(0.002)
        held = begin("train/epoch_barrier")
        time.sleep(0.002)
        held.end()
    finally:
        jax.profiler.stop_trace()
    assert isinstance(span("train/loss_sync"), contextlib.nullcontext)
    names = _host_event_names(str(tmp_path / "trace"))
    assert "train/loss_sync" in names and "train/epoch_barrier" in names
    assert "predict/sweep" not in names
    assert not any(n.startswith("train/loss_sync") and n != "train/loss_sync"
                   for n in names)


def test_one_place_opens_a_trace_annotation():
    hits = []
    for base, _, files in os.walk(PKG):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f), encoding="utf-8") as fh:
                    if re.search(r"TraceAnnotation|TraceMe\b", fh.read()):
                        hits.append(os.path.relpath(
                            os.path.join(base, f), PKG))
    assert hits == [os.path.join("obs", "trace.py")]


# ---- compiles ----------------------------------------------------------

def test_a_forced_retrace_moves_the_compile_counters(tmp_path):
    import jax
    import jax.numpy as jnp
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={})

    @jax.jit
    def seam_probe(x):
        return x * 3 + 1

    a, b = jnp.ones(3), jnp.ones(5)      # (making them compiles too)
    seam_probe(a).block_until_ready()
    tel.heartbeat(7)
    before = tel.registry.snapshot()["counters"]
    seam_probe(b).block_until_ready()                # a new shape
    after = tel.registry.snapshot()["counters"]
    seam_probe(b).block_until_ready()                # the same: nothing
    again = tel.registry.snapshot()["counters"]
    tel.close()
    assert after["compile/traces"] > before["compile/traces"]
    assert (after["compile/backend_compiles"]
            == before["compile/backend_compiles"] + 1)
    assert (after["compile/backend_compile_seconds"]
            > before["compile/backend_compile_seconds"])
    assert again == after
    mine = [e for e in _events(path, "compile")
            if e["fun_name"] == "jit(seam_probe)"]
    assert [e["step"] for e in mine] == [-1, 7]
    assert all(e["seconds"] > 0 for e in mine)


def test_compile_counters_start_at_zero_and_listeners_go_with_the_run(
        tmp_path):
    import jax
    import jax.numpy as jnp
    tel = RunTelemetry(str(tmp_path / "m.jsonl"), meta={})
    c = tel.registry.snapshot()["counters"]
    assert {k: c[k] for k in c if k.startswith("compile/")} == {
        "compile/backend_compiles": 0, "compile/backend_compile_seconds": 0,
        "compile/traces": 0, "compile/cache_hits": 0,
        "compile/cache_misses": 0}
    tel.close()
    jax.jit(lambda x: x - 2)(jnp.ones(7)).block_until_ready()
    assert tel.registry.snapshot()["counters"][
        "compile/backend_compiles"] == 0
    tel.close()                 # idempotent, listeners already gone


# ---- the loops' phases -------------------------------------------------

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    from fast_tffm_tpu.predict import predict
    from fast_tffm_tpu.train import train
    d = tmp_path_factory.mktemp("seam")
    cfg = _train_cfg(d, np.random.default_rng(0), trace_spans=True,
                     log_steps=2, predict_files=(str(d / "val.txt"),),
                     score_path=str(d / "score"))
    train(cfg)
    train_events = list(read_events(cfg.model_file + ".metrics.jsonl"))
    os.remove(cfg.model_file + ".metrics.jsonl")
    predict(cfg)
    return train_events, list(read_events(cfg.model_file + ".metrics.jsonl"))


@pytest.fixture(scope="module")
def loop_placed_run(tmp_path_factory):
    """The same two epochs under ``vocab_mode = admit``: a publish
    barrier may re-point a queued batch, so the loop encodes and places
    for itself (``StepLoop.wire_place``), as every run did until the
    feed placed ahead (ISSUE 46)."""
    from fast_tffm_tpu.train import train
    d = tmp_path_factory.mktemp("seam_loop")
    cfg = _train_cfg(d, np.random.default_rng(0), trace_spans=True,
                     log_steps=2, vocab_mode="admit", hash_feature_id=True)
    train(cfg)
    return list(read_events(cfg.model_file + ".metrics.jsonl"))


@pytest.mark.parametrize("names,counter,count", [
    # 8 + 2 ends; an epoch's first wait (2) goes by another name
    ("train/input_wait pipeline/first_batch", "train/input_wait_seconds",
     10),
    ("pipeline/first_batch", "pipeline/first_batch_seconds", 2),
    ("train/batch_checks", "train/batch_checks_seconds", 10),
    # encode + placement, a batch ahead on the feed's own thread; the
    # sweeps' feed places its 2 batches under the same name (ISSUE 51),
    # once: the second sweep scores what the first placed (ISSUE 53)
    ("feed/place", "train/place_seconds validation/place_seconds", 10),
    ("pipeline/emit", "pipeline/emit_seconds", 8),
    ("train/step", "train/dispatch_seconds", 8),
    ("train/bookkeeping", "train/bookkeeping_seconds", 8),
    ("train/loss_sync", "train/loss_sync_seconds", 4),
    ("train/log_line", "train/log_line_seconds", 4),
    ("obs/flush", "obs/flush_seconds", 4),
    ("train/epoch_barrier", "train/epoch_barrier_seconds", 2),
    ("train/barrier_reports", "train/barrier_reports_seconds", 2),
    ("pipeline/open", "pipeline/open_seconds", 2),
    ("train/validation", "train/validation_seconds", 2),
    ("obs/barrier_flush", None, None),
])
def test_train_loop_phase(traced_run, names, counter, count):
    """2 epochs of 4 steps, a loss line and a flush every 2: each phase
    is a span of the loop, and its counter is the sum of its spans."""
    events, _ = traced_run
    spans = [e for e in events if e["event"] == "span"
             and e["name"] in names.split()]
    assert spans
    if counter is None:
        return
    assert len(spans) == count
    counters = [e for e in events if e["event"] == "metrics"][-1]["counters"]
    assert sum(counters[c] for c in counter.split()) == pytest.approx(
        sum(s["dur"] for s in spans), rel=1e-9)


@pytest.mark.parametrize("name,counter", [
    ("train/encode", "train/encode_seconds"),
    ("train/h2d", "train/h2d_seconds"),
])
def test_train_loop_phase_where_the_loop_places(loop_placed_run, name,
                                                counter):
    """Re-pinned from ``test_train_loop_phase`` (ISSUE 46): encode and
    h2d are phases of the loop's thread where the loop places for
    itself, 8 spans each, and nothing is placed ahead there."""
    spans = [e for e in loop_placed_run if e["event"] == "span"
             and e["name"] == name]
    assert len(spans) == 8
    (tid,) = {e["tid"] for e in loop_placed_run if e["event"] == "span"
              and e["name"] == "train/step"}
    assert {s["tid"] for s in spans} == {tid}
    counters = [e for e in loop_placed_run
                if e["event"] == "metrics"][-1]["counters"]
    assert counters[counter] == pytest.approx(
        sum(s["dur"] for s in spans), rel=1e-9)
    assert counters["train/placed_ahead"] == 0
    assert counters["train/place_seconds"] == 0
    assert not [e for e in loop_placed_run if e["event"] == "span"
                and e["name"] == "feed/place"]


def test_the_epoch_barrier_encloses_its_parts(traced_run):
    events, _ = traced_run
    spans = [e for e in events if e["event"] == "span"]
    barrier = [s for s in spans if s["name"] == "train/epoch_barrier"][0]
    lo, hi = barrier["ts"], barrier["ts"] + barrier["dur"]
    inside = {s["name"] for s in spans
              if lo <= s["ts"] and s["ts"] + s["dur"] <= hi + 1e-6
              and s is not barrier}
    # the first barrier ends when the second epoch's first dispatch
    # returns: the reports, the validation pass, the flush, the cold
    # pipeline, its first batch and that first step lie inside it
    assert {"train/barrier_reports", "train/validation",
            "obs/barrier_flush", "pipeline/open", "pipeline/first_batch",
            "train/step"} <= inside
    assert "pipeline/start" not in {s["name"] for s in spans}


@pytest.mark.parametrize("which", ["mid-epoch", "epoch's last",
                                   "mid-epoch, the loop placing"])
def test_a_loss_line_syncs_after_the_next_batch_is_placed(
        traced_run, loop_placed_run, which):
    """A live loss line waits for the device only once the next batch is
    fetched and placed, just ahead of its dispatch, so the device waits
    for the host one dispatch after a line and not a placement too; the
    epoch's last line syncs before the barrier opens. Where the feed
    places (ISSUE 46) the batch arrives placed, and nothing of the
    loop's lies between the last dispatch and the sync."""
    events, _ = traced_run
    if which.endswith("the loop placing"):
        events = loop_placed_run
    spans = sorted((e for e in events if e["event"] == "span"
                    and e["name"] in ("train/loss_sync", "train/h2d",
                                      "train/step", "train/epoch_barrier")),
                   key=lambda e: e["ts"])
    names = [s["name"] for s in spans]
    syncs = [i for i, n in enumerate(names) if n == "train/loss_sync"]
    assert len(syncs) == 4          # steps 2, 4 (epoch 1), 6, 8 (epoch 2)
    if which.startswith("mid-epoch"):
        before = ("train/h2d" if which.endswith("the loop placing")
                  else "train/step")    # step 3's, step 7's h2d; or none
        for i in (syncs[0], syncs[2]):
            assert names[i - 1] == before
            assert names[i + 1] == "train/step"
            assert (spans[i]["ts"] + spans[i]["dur"]
                    <= spans[i + 1]["ts"] + 1e-6)
    else:
        i = syncs[1]
        assert names[i - 1] == "train/step"         # step 4's dispatch
        assert names[i + 1] == "train/epoch_barrier"
        assert names[syncs[3] - 1] == "train/step"  # step 8, then the end


def test_dead_gauges_are_gone(traced_run):
    events, predict_events = traced_run
    for evs in traced_run:
        last = [e for e in evs if e["event"] == "metrics"][-1]
        names = set(last["counters"]) | set(last["gauges"])
        assert not names & {"flush/window_seconds",
                            "train/examples_per_sec_total",
                            "pipeline/example_capacity", "predict/batches",
                            "train/checkpoints"}


@pytest.mark.parametrize("name,counter", [
    ("predict/setup", "predict/setup_seconds"),
    ("predict/input_wait", None),
    ("predict/score_dispatch", None),
    ("predict/drain", None),
    ("predict/write_wait", None),
    ("fetch/bulk", "fetch/d2h_seconds"),
    ("predict/write", "predict/write_seconds"),
    ("predict/run", "predict/seconds"),
])
def test_predict_loop_phase(traced_run, name, counter):
    _, events = traced_run
    spans = [e for e in events if e["event"] == "span"
             and e["name"] == name]
    assert spans
    counters = [e for e in events if e["event"] == "metrics"][-1]["counters"]
    if counter is not None:
        assert counters[counter] == pytest.approx(
            sum(s["dur"] for s in spans), rel=1e-9)
    if name == "predict/setup":
        # entry of predict() to the sweep's first dispatch
        first = min(s["ts"] for s in events if s["event"] == "span"
                    and s["name"] == "predict/score_dispatch")
        (setup,) = spans
        assert setup["ts"] + setup["dur"] <= first + 1e-3
        assert setup["dur"] < counters["predict/seconds"] + setup["dur"]
