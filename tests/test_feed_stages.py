"""Who waited for whom along a job's feed (ISSUE 54): ``_read_ahead``
counts a blocked producer and a starved consumer, the build ring the
coordinator's wait at its head and its workers' time without a task,
the scanner has its span with the file read inside it, every counter of
``telemetry.FEED_STAGES`` is in the stream from the feed's making, and
one ``next(feed)`` of ``telemetry.FEED_STALL_SECONDS`` or more writes
one ``feed_stall`` event that names the stage that slept. The waits are
counters and JSONL events, never profiler annotations; with nothing
listening no stage reads a clock."""

import contextlib
import threading
import time

import numpy as np
import pytest

from fast_tffm_tpu import train as train_mod
from fast_tffm_tpu.data import pipeline
from fast_tffm_tpu.obs import telemetry
from fast_tffm_tpu.obs.attribution import attribution, summarize
from fast_tffm_tpu.obs.sink import read_events
from fast_tffm_tpu.obs.telemetry import (FEED_STAGES, FEED_STALL, TRAIN_FEED,
                                         RunTelemetry, activate,
                                         feed_counters)

from tests.test_e2e import make_dataset
from tests.test_health_trace import _train_cfg
from tests.test_span_seam import _host_event_names

PLANES = {"pipeline": "train", "validation_plane": "validation"}
WAITS = [name for name in TRAIN_FEED if "_wait_" in name or "idle" in name]


@contextlib.contextmanager
def _telemetry(tmp_path, **kw):
    tel = RunTelemetry(str(tmp_path / "m.jsonl"), meta={}, **kw)
    try:
        with activate(tel):
            yield tel
    finally:
        tel.close()


def _counters(tel):
    return tel.registry.snapshot()["counters"]


def _slow(items, seconds):
    for item in items:
        time.sleep(seconds)
        yield item


@contextlib.contextmanager
def _clock_reads():
    """Counts the reads of the two clocks a span or a counter's timer
    takes, on any thread, while the block runs."""
    reads = []
    real = time.perf_counter, time.time
    time.perf_counter = lambda: reads.append("perf_counter") or real[0]()
    time.time = lambda: reads.append("time") or real[1]()
    try:
        yield reads
    finally:
        time.perf_counter, time.time = real


# ---- the hand-overs --------------------------------------------------------

def test_a_slow_consumer_counts_put_wait_and_no_get_wait(tmp_path):
    with _telemetry(tmp_path) as tel:
        ahead = pipeline._read_ahead(iter(range(8)), 1, "fm-scan", "plane")
        next(ahead)     # the one get that may find the queue empty
        starved = _counters(tel).get("plane/fm_scan_get_wait_seconds", 0.0)
        done = object()
        while True:
            time.sleep(0.05)    # (long enough for a loaded host's producer)
            if next(ahead, done) is done:
                break
        c = _counters(tel)
    assert c["plane/fm_scan_put_wait_seconds"] >= 0.08
    # the producer runs ahead: no later get finds the queue empty
    assert c.get("plane/fm_scan_get_wait_seconds", 0.0) == starved


def test_a_slow_producer_counts_get_wait_and_no_put_wait(tmp_path):
    with _telemetry(tmp_path) as tel:
        # (room for all six: a consumer a loaded host holds up blocks no put)
        ahead = pipeline._read_ahead(_slow(range(6), 0.02), 6, "prefetch",
                                     "plane")
        assert list(ahead) == list(range(6))
        c = _counters(tel)
    assert c["plane/prefetch_get_wait_seconds"] >= 0.08
    assert "plane/prefetch_put_wait_seconds" not in c


def test_the_last_stage_leaves_the_get_to_its_consumer(tmp_path):
    """``place_ahead``'s: the loop's own ``train/input_wait`` is that
    wait, and is not counted a second time."""
    with _telemetry(tmp_path) as tel:
        feed = pipeline.place_ahead(_slow(iter("abcdef"), 0.002),
                                    lambda b: (b, b), 1, "loop")
        for _ in feed:
            time.sleep(0.03)
        c = _counters(tel)
    assert c["loop/fm_place_put_wait_seconds"] > 0.0
    assert not [k for k in c if k.endswith("get_wait_seconds")]
    assert c["loop/place_seconds"] > 0.0


def test_a_wait_is_an_event_of_a_traced_run_and_no_annotation(tmp_path):
    with _telemetry(tmp_path, trace_spans=True):
        # (room for all three, so that only the consumer ever waits)
        assert list(pipeline._read_ahead(_slow(range(3), 0.01), 3, "fm-scan",
                                         "pipeline")) == [0, 1, 2]
    spans = [e for e in read_events(str(tmp_path / "m.jsonl"))
             if e["event"] == "span"]
    assert {e["name"] for e in spans} == {"pipeline/fm_scan_get_wait"}
    assert all(e["tid"] != "fm-scan" for e in spans)    # the consumer's


@pytest.mark.parametrize("prefix,active_run", [
    (None, True),       # predict's prefetch, the stream's own thread
    ("pipeline", False),
    (None, False)])
def test_with_nothing_to_count_no_hand_over_reads_a_clock(
        tmp_path, prefix, active_run):
    run = _telemetry(tmp_path) if active_run else contextlib.nullcontext()
    with run as tel, _clock_reads() as reads:
        # both sides wait: a slow producer, then a slow consumer
        ahead = pipeline._read_ahead(_slow(range(3), 0.01), 1, "prefetch",
                                     prefix)
        for _ in ahead:
            pass
        for _ in pipeline._read_ahead(iter(range(4)), 1, "prefetch", prefix):
            time.sleep(0.01)
        assert reads == []
        if tel is not None:
            assert not [k for k in _counters(tel) if "_wait_" in k]


# ---- the ring --------------------------------------------------------------

def test_a_ring_whose_work_sleeps_counts_ring_wait_on_the_coordinator(
        tmp_path):
    def work(_state, payload):
        time.sleep(0.03)
        return payload

    with _telemetry(tmp_path, trace_spans=True) as tel:
        ring = pipeline._BuildRing(2, 4, work, counters="plane")
        try:
            seqs = [ring.submit(i) for i in range(4)]
            assert [ring.wait(s) for s in seqs] == [("ok", i)
                                                   for i in range(4)]
        finally:
            ring.close()
        c = _counters(tel)
    assert c["plane/ring_wait_seconds"] >= 0.04
    assert c["plane/worker_build_seconds"] >= 0.12
    waits = [e for e in read_events(str(tmp_path / "m.jsonl"))
             if e["event"] == "span" and e["name"] == "plane/ring_wait"]
    assert waits and {e["tid"] for e in waits} == {
        threading.current_thread().name}


def test_a_result_that_is_in_costs_the_coordinator_no_ring_wait(tmp_path):
    with _telemetry(tmp_path) as tel:
        ring = pipeline._BuildRing(1, 2, lambda _s, p: p, counters="plane")
        try:
            s = ring.submit("x")
            while not ring.has(s):
                time.sleep(0.001)
            assert ring.wait(s) == ("ok", "x")
        finally:
            ring.close()
        assert "plane/ring_wait_seconds" not in _counters(tel)


def test_a_ring_with_no_tasks_counts_worker_idle(tmp_path):
    with _telemetry(tmp_path) as tel:
        ring = pipeline._BuildRing(2, 4, lambda _s, p: p, counters="plane")
        try:
            time.sleep(0.05)
            s = ring.submit(1)      # wakes them: the idle is counted
            assert ring.wait(s) == ("ok", 1)
            time.sleep(0.01)
            idle = _counters(tel)["plane/worker_idle_seconds"]
        finally:
            ring.close()
    assert idle >= 0.04     # one worker's at the least; summed over both


def test_with_no_telemetry_the_ring_and_its_workers_read_no_clock():
    def work(_state, payload):
        time.sleep(0.01)
        return payload

    with _clock_reads() as reads:
        ring = pipeline._BuildRing(2, 4, work)
        try:
            time.sleep(0.02)        # idle workers
            seqs = [ring.submit(i) for i in range(4)]
            assert [ring.wait(s)[1] for s in seqs] == list(range(4))
        finally:
            ring.close()
        assert reads == []


# ---- the plane: scanner, ring, zero start ---------------------------------

@pytest.fixture(scope="module")
def traced_feed_run(tmp_path_factory):
    """Two epochs of four steps on the ring's route, spans on."""
    d = tmp_path_factory.mktemp("feed")
    cfg = _train_cfg(d, np.random.default_rng(0), trace_spans=True,
                     host_threads=2, validation_files=())
    train_mod.train(cfg)
    path = cfg.model_file + ".metrics.jsonl"
    return path, list(read_events(path))


def test_scan_holds_scan_read(traced_feed_run):
    _, events = traced_feed_run
    spans = [e for e in events if e["event"] == "span"]
    scans = [e for e in spans if e["name"] == "pipeline/scan"]
    reads = [e for e in spans if e["name"] == "pipeline/scan_read"]
    assert scans and reads
    assert {e["tid"] for e in scans} == {e["tid"] for e in reads} == {
        "fm-scan"}
    slack = 2e-5    # time.time() stamps a start, perf_counter a length
    for r in reads:
        assert any(s["ts"] - slack <= r["ts"]
                   and r["ts"] + r["dur"] <= s["ts"] + s["dur"] + slack
                   for s in scans), r
    last = [e for e in events if e["event"] == "metrics"][-1]["counters"]
    assert (last["pipeline/scan_seconds"]
            >= last["pipeline/scan_read_seconds"] > 0.0)
    # one group a batch and an end an epoch
    assert len(scans) == last["pipeline/batches"] + 2


def test_the_ring_route_counts_every_stage(traced_feed_run):
    path, events = traced_feed_run
    last = [e for e in events if e["event"] == "metrics"][-1]["counters"]
    assert set(TRAIN_FEED) | set(FEED_STALL) <= set(last)
    assert last["pipeline/worker_idle_seconds"] > 0.0
    assert last["pipeline/fm_scan_put_wait_seconds"] > 0.0
    rows = attribution(summarize([path]))["feed_stages"]
    assert [r["stage"] for r in rows] == [st.label for st in FEED_STAGES]
    place = rows[-1]
    assert place["starved"] == pytest.approx(
        last["train/input_wait_seconds"] / last["pipeline/batches"])
    from fast_tffm_tpu.obs.attribution import render
    said = render(summarize([path]))
    assert "FEED (s a batch" in said and "occupancy" not in said


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_every_feed_counter_starts_at_zero(tmp_path, plane):
    """Where the feed is made, whichever route it will take and before
    a thread of it has run: a window in which nothing waited reads 0.0
    and not nothing."""
    loop = PLANES[plane]
    cfg = _train_cfg(tmp_path, np.random.default_rng(0))
    with _telemetry(tmp_path, flush_steps=1) as tel:
        feed = pipeline.EpochFeed(
            cfg, cfg.train_files, range(0, 1), place=lambda b: (b, None),
            hold=False, uniq_bucket=lambda: 0, counters=plane,
            loop=loop)
        try:
            tel.maybe_flush(1)
        finally:
            feed.close()
    (first, *_) = [e for e in read_events(str(tmp_path / "m.jsonl"))
                   if e["event"] == "metrics"]
    names = feed_counters(plane, loop)
    assert len(names) == 12 and len(set(names)) == 12
    assert [first["counters"].get(name) for name in names] == [0.0] * 12
    assert first["counters"][plane + "/epochs_fed_ahead"] == 0.0
    assert sum(name.startswith(loop + "/") for name in names) == 2


def test_a_feed_whose_consumer_places_has_no_stage_on_that_side(tmp_path):
    """Admit, offload, more processes: ``place`` None, the loop's own
    ``train/h2d`` is the placement, and ``train/place_seconds`` stays
    out of the stream (tests/test_sweep_feed.py reads its absence)."""
    cfg = _train_cfg(tmp_path, np.random.default_rng(0))
    with _telemetry(tmp_path) as tel:
        feed = pipeline.EpochFeed(cfg, cfg.train_files, range(0, 1),
                                  place=None, hold=True,
                                  uniq_bucket=lambda: 0)
        feed.close()
        c = _counters(tel)
    assert set(feed_counters("pipeline", None)) <= set(c)
    assert not [name for name in c if name.startswith("train/")]
    assert len(feed_counters("pipeline", None)) == 10


def test_the_loops_clock_starts_the_stall_counters_at_zero(tmp_path):
    with _telemetry(tmp_path) as tel:
        tel.loop_start()
        c = _counters(tel)
        tel.loop_stop()
    assert [c[name] for name in FEED_STALL] == [0.0, 0.0]


# ---- a stall says where it was --------------------------------------------

def _stalled_train(tmp_path, monkeypatch, seconds, limit):
    """One epoch of 32 steps whose scanner sleeps ``seconds`` inside
    its 24th group's file read: the feed's queues hold a dozen batches
    ahead, the loop takes those and waits for the 24th. The constant is
    ``limit`` from there on only (a CPU's first batch and first compile
    are slow too)."""
    rng = np.random.default_rng(1)
    cfg = _train_cfg(tmp_path, rng, epoch_num=1, validation_files=(),
                     host_threads=2, batch_size=8)
    make_dataset(tmp_path / "train.txt", 256, rng)
    real = pipeline._GroupScanner.next_group
    calls = []

    def next_group(self):
        calls.append(1)
        if len(calls) == 24:
            from fast_tffm_tpu.obs.trace import span
            monkeypatch.setattr(telemetry, "FEED_STALL_SECONDS", limit)
            # where a file read stalls: under scan_read, inside scan
            with span("pipeline/scan_read",
                      seconds="pipeline/scan_read_seconds"):
                time.sleep(seconds)
        return real(self)

    monkeypatch.setattr(telemetry, "FEED_STALL_SECONDS", 1e9)
    monkeypatch.setattr(pipeline._GroupScanner, "next_group", next_group)
    train_mod.train(cfg)
    path = cfg.model_file + ".metrics.jsonl"
    return path, list(read_events(path))


def test_a_planted_wait_emits_one_feed_stall_naming_the_stage_that_slept(
        tmp_path, monkeypatch, capsys):
    path, events = _stalled_train(tmp_path, monkeypatch, 0.4, 0.2)
    (stall,) = [e for e in events if e["event"] == "feed_stall"]
    # the coordinator asks for a group before it emits a head that is
    # in, so the batches of the groups in the ring wait with it
    assert 20 <= stall["step"] <= 24 and 0.2 <= stall["wall"] < 5.0
    assert stall["window"] >= stall["wall"]
    stages = stall["stages"]
    assert set(stages) <= set(TRAIN_FEED)
    assert list(stages.values()) == sorted(stages.values(), reverse=True)
    # of the stages' WORK the scanner's read is what grew; the waits
    # behind it (the coordinator for a group, the placer for a batch)
    # grew with it, and the builders had no task
    work = {name: s for name, s in stages.items() if name not in WAITS}
    assert max(work, key=work.get) in ("pipeline/scan_seconds",
                                       "pipeline/scan_read_seconds")
    assert stages["pipeline/scan_read_seconds"] >= 0.4
    assert stages["pipeline/fm_scan_get_wait_seconds"] >= 0.3
    assert stages["pipeline/prefetch_get_wait_seconds"] >= 0.3
    last = [e for e in events if e["event"] == "metrics"][-1]["counters"]
    assert last["train/feed_stalls"] == 1
    assert last["train/feed_stall_seconds"] == pytest.approx(stall["wall"])
    from tools.fmstat import main as fmstat_main
    assert fmstat_main([path]) == 0
    out = capsys.readouterr().out
    said = f"feed stall at step {stall['step']}"
    assert said in out
    assert "scan" in out.split(said)[1].splitlines()[0]


def test_a_wait_under_the_limit_emits_none(tmp_path, monkeypatch):
    _, events = _stalled_train(tmp_path, monkeypatch, 0.05, 5.0)
    assert not [e for e in events if e["event"] == "feed_stall"]
    last = [e for e in events if e["event"] == "metrics"][-1]["counters"]
    assert last["train/feed_stalls"] == 0.0
    assert last["train/feed_stall_seconds"] == 0.0


def test_feed_stall_differences_against_the_last_flush(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={}, flush_steps=1)
    tel.loop_start()
    tel.count("pipeline/worker_build_seconds", 4.0)     # before the flush
    tel.maybe_flush(1)
    tel.count("pipeline/worker_build_seconds", 0.5)
    tel.count("pipeline/scan_read_seconds", 2.0)
    tel.count("train/input_wait_seconds", 2.0)          # the loop's: no stage
    tel.feed_stall(2, 2.0)
    tel.close()
    (stall,) = [e for e in read_events(path) if e["event"] == "feed_stall"]
    assert stall["stages"] == {"pipeline/scan_read_seconds": 2.0,
                               "pipeline/worker_build_seconds": 0.5}
    assert (stall["step"], stall["wall"]) == (2, 2.0)
    assert 0.0 <= stall["window"] < 1.0


# ---- the profiler's host plane --------------------------------------------

def test_the_waits_stay_out_of_a_live_profilers_trace(tmp_path):
    """``trace_reduce.idle_gaps`` names a gap after the host event over
    most of it: a stage that is blocked nearly all the time must not be
    there, the stages' work must."""
    import jax
    cfg = _train_cfg(tmp_path, np.random.default_rng(0), epoch_num=1,
                     validation_files=(), host_threads=2, batch_size=4)
    make_dataset(tmp_path / "train.txt", 128, np.random.default_rng(0))

    def work(_state, payload):
        time.sleep(0.01)
        return payload

    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        with _telemetry(tmp_path) as tel:
            feed = pipeline.EpochFeed(
                cfg, cfg.train_files, range(0, 1),
                place=lambda b: (b, None), hold=False,
                uniq_bucket=lambda: 0)
            try:
                for _ in feed:
                    time.sleep(0.005)   # blocks every stage behind it
            finally:
                feed.close()
            ring = pipeline._BuildRing(1, 2, work)
            try:
                assert ring.wait(ring.submit(1)) == ("ok", 1)
            finally:
                ring.close()
            c = _counters(tel)
    finally:
        jax.profiler.stop_trace()
    assert c["pipeline/prefetch_put_wait_seconds"] > 0.0
    assert c["train/fm_place_put_wait_seconds"] > 0.0
    names = _host_event_names(str(tmp_path / "trace"))
    assert {"pipeline/scan", "pipeline/scan_read", "pipeline/ring_wait",
            "pipeline/build_worker", "feed/place"} <= names
    ours = {n for n in names if n.startswith(("pipeline/", "train/"))}
    assert not [n for n in ours if "_wait" in n and n != "pipeline/ring_wait"]
    assert not [n for n in ours if "idle" in n]
