"""Step anatomy (obs/anatomy.py + the anatomy/* gauge surface):
synthetic two-rank streams with KNOWN injected clock offset/drift and
a known straggler must come back out of the clock fit and the verdict;
the pre-aggregated gauges must never touch the device; and the fmstat
EFFICIENCY / bench --compare consumers must read the same surfaces."""

import json
import os
import subprocess
import sys

import pytest

from fast_tffm_tpu.obs import anatomy
from fast_tffm_tpu.obs.attribution import efficiency_table
from fast_tffm_tpu.obs.telemetry import (RunTelemetry, anatomy_gauges,
                                         make_telemetry)
from fast_tffm_tpu.obs.sink import read_events


# ------------------------------------------------- synthetic streams

def _clock(offset, drift, t_ref=0.0):
    """A rank's wall clock as a function of true time: true + offset
    + drift * (true - t_ref). Rank 0 uses (0, 0) = truth."""
    return lambda true: true + offset + drift * (true - t_ref)


def _rank_events(pid, clock, barriers, locals_=()):
    """One rank's event list: run_start with the pid, then span events
    stamped in the rank's OWN clock. ``barriers`` is a list of
    (name, arrival_true, release_true); ``locals_`` of
    (name, start_true, dur_true)."""
    evs = [{"event": "run_start", "t": clock(0.0),
            "meta": {"process_index": pid}}]
    spans = [(n, a, r - a) for (n, a, r) in barriers] + list(locals_)
    for name, start, dur in sorted(spans, key=lambda s: s[1]):
        ts = clock(start)
        evs.append({"event": "span", "name": name, "t": ts, "ts": ts,
                    "dur": clock(start + dur) - ts, "tid": "main"})
    return evs


def _straggler_streams(offset=0.0, drift=0.0, n_steps=20,
                       late=0.04, transport=0.002):
    """Two ranks, flags barrier each 0.1 s step: rank 1 arrives
    ``late`` seconds after rank 0 (rank 1 is the straggler), release
    ``transport`` after the last arrival. Rank 1's stream is written
    in a clock offset/drifted from rank 0's."""
    b0, b1, l0, l1 = [], [], [], []
    for k in range(n_steps):
        t = 0.1 * k
        l0.append(("train/h2d", t, 0.005))
        l1.append(("train/h2d", t, 0.005))
        arr0, arr1 = t + 0.01, t + 0.01 + late
        rel = max(arr0, arr1) + transport
        b0.append(("train/step_flags", arr0, rel))
        b1.append(("train/step_flags", arr1, rel))
    return {
        0: _rank_events(0, _clock(0.0, 0.0), b0, l0),
        1: _rank_events(1, _clock(offset, drift), b1, l1),
    }


# ---------------------------------------------------- clock alignment

def test_clock_fit_recovers_injected_offset_and_drift():
    off, dr = 3.7, 50e-6  # 3.7 s offset, 50 ppm drift
    ranks = _straggler_streams(offset=off, drift=dr)
    rep = anatomy.build_report(ranks)
    c = rep["clock"][1]
    # The release edges are exactly affine in the synthetic streams,
    # so the fit is essentially exact: offset recovered to ~the drift
    # accumulated over the 2 s window, residual near zero.
    assert c["sync_points"] == 20
    assert c["offset_ms"] == pytest.approx(-off * 1e3, abs=1.0)
    assert c["drift_ppm"] == pytest.approx(-dr * 1e6, rel=0.1)
    assert c["residual_ms"] < 0.01
    # Round trip: rank 1's local release edges align onto rank 0's.
    fits = anatomy.align_clocks(ranks)
    clock1 = _clock(off, dr)
    for k in range(20):
        rel = 0.1 * k + 0.01 + 0.04 + 0.002
        assert fits[1].aligned(clock1(rel)) == pytest.approx(
            rel, abs=1e-6)


def test_identity_fit_for_reference_rank():
    rep = anatomy.build_report(_straggler_streams())
    assert rep["clock"][0]["offset_ms"] == 0.0
    assert rep["clock"][0]["drift_ppm"] == 0.0


# ------------------------------------------------ straggler anatomy

def test_straggler_attributed_through_skewed_clocks():
    """Rank 1 arrives 40 ms late at every flags barrier; its stream is
    written 3.7 s + 50 ppm away from rank 0's clock. Raw timestamps
    would call rank ONE the early one (its clock runs ahead) — only
    the aligned view names it."""
    rep = anatomy.build_report(
        _straggler_streams(offset=3.7, drift=50e-6))
    assert rep["straggler_rank"] == 1
    assert rep["ranks"][1]["last_arrivals"] == 20
    assert rep["ranks"][0]["last_arrivals"] == 0
    # Rank 0 pays the straggler wait (40 ms of each ~100 ms step);
    # rank 1 pays none.
    assert rep["ranks"][0]["phases"]["straggler wait"] == pytest.approx(
        0.04 * 20, rel=0.05)
    assert rep["ranks"][1]["phases"]["straggler wait"] == pytest.approx(
        0.0, abs=1e-3)
    assert rep["top_barrier"] == "train/step_flags"
    assert "straggler" in rep["verdict"]
    assert "rank 1" in rep["verdict"]
    # Efficiency: rank 0 loses the 42 ms wait of each ~100 ms step.
    assert rep["ranks"][0]["efficiency"] == pytest.approx(0.58,
                                                          abs=0.05)
    out = anatomy.render(rep)
    assert "STEP ANATOMY" in out and "straggler" in out


def test_transport_dominant_verdict():
    """Both ranks arrive together but the release comes 30 ms later:
    the wall is the collective itself, not a straggler."""
    rep = anatomy.build_report(
        _straggler_streams(late=0.0, transport=0.03))
    assert rep["transport_fraction"] > 0.15
    assert rep["straggler_wait_fraction"] < 0.05
    assert "transport" in rep["verdict"]


def test_baseline_eps_prices_the_in_program_stall():
    """With a single-process baseline rate, the report computes the
    ABSOLUTE per-worker efficiency (useful compute time / wall) —
    the number comparable to bench --multihost's counter-derived
    value, which also counts stalls inside the dispatched program."""
    ranks = _straggler_streams()
    # 2 s wall per rank; 400 examples at a 1000 eps baseline = 0.4 s
    # of useful compute -> efficiency_vs_single = 0.2.
    for pid in (0, 1):
        ranks[pid].append({"event": "metrics", "t": 2.1, "step": 20,
                           "counters": {"train/examples": 400.0},
                           "gauges": {}, "hists": {}})
    rep = anatomy.build_report(ranks, baseline_eps=1000.0)
    for pid in (0, 1):
        assert rep["ranks"][pid]["examples"] == 400.0
        assert rep["ranks"][pid]["efficiency_vs_single"] == \
            pytest.approx(0.2, rel=0.1)
    assert rep["efficiency_vs_single"] == pytest.approx(0.2, rel=0.1)
    assert "vs single-process rate" in rep["verdict"]
    assert "0.2" in anatomy.render(rep)
    # Without a baseline the field stays out of the report rows.
    rep2 = anatomy.build_report(_straggler_streams())
    assert rep2["efficiency_vs_single"] is None
    assert "efficiency_vs_single" not in rep2["ranks"][0]


def test_in_program_wall_verdict():
    """Dominant 'step dispatch' on a multi-rank run: the verdict must
    say the wall is inside the dispatched program (the host cannot
    split in-program allreduce from compute), not claim efficiency."""
    b0, b1, l0, l1 = [], [], [], []
    for k in range(10):
        t = 0.1 * k
        # 80 ms of every 100 ms step inside the dispatched program.
        l0.append(("train/step", t, 0.08))
        l1.append(("train/step", t, 0.08))
        b0.append(("train/step_flags", t + 0.085, t + 0.09))
        b1.append(("train/step_flags", t + 0.085, t + 0.09))
    ranks = {0: _rank_events(0, _clock(0.0, 0.0), b0, l0),
             1: _rank_events(1, _clock(0.0, 0.0), b1, l1)}
    rep = anatomy.build_report(ranks)
    assert "inside the dispatched program" in rep["verdict"]


def test_empty_input_is_an_error_report():
    rep = anatomy.build_report({})
    assert "error" in rep
    assert "trace_spans" in anatomy.render(rep)


# -------------------------------------------------- fmtrace --anatomy

def _write_streams(tmp_path, ranks):
    paths = []
    for pid, evs in ranks.items():
        p = str(tmp_path / (f"m.jsonl" if pid == 0
                            else f"m.jsonl.p{pid}"))
        with open(p, "w") as fh:
            for e in evs:
                fh.write(json.dumps(e) + "\n")
        paths.append(p)
    return paths


def test_fmtrace_anatomy_cli(tmp_path, capsys):
    from tools.fmtrace import main
    paths = _write_streams(tmp_path,
                           _straggler_streams(offset=1.25))
    assert main(["--anatomy"] + paths) == 0
    out = capsys.readouterr().out
    assert "STEP ANATOMY" in out and "verdict:" in out
    assert main(["--anatomy", "--json"] + paths) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["straggler_rank"] == 1
    assert rep["clock"]["1"]["offset_ms"] == pytest.approx(-1250.0,
                                                           abs=1.0)


# ------------------------------------------------- anatomy/* gauges

def test_anatomy_gauges_derive_from_snapshot():
    snap = {
        "counters": {"train/input_wait_seconds": 1.5,
                     "pipeline/build_seconds": 0.5,
                     "train/step_flags_seconds": 2.0,
                     "train/examples": 640.0},
        "gauges": {},
        "hists": {"train/step_seconds":
                  {"count": 20, "sum": 10.0}},
    }
    rows = anatomy_gauges(snap)
    assert rows["anatomy/input_wait_seconds"] == 1.5
    assert rows["anatomy/host_build_seconds"] == 0.5
    assert rows["anatomy/flags_wait_seconds"] == 2.0
    assert rows["anatomy/step_wall_seconds"] == 10.0
    assert rows["anatomy/steps"] == 20.0
    assert rows["anatomy/examples"] == 640.0
    # Phases the run never recorded stay absent, not zero rows.
    assert "anatomy/h2d_seconds" not in rows


def test_anatomy_gauges_add_zero_device_fetches(tmp_path, monkeypatch):
    """The EFFICIENCY surface is pre-aggregated host floats: a flush
    with anatomy on performs NO bulk_fetch (the scalar barrier remains
    the only fetch point, exactly as without anatomy)."""
    import fast_tffm_tpu.utils.fetch as fetch
    calls = []
    monkeypatch.setattr(fetch, "bulk_fetch",
                        lambda pairs, consume: calls.append(len(pairs))
                        or [])
    tel = RunTelemetry(str(tmp_path / "m.jsonl"), meta={},
                       flush_steps=1, anatomy=True)
    tel.count("train/step_flags_seconds", 0.25)
    tel.count("lockstep/allgather_seconds", 0.5)
    tel.count("train/examples", 64)
    tel.observe("train/step_seconds", 0.1)
    tel.maybe_flush(1)
    tel.barrier_flush(2)
    tel.close()
    assert calls == []  # no buffered scalars -> no fetch, ever
    evs = [e for e in read_events(str(tmp_path / "m.jsonl"))
           if e.get("event") == "metrics"]
    assert evs
    g = evs[-1]["gauges"]
    assert g["anatomy/flags_wait_seconds"] == 0.25
    assert g["anatomy/allgather_seconds"] == 0.5
    assert g["anatomy/step_wall_seconds"] == pytest.approx(0.1)


def test_anatomy_off_emits_no_gauges(tmp_path):
    tel = RunTelemetry(str(tmp_path / "m.jsonl"), meta={},
                       flush_steps=1, anatomy=False)
    tel.count("train/step_flags_seconds", 0.25)
    tel.observe("train/step_seconds", 0.1)
    tel.maybe_flush(1)
    tel.close()
    evs = [e for e in read_events(str(tmp_path / "m.jsonl"))
           if e.get("event") == "metrics"]
    assert not any(k.startswith("anatomy/")
                   for k in evs[-1]["gauges"])


def test_make_telemetry_reads_anatomy_knob(tmp_path):
    from fast_tffm_tpu.config import FmConfig
    cfg = FmConfig(vocabulary_size=16, factor_num=2,
                   train_files=("x",),
                   model_file=str(tmp_path / "fm"),
                   metrics_file=str(tmp_path / "m.jsonl"))
    tel = make_telemetry(cfg, "train")
    assert tel is not None and tel.anatomy is True
    tel.close()
    cfg2 = FmConfig(vocabulary_size=16, factor_num=2,
                    train_files=("x",),
                    model_file=str(tmp_path / "fm2"),
                    metrics_file=str(tmp_path / "m2.jsonl"),
                    anatomy=False)
    tel2 = make_telemetry(cfg2, "train")
    assert tel2 is not None and tel2.anatomy is False
    tel2.close()


# -------------------------------------------- fmstat EFFICIENCY rows

def _proc_gauges(wall, flags, allgather, examples, build=0.0):
    return {"anatomy/step_wall_seconds": wall,
            "anatomy/flags_wait_seconds": flags,
            "anatomy/allgather_seconds": allgather,
            "anatomy/host_build_seconds": build,
            "anatomy/examples": examples}


def test_efficiency_table_names_the_straggler():
    # Rank 1 waits the LEAST -> everyone else waits on rank 1.
    summary = {"gauges_by_process": {
        0: _proc_gauges(10.0, 4.0, 1.0, 640.0),
        1: _proc_gauges(10.0, 0.5, 0.5, 640.0, build=6.0),
    }}
    eff = efficiency_table(summary)
    assert eff is not None
    assert eff["straggler_rank"] == 1
    assert eff["ranks"][0]["efficiency"] == pytest.approx(0.5)
    assert eff["ranks"][1]["efficiency"] == pytest.approx(0.9)
    assert "rank 1" in eff["verdict"]
    assert "host build" in eff["verdict"]


def test_efficiency_table_of_one_process_and_absent_without_phases():
    # A single-process run has no collective waits and gets the phase
    # table all the same, over the loop's wall where the stream has it.
    one = {"gauges_by_process": {
        0: {"anatomy/step_wall_seconds": 8.0, "anatomy/loop_seconds": 10.0,
            "anatomy/dispatch_seconds": 6.0, "anatomy/h2d_seconds": 1.0,
            "anatomy/unnamed_seconds": 0.05, "anatomy/examples": 640.0}}}
    eff = efficiency_table(one)
    assert eff["straggler_rank"] is None and eff["efficiency"] == 1.0
    r = eff["ranks"][0]
    assert r["wall_seconds"] == 10.0 and r["examples_per_sec"] == 64.0
    assert r["phases"]["dispatch"] == 6.0 and r["phases"]["unnamed"] == 0.05
    assert "largest phase: dispatch 60%" in eff["verdict"]
    assert "unnamed 0.5%" in eff["verdict"]
    # Anatomy gauges but no phase, or none at all: no section.
    summary = {"gauges_by_process": {
        0: {"anatomy/step_wall_seconds": 10.0,
            "anatomy/examples": 640.0}}}
    assert efficiency_table(summary) is None
    assert efficiency_table({"gauges_by_process": {}}) is None


def test_fmstat_renders_efficiency_section(tmp_path, capsys):
    """A merged stream whose processes carry anatomy/* gauges gets the
    EFFICIENCY section, verdict line included."""
    from tools.fmstat import main as fmstat_main
    for pid in (0, 1):
        p = str(tmp_path / ("m.jsonl" if pid == 0
                            else f"m.jsonl.p{pid}"))
        with open(p, "w") as fh:
            fh.write(json.dumps(
                {"event": "run_start", "t": 0.0,
                 "meta": {"kind": "train",
                          "process_index": pid}}) + "\n")
            fh.write(json.dumps(
                {"event": "metrics", "t": 10.0, "step": 100,
                 "run": {"kind": "train", "process_index": pid},
                 "counters": {"train/examples": 640.0},
                 "gauges": _proc_gauges(10.0, 4.0 - 3.0 * pid, 1.0,
                                        640.0),
                 "hists": {}}) + "\n")
    rc = fmstat_main([str(tmp_path / "m.jsonl"),
                      str(tmp_path / "m.jsonl.p1")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "EFFICIENCY (step anatomy):" in out
    assert "collective wait" in out


# ------------------------------------------- real 2-process anatomy

@pytest.mark.slow
def test_two_process_run_names_the_collective_wall(tmp_path):
    """A REAL 2-process gloo cluster with tracing on: fmtrace
    --anatomy must align the shards, match barriers, and name the
    collective wall this container actually has (the flags allgather
    and the transport that absorbs queued device compute)."""
    import socket as socketlib
    with socketlib.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = s.getsockname()[1]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    data = tmp_path / "train.txt"
    lines = ["%d %d:1 %d:1" % (i % 2, i % 97, 97 + (i * 7) % 89)
             for i in range(1920)]
    data.write_text("\n".join(lines) + "\n")
    metrics = str(tmp_path / "metrics.jsonl")
    cfg = tmp_path / "anatomy.cfg"
    hosts = ",".join(f"localhost:{coord - 1000 + i}" for i in range(2))
    cfg.write_text(f"""
[General]
vocabulary_size = 256
factor_num = 4
model_file = {tmp_path / 'model' / 'fm'}

[Train]
train_files = {data}
epoch_num = 1
batch_size = 32
learning_rate = 0.05
shuffle = False
log_steps = 0
metrics_file = {metrics}
trace_spans = True

[Cluster]
worker_hosts = {hosts}
""")
    procs = [subprocess.Popen(
        [sys.executable, "run_tffm.py", "train", str(cfg),
         "dist_train", "worker", str(i)],
        cwd=repo, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL) for i in range(2)]
    rcs = [p.wait(timeout=300) for p in procs]
    assert rcs == [0, 0]
    shards = [metrics, metrics + ".p1"]
    assert all(os.path.exists(p) for p in shards)
    rep = anatomy.report(shards)
    assert "error" not in rep
    assert rep["matched_barriers"] > 0
    assert rep["top_barrier"] in anatomy.BARRIER_SPANS
    assert set(rep["ranks"]) == {0, 1}
    for r in rep["ranks"].values():
        assert 0.0 <= r["efficiency"] <= 1.0
    # Localhost gloo: the clock fit must land far under a step.
    for c in rep["clock"].values():
        assert c["residual_ms"] < 50.0
    # The verdict names the wall this container actually has: the
    # in-program allreduce inside the dispatched step program, or (on
    # a loaded machine) a straggler/transport-dominated barrier.
    assert ("inside the dispatched program" in rep["verdict"]
            or "straggler" in rep["verdict"]
            or "transport" in rep["verdict"])
    out = anatomy.render(rep)
    assert "verdict:" in out
    # The JSONL-only EFFICIENCY surface sees the same run: per-worker
    # efficiency from pre-aggregated gauges within 25% (absolute) of
    # the trace-replay number (different denominators: gauges use the
    # step-wall histogram, the replay uses span coverage).
    from fast_tffm_tpu.obs.attribution import summarize
    eff = efficiency_table(summarize(shards))
    assert eff is not None
    for pid, row in eff["ranks"].items():
        assert abs(row["efficiency"]
                   - rep["ranks"][pid]["efficiency"]) < 0.25
