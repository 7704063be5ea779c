"""obs/ telemetry: registry semantics (merge, quantiles), JSONL sink
link-safety (one bulk fetch per barrier, zero fetches per flush),
end-to-end train/predict event streams, and fmstat's attribution
rendering over them."""

import json
import os

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.obs.registry import (Counter, Gauge, Histogram,
                                        MetricsRegistry)
from fast_tffm_tpu.obs.sink import JsonlSink, read_events
from fast_tffm_tpu.obs.telemetry import (RunTelemetry, activate, active,
                                         make_telemetry,
                                         resolve_metrics_path, run_meta)

from tests.test_e2e import make_dataset


# ---------------------------------------------------------------- registry

def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    r.count("a", 2)
    r.count("a")
    r.set("g", 0.5)
    for v in (0.001, 0.002, 0.004, 10.0):
        r.observe("h", v)
    snap = r.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 0.5
    h = snap["hists"]["h"]
    assert h["count"] == 4
    assert h["min"] == 0.001 and h["max"] == 10.0
    assert h["sum"] == pytest.approx(10.007)
    # p50 falls in the bucket holding the 2nd point; p99 in the max's.
    assert h["p50"] <= 0.004
    assert h["p99"] == pytest.approx(10.0)


def test_histogram_merge_and_roundtrip():
    a, b = Histogram(bounds=(1, 2, 4)), Histogram(bounds=(1, 2, 4))
    for v in (0.5, 1.5, 3.0):
        a.observe(v)
    for v in (8.0, 0.1):
        b.observe(v)
    a.merge(Histogram.from_summary(b.summary()))
    assert a.count == 5
    assert a.min == 0.1 and a.max == 8.0
    assert a.sum == pytest.approx(13.1)
    assert sum(a.counts) == 5
    with pytest.raises(ValueError, match="different bounds"):
        a.merge(Histogram(bounds=(1, 2)))


def test_registry_merge_counters_add_hists_fold():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.count("c", 5)
    r2.count("c", 7)
    r2.count("only2", 1)
    r1.observe("h", 0.01, bounds=(0.1, 1.0))
    r2.observe("h", 0.5, bounds=(0.1, 1.0))
    r2.set("g", 3.0)
    r1.merge(r2)
    snap = r1.snapshot()
    assert snap["counters"]["c"] == 12
    assert snap["counters"]["only2"] == 1
    assert snap["hists"]["h"]["count"] == 2
    assert snap["gauges"]["g"] == 3.0


# ------------------------------------------------------------------- sink

def test_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path, meta={"kind": "test", "config_hash": "abc"})
    sink.emit("metrics", {"step": 4, "counters": {"x": 1.5}})
    sink.flush()
    sink.close()
    evs = list(read_events(path))
    assert [e["event"] for e in evs] == ["run_start", "metrics",
                                        "run_end"]
    assert evs[0]["meta"]["config_hash"] == "abc"
    assert evs[1]["step"] == 4 and evs[1]["counters"] == {"x": 1.5}
    # numpy values must serialize, not crash the flush
    sink2 = JsonlSink(str(tmp_path / "n.jsonl"), meta={})
    sink2.emit("metrics", {"v": np.float32(1.25), "a": np.arange(3)})
    sink2.close()
    ev = [e for e in read_events(str(tmp_path / "n.jsonl"))
          if e["event"] == "metrics"][0]
    assert ev["v"] == 1.25 and ev["a"] == [0, 1, 2]


def test_read_events_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as fh:
        fh.write('{"event": "metrics", "step": 1}\n{"event": "met')
    evs = list(read_events(path))
    assert len(evs) == 1 and evs[0]["step"] == 1


def test_scalar_buffer_single_bulk_fetch(tmp_path, monkeypatch):
    """Buffered device scalars flush in exactly ONE bulk_fetch per
    barrier, and a plain flush() performs none (link-safety)."""
    import jax
    import fast_tffm_tpu.utils.fetch as fetch
    calls = []
    real = fetch.bulk_fetch

    def counting(pairs, consume):
        calls.append(len(pairs))
        return real(pairs, consume)

    monkeypatch.setattr(fetch, "bulk_fetch", counting)
    sink = JsonlSink(str(tmp_path / "m.jsonl"), meta={})
    for i in range(5):
        sink.add_scalar("loss", i, jax.numpy.float32(i))
    sink.flush()          # host flush: must NOT touch the device
    assert calls == []
    sink.barrier()        # ONE grouped transfer for all 5
    assert calls == [5]
    sink.close()
    assert calls == [5]   # nothing left to fetch at close
    evs = [e for e in read_events(str(tmp_path / "m.jsonl"))
           if e["event"] == "scalar"]
    assert [(e["step"], e["value"]) for e in evs] == [
        (i, float(i)) for i in range(5)]


def test_scalar_buffer_cap_forces_drain(tmp_path, monkeypatch):
    import fast_tffm_tpu.obs.sink as sink_mod
    monkeypatch.setattr(sink_mod, "SCALAR_BUFFER_MAX", 3)
    sink = JsonlSink(str(tmp_path / "m.jsonl"), meta={})
    for i in range(4):
        sink.add_scalar("x", i, float(i))
    assert len(sink._scalars) == 1  # cap hit drained the first 3
    sink.close()


# -------------------------------------------------------------- telemetry

def test_activate_scopes_active():
    assert active() is None
    t = RunTelemetry.__new__(RunTelemetry)  # no sink needed for scoping
    with activate(t) as got:
        assert got is t and active() is t
        with activate(None):
            assert active() is t  # None passes through
    assert active() is None


def test_resolve_metrics_path(tmp_path):
    cfg = FmConfig(metrics_file="")
    assert resolve_metrics_path(cfg) is None
    cfg = FmConfig(metrics_file="auto",
                   model_file=str(tmp_path / "m" / "fm"))
    assert resolve_metrics_path(cfg) == str(
        tmp_path / "m" / "fm") + ".metrics.jsonl"
    cfg = FmConfig(metrics_file=str(tmp_path / "x.jsonl"))
    assert resolve_metrics_path(cfg) == str(tmp_path / "x.jsonl")


def test_run_meta_fields(tmp_path):
    cfg = FmConfig(metrics_file="auto")
    meta = run_meta(cfg, "train")
    assert meta["kind"] == "train"
    assert meta["backend"] == "cpu" and meta["device_count"] == 8
    assert meta["process_count"] == 1
    assert len(meta["config_hash"]) == 12
    # same config -> same hash; different config -> different
    assert meta["config_hash"] == run_meta(cfg, "x")["config_hash"]
    assert (run_meta(FmConfig(factor_num=9), "x")["config_hash"]
            != meta["config_hash"])


def test_run_meta_says_the_model_and_its_order():
    """The header of every event names the model: an order-3 run's step
    holds a scan an order-2 run's does not (ISSUE 41)."""
    assert run_meta(FmConfig(), "train")["model"] == {
        "model_type": "fm", "order": 2, "factor_num": FmConfig().factor_num}
    assert run_meta(FmConfig(order=3, factor_num=8), "train")["model"] == {
        "model_type": "fm", "order": 3, "factor_num": 8}
    assert run_meta(None, "serve")["model"] is None


def test_flush_cadence_writes_metrics_events(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={"kind": "t"}, flush_steps=2)
    for step in range(1, 7):
        tel.count("steps")
        tel.maybe_flush(step)
    tel.close(6)
    evs = [e for e in read_events(path) if e["event"] == "metrics"]
    # steps 2, 4, 6 flushed + the close event
    assert [e["step"] for e in evs] == [2, 4, 6, 6]
    # cumulative counters: each later event >= the earlier
    vals = [e["counters"]["steps"] for e in evs]
    assert vals == sorted(vals) and vals[-1] == 6
    # run metadata rides every metrics event
    assert all(e["run"] == {"kind": "t"} for e in evs)


# ------------------------------------------------- end-to-end train/predict

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


def test_train_emits_parseable_jsonl_with_all_stages(tmp_path, rng):
    cfg = _train_cfg(tmp_path, rng)
    from fast_tffm_tpu.train import train
    train(cfg)
    path = cfg.model_file + ".metrics.jsonl"
    evs = list(read_events(path))
    kinds = {e["event"] for e in evs}
    assert {"run_start", "metrics", "scalar", "run_end"} <= kinds
    last = [e for e in evs if e["event"] == "metrics"][-1]
    c, g, h = last["counters"], last["gauges"], last["hists"]
    # pipeline counters (train 4 batches x 2 epochs + validation)
    assert c["pipeline/examples"] >= 256
    assert c["pipeline/feature_nnz"] > 0
    assert c["pipeline/batches"] >= 8
    # step-time histogram summary: 8 train steps
    assert h["train/step_seconds"]["count"] == 8
    assert h["train/step_seconds"]["p50"] > 0
    assert c["train/steps"] == 8
    assert c["train/examples"] == 256
    assert c["train/h2d_bytes"] > 0
    assert c["train/epochs"] == 2
    # examples/sec gauges from the shared StepTimer window
    assert g["train/examples_per_sec_window"] > 0
    assert 0.0 <= g["validation/auc"] <= 1.0
    # run metadata on the event itself
    assert last["run"]["kind"] == "train"
    assert last["run"]["backend"] == "cpu"
    # buffered scalars landed with step attribution (flush cadence 2)
    loss_steps = [e["step"] for e in evs
                  if e["event"] == "scalar" and e["name"] == "train/loss"]
    assert loss_steps == [2, 4, 6, 8]
    auc_steps = [e["step"] for e in evs
                 if e["event"] == "scalar"
                 and e["name"] == "validation/auc"]
    assert auc_steps == [4, 8]


def test_train_metrics_zero_midstream_fetches(tmp_path, rng,
                                              monkeypatch):
    """Link-safety acceptance: with metrics on at a step-level flush
    cadence, bulk_fetch runs ONLY at epoch barriers — one grouped
    transfer per epoch, nothing per step/flush."""
    import fast_tffm_tpu.utils.fetch as fetch
    calls = []
    real = fetch.bulk_fetch

    def counting(pairs, consume):
        calls.append(len(pairs))
        return real(pairs, consume)

    monkeypatch.setattr(fetch, "bulk_fetch", counting)
    cfg = _train_cfg(tmp_path, rng, metrics_flush_steps=1)
    from fast_tffm_tpu.train import train
    train(cfg)
    # 2 epochs: each barrier drains (loss x4/epoch + auc x1) in ONE call
    assert calls == [5, 5]


def test_metrics_off_writes_nothing(tmp_path, rng):
    cfg = _train_cfg(tmp_path, rng, metrics_file="")
    from fast_tffm_tpu.train import train
    train(cfg)
    assert not os.path.exists(cfg.model_file + ".metrics.jsonl")
    # and nothing left active after the run
    assert active() is None


def test_sink_closes_on_midrun_crash(tmp_path, rng, monkeypatch):
    """Satellite: a crash mid-epoch must still flush the sink — the
    JSONL ends with the close-time metrics event, not silence."""
    cfg = _train_cfg(tmp_path, rng)
    from fast_tffm_tpu import train as train_mod

    def boom(*a, **k):
        raise RuntimeError("mid-epoch crash")

    # evaluate runs at the first epoch barrier, after 4 steps
    monkeypatch.setattr(train_mod, "evaluate", boom)
    with pytest.raises(RuntimeError, match="mid-epoch crash"):
        train_mod.train(cfg)
    assert active() is None  # popped even on the error path
    evs = list(read_events(cfg.model_file + ".metrics.jsonl"))
    assert evs[-1]["event"] == "run_end"
    last = [e for e in evs if e["event"] == "metrics"][-1]
    assert last["counters"]["train/steps"] == 4
    # the buffered loss scalars since the last barrier survived too
    assert [e["step"] for e in evs if e["event"] == "scalar"
            and e["name"] == "train/loss"] == [2, 4]


def test_predict_emits_rate_and_depth(tmp_path, rng):
    cfg = _train_cfg(tmp_path, rng)
    from fast_tffm_tpu.train import train
    from fast_tffm_tpu.predict import predict
    train(cfg)
    import dataclasses
    cfgp = dataclasses.replace(
        cfg, predict_files=(str(tmp_path / "val.txt"),),
        score_path=str(tmp_path / "score"),
        metrics_file=str(tmp_path / "predict.jsonl"))
    predict(cfgp)
    evs = list(read_events(str(tmp_path / "predict.jsonl")))
    pf = [e for e in evs if e["event"] == "predict_file"]
    assert len(pf) == 1
    assert pf[0]["examples"] == 64 and pf[0]["examples_per_sec"] > 0
    last = [e for e in evs if e["event"] == "metrics"][-1]
    assert last["run"]["kind"] == "predict"
    assert last["counters"]["predict/examples"] == 64
    assert last["hists"]["predict/fetch_depth"]["count"] == 2
    # fmstat surfaces predict streams too (not just train loops)
    from fast_tffm_tpu.obs.attribution import attribution, summarize
    att = attribution(summarize([str(tmp_path / "predict.jsonl")]))
    assert att["predict_examples"] == 64
    assert att["predict_examples_per_sec"] > 0
    assert att["verdict"].startswith("predict:")


# ----------------------------------------------------------------- fmstat

def test_fmstat_renders_attribution(tmp_path, rng, capsys):
    cfg = _train_cfg(tmp_path, rng)
    from fast_tffm_tpu.train import train
    train(cfg)
    path = cfg.model_file + ".metrics.jsonl"
    from tools.fmstat import main as fmstat_main
    assert fmstat_main([path]) == 0
    out = capsys.readouterr().out
    assert "kind=train" in out and "backend=cpu" in out
    assert "examples/sec" in out
    assert "dedup hit rate" in out
    assert "padding-waste fraction" in out
    assert "verdict:" in out
    # --json mode round-trips
    assert fmstat_main(["--json", path]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["attribution"]["examples"] == 256
    assert d["attribution"]["verdict"]


def test_fmstat_merges_worker_shards(tmp_path):
    """Per-worker shard files merge: counters add, hists fold, gauges
    keyed by process index — the sharded path's read-time merge."""
    from fast_tffm_tpu.obs.attribution import summarize
    for p in range(2):
        path = str(tmp_path / ("m.jsonl" if p == 0
                               else f"m.jsonl.p{p}"))
        tel = RunTelemetry(path, meta={"kind": "train",
                                       "process_index": p,
                                       "pid": 100 + p,
                                       "start_time": 1.0},
                           flush_steps=0)
        tel.count("train/examples", 100 * (p + 1))
        tel.observe("train/step_seconds", 0.01 * (p + 1))
        tel.set("predict/examples_per_sec", 50.0 + p)
        tel.close(5)
    s = summarize([str(tmp_path / "m.jsonl"),
                   str(tmp_path / "m.jsonl.p1")])
    assert s["counters"]["train/examples"] == 300
    assert s["hists"]["train/step_seconds"]["count"] == 2
    assert s["gauges_by_process"][0]["predict/examples_per_sec"] == 50.0
    assert s["gauges_by_process"][1]["predict/examples_per_sec"] == 51.0


def test_lockstep_counters_feed_active_telemetry(tmp_path, rng):
    """The sharded scoring protocol counts rounds/batches/examples into
    the active run's stream (single-process on the fake 8-device mesh;
    real multi-worker shard files are covered by the merge test)."""
    import jax
    from fast_tffm_tpu.data.pipeline import (batch_iterator,
                                             probe_uniq_bucket)
    from fast_tffm_tpu.models.fm import ModelSpec
    from fast_tffm_tpu.parallel.sharded import (init_sharded_state,
                                                lockstep_score_batches,
                                                make_mesh,
                                                make_sharded_score_fn)
    lines = []
    for _ in range(40):
        ids = rng.choice(64, size=4, replace=False)
        lines.append("1 " + " ".join(f"{i}:1" for i in sorted(ids)))
    data = tmp_path / "d.txt"
    data.write_text("\n".join(lines) + "\n")
    cfg = FmConfig(vocabulary_size=64, factor_num=4, batch_size=8,
                   shuffle=False, bucket_ladder=(8,), dedup="host",
                   model_file=str(tmp_path / "m" / "fm"))
    mesh = make_mesh(jax.devices()[:8])
    table, _ = init_sharded_state(cfg, mesh)
    score_fn = make_sharded_score_fn(ModelSpec.from_config(cfg), mesh)
    ub = probe_uniq_bucket(cfg, [str(data)])
    tel = RunTelemetry(str(tmp_path / "m.jsonl"), meta={"kind": "t"})
    with activate(tel):
        it = batch_iterator(cfg, [str(data)], training=False, epochs=1,
                            fixed_shape=True, uniq_bucket=ub)
        n = sum(b.num_real for b, _ in lockstep_score_batches(
            cfg, it, mesh, score_fn, table, ub))
    snap = tel.registry.snapshot()["counters"]
    assert n == 40
    assert snap["lockstep/examples"] == 40
    assert snap["lockstep/real_batches"] == 5
    assert snap["lockstep/filler_batches"] == 0  # one process, no peers
    assert snap["lockstep/windows"] >= 1
    # the cross-check invariant: real + filler == collective programs
    assert (snap["lockstep/real_batches"]
            + snap["lockstep/filler_batches"]
            == snap["lockstep/programs"])
    # the pipeline wrapper fed batch counters on the same stream
    assert snap["pipeline/batches"] == 5
    tel.close()


