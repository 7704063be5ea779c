"""Closed-loop SLO observability (README "SLOs & quality gate"):
the per-publish quality collector's math, the publish gate's decision
contract (first-publish min-AUC-only, NaN holds, broadcast-identical
across workers), the gate/retention interaction (a held step later
GC'd leaves the pointer valid), the declarative SLO spec + evaluator +
`fmstat slo` CLI, the Prometheus exposition format, `fmstat --follow`,
and the GATE-HELD verdict's place in the severity ladder."""

import io
import json
import math
import os

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.obs.quality import (LOGLOSS_EPS, PublishGate,
                                       QualityStats)
from fast_tffm_tpu.obs.slo import (SloSpec, evaluate_slos, overall,
                                   render_slo)


# --- QualityStats math -----------------------------------------------------


def _sigmoid(s):
    return 1.0 / (1.0 + np.exp(-np.asarray(s, np.float64)))


def test_quality_stats_logistic_math():
    s = np.array([0.0, 2.0, -1.0])
    y = np.array([0.0, 1.0, 1.0])
    w = np.array([1.0, 2.0, 0.5])
    q = QualityStats("logistic")
    q.update(s, y, w)
    p = np.clip(_sigmoid(s), LOGLOSS_EPS, 1 - LOGLOSS_EPS)
    loss = -(y * np.log(p) + (1 - y) * np.log(1 - p))
    assert q.loss == pytest.approx((w * loss).sum() / w.sum())
    assert q.calibration == pytest.approx(
        (w * _sigmoid(s)).sum() / (w * y).sum())


def test_quality_stats_mse_math():
    s = np.array([0.2, 0.9])
    y = np.array([0.0, 1.0])
    w = np.ones(2)
    q = QualityStats("mse")
    q.update(s, y, w)
    assert q.loss == pytest.approx(((s - y) ** 2).mean())
    # mse calibration: raw score mass over label mass
    assert q.calibration == pytest.approx(s.sum() / y.sum())


def test_quality_stats_empty_and_no_positives():
    q = QualityStats()
    assert q.loss is None and q.calibration is None
    q.update(np.array([1.0]), np.array([0.0]), np.array([1.0]))
    assert q.loss is not None
    assert q.calibration is None  # zero label mass: undefined, not inf


def test_quality_stats_sums_roundtrip_and_incremental():
    a = QualityStats("logistic")
    b = QualityStats("logistic")
    rng = np.random.default_rng(7)
    s = rng.normal(size=40)
    y = (rng.uniform(size=40) < 0.5).astype(float)
    w = rng.uniform(0.5, 2.0, size=40)
    a.update(s, y, w)
    for i in range(0, 40, 7):  # chunked feeding matches one-shot
        b.update(s[i:i + 7], y[i:i + 7], w[i:i + 7])
    assert b.sums() == pytest.approx(a.sums())
    c = QualityStats("logistic")
    c.load_sums(a.sums())
    assert c.loss == a.loss and c.calibration == a.calibration
    with pytest.raises(ValueError):
        c.load_sums(np.zeros(3))


def test_quality_sums_survive_hi_lo_float32_transit():
    """The lockstep merge ships every f64 as a (hi, lo) float32 pair
    (train.evaluate_distributed); the quality sums ride the same
    payload, so they must reconstruct through that transit."""
    q = QualityStats()
    q.update(np.full(1000, 3.3), np.ones(1000), np.full(1000, 1.7))
    payload = q.sums()
    hi = payload.astype(np.float32)
    lo = (payload - hi.astype(np.float64)).astype(np.float32)
    back = hi.astype(np.float64) + lo.astype(np.float64)
    assert back == pytest.approx(payload, rel=1e-12)


# --- evaluate(collect=) rides the existing sweep ---------------------------


def _eval_cfg(tmp_path, **over):
    base = dict(vocabulary_size=100, factor_num=4, batch_size=16,
                epoch_num=1, learning_rate=0.1, shuffle=False, seed=0,
                log_steps=0,
                train_files=(os.path.join(str(tmp_path), "t.txt"),),
                model_file=os.path.join(str(tmp_path), "model", "fm"))
    base.update(over)
    return FmConfig(**base)


def _write_lines(path, n, seed=0, vocab=100):
    rng = np.random.default_rng(seed)
    labels = []
    with open(path, "w") as fh:
        for _ in range(n):
            y = int(rng.integers(0, 2))
            labels.append(y)
            feats = sorted(rng.choice(vocab, size=3, replace=False))
            fh.write(f"{y} " + " ".join(f"{i}:1.0" for i in feats)
                     + "\n")
    return np.asarray(labels, np.float64)


def test_evaluate_collect_matches_manual_sweep(tmp_path):
    """The collector consumes the SAME score chunks the AUC update
    does: loss/calibration from evaluate(collect=) must equal the
    values computed from an independent scoring pass, and the returned
    AUC must be unchanged by the collector's presence."""
    from fast_tffm_tpu.data.pipeline import batch_iterator
    from fast_tffm_tpu.models.fm import (ModelSpec, batch_args,
                                         init_table,
                                         make_batch_scorer)
    from fast_tffm_tpu.train import evaluate
    cfg = _eval_cfg(tmp_path)
    labels = _write_lines(cfg.train_files[0], 60, seed=5)
    table = init_table(cfg, 0)
    stats = QualityStats(cfg.loss_type)
    auc_c, n = evaluate(cfg, table, cfg.train_files, collect=stats)
    auc_plain, _ = evaluate(cfg, table, cfg.train_files)
    assert n == 60 and auc_c == auc_plain
    score_fn = make_batch_scorer(ModelSpec.from_config(cfg))
    chunks = []
    for b in batch_iterator(cfg, cfg.train_files, training=False,
                            epochs=1):
        args = batch_args(b)
        args.pop("labels"), args.pop("weights")
        chunks.append(np.asarray(score_fn(table, args))[:b.num_real])
    scores = np.concatenate(chunks).astype(np.float64)
    want = QualityStats(cfg.loss_type)
    want.update(scores, labels, np.ones_like(labels))
    assert stats.sums() == pytest.approx(want.sums(), rel=1e-9)


# --- PublishGate decision contract ----------------------------------------


def test_gate_first_publish_uses_min_auc_only():
    g = PublishGate(min_auc=0.8, max_drop=0.05)
    # No baseline yet: only the absolute floor applies.
    d = g.decide(0.82, step=10)
    assert not d["held"] and d["baseline"] is None
    d = g.decide(0.7, step=10)
    assert d["held"] and "publish_min_auc" in d["reasons"][0]
    # Baseline only moves on note_published, never on decide.
    assert g.baseline is None


def test_gate_drop_vs_last_published():
    g = PublishGate(min_auc=0.0, max_drop=0.05)
    d0 = g.decide(0.9, step=1)
    assert not d0["held"]  # no baseline, no min floor: passes
    g.note_published(0.9)
    assert not g.decide(0.86, step=2)["held"]  # within the budget
    d = g.decide(0.84, step=3)
    assert d["held"] and "dropped" in d["reasons"][0]
    # A held decision never becomes the baseline; recovery is judged
    # against the last PUBLISHED AUC.
    assert g.baseline == 0.9
    assert not g.decide(0.89, step=4)["held"]


def test_gate_nan_auc_holds_configured_gate():
    g = PublishGate(min_auc=0.5)
    assert g.decide(float("nan"), step=1)["held"]
    g2 = PublishGate(max_drop=0.1)
    g2.note_published(0.9)
    assert g2.decide(float("nan"), step=1)["held"]
    # NaN never becomes a baseline (it would disarm the drop check).
    g2.note_published(float("nan"))
    assert g2.baseline == 0.9
    # The sharp corner: a max_drop-ONLY gate on its very FIRST publish
    # (no baseline, no min floor) — neither threshold comparison fires,
    # but an unevaluable model must still hold a configured gate.
    g3 = PublishGate(max_drop=0.1)
    d = g3.decide(float("nan"), step=1)
    assert d["held"] and "unevaluable" in d["reasons"][0]
    assert not g3.decide(0.8, step=2)["held"]  # a real AUC still passes


def test_gate_baseline_persists_beside_pointer(tmp_path):
    """The drop baseline survives a restart: it is written beside the
    `published` pointer on each successful publish and a fresh gate
    re-arms from it — a preempt-resume must not exempt its first
    publish from publish_max_auc_drop."""
    from fast_tffm_tpu.checkpoint import (read_gate_baseline,
                                          write_gate_baseline)
    d = str(tmp_path)
    assert read_gate_baseline(d) is None  # pre-first-publish state
    write_gate_baseline(d, 0.912345)
    assert read_gate_baseline(d) == pytest.approx(0.912345)
    # A resumed gate armed from the file holds a post-restart drop.
    g = PublishGate(max_drop=0.05)
    g.note_published(read_gate_baseline(d))
    assert g.decide(0.80, step=9)["held"]
    assert not g.decide(0.88, step=9)["held"]
    # Garbled file degrades to the baseline-free first-publish state,
    # never a crash.
    (tmp_path / "gate_baseline").write_text("not a float\n")
    assert read_gate_baseline(d) is None


def test_gate_from_config():
    assert PublishGate.from_config(FmConfig()) is None
    cfg = FmConfig(run_mode="stream", stream_dir="/tmp/x",
                   publish_interval_seconds=1.0,
                   validation_files=("v.txt",), publish_min_auc=0.6)
    g = PublishGate.from_config(cfg)
    assert g is not None and g.min_auc == 0.6


def test_gate_config_requires_stream_validation_publishing():
    with pytest.raises(ValueError, match="validation_files"):
        FmConfig(run_mode="stream", stream_dir="/tmp/x",
                 publish_interval_seconds=1.0, publish_min_auc=0.5)
    with pytest.raises(ValueError, match="run_mode = stream"):
        FmConfig(publish_min_auc=0.5,
                 validation_files=("v.txt",))
    with pytest.raises(ValueError, match="publish_interval_seconds"):
        FmConfig(run_mode="stream", stream_dir="/tmp/x",
                 validation_files=("v.txt",),
                 publish_max_auc_drop=0.1)


def test_publish_quality_eval_knob_validation():
    # off conflicts with a configured gate (the gate IS the sweep).
    with pytest.raises(ValueError, match="publish_quality_eval"):
        FmConfig(run_mode="stream", stream_dir="/tmp/x",
                 publish_interval_seconds=1.0,
                 validation_files=("v.txt",), publish_min_auc=0.5,
                 publish_quality_eval="off")
    # on needs somewhere (and some cadence) to sweep.
    with pytest.raises(ValueError, match="publish_quality_eval = on"):
        FmConfig(publish_quality_eval="on")
    with pytest.raises(ValueError, match="unknown publish_quality_eval"):
        FmConfig(publish_quality_eval="sometimes")
    # auto + gate / on + stream corpus are both legal.
    FmConfig(run_mode="stream", stream_dir="/tmp/x",
             publish_interval_seconds=1.0,
             validation_files=("v.txt",), publish_min_auc=0.5)
    FmConfig(run_mode="stream", stream_dir="/tmp/x",
             publish_interval_seconds=1.0,
             validation_files=("v.txt",), publish_quality_eval="on")


def test_gate_decisions_broadcast_identical_across_workers():
    """The multi-host contract: the chief's decision dict survives the
    JSON wire (broadcast_blob) byte-exactly, a follower applying the
    wire decision stays in lockstep with the chief through a
    pass/hold/recover sequence, and the single-process broadcast is
    the identity."""
    from fast_tffm_tpu.data.stream import broadcast_blob
    chief = PublishGate(min_auc=0.6, max_drop=0.1)
    follower = PublishGate(min_auc=0.6, max_drop=0.1)
    for step, auc in enumerate([0.9, 0.85, 0.3, 0.88, 0.7]):
        d = chief.decide(auc, step)
        # identity when process_count == 1 — the same call sites run
        # unchanged in single-process mode
        assert broadcast_blob(d, "test/gate") is d
        wire = json.loads(json.dumps(d))
        assert wire == d  # JSON-safe: what the chief decides is what
        # every worker receives
        assert follower.decide(auc, step) == d  # deterministic too
        if not wire["held"]:
            chief.note_published(d["auc"])
            follower.note_published(wire["auc"])
        assert follower.baseline == chief.baseline
    # The poisoned step (0.3) held on both checks; recovery at 0.88
    # passed against the 0.85 baseline; 0.7 holds again.
    assert chief.decide(0.7, 9)["held"]


# --- gate + retention + walk-back interaction ------------------------------


def test_held_step_gcd_pointer_still_valid(tmp_path):
    """A held step is saved (by periodic saves) but never published;
    once recovery publishes a newer step, retention GC eventually
    deletes the held step — and the published pointer must still name
    a live, verifiable step, with the quarantine walk-back restoring
    past the torn newest step without ever touching the pointer."""
    import jax
    from fast_tffm_tpu.checkpoint import (CheckpointState,
                                          list_step_dirs,
                                          read_published,
                                          verify_step_dir)
    from fast_tffm_tpu.models.fm import init_accumulator, init_table
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    from fast_tffm_tpu.checkpoint import checkpoint_template, ckpt_state
    cfg = _eval_cfg(tmp_path, vocabulary_size=50, factor_num=2)
    model = cfg.model_file
    ckpt = CheckpointState(model, max_to_keep=3, verify="size")

    def save(step):
        t = init_table(cfg, step)
        a = init_accumulator(cfg)
        ckpt.save(step, *ckpt_state(cfg, t, a),
                  vocabulary_size=cfg.vocabulary_size, wait=True)

    save(1)
    assert ckpt.publish_step(1) is not None          # good publish
    save(2)                                          # HELD: no publish
    save(3)
    assert ckpt.publish_step(3) is not None          # recovery publish
    save(4)                                          # GCs step 1
    save(5)                                          # GCs held step 2
    ckpt.close()
    steps = list_step_dirs(model + ".ckpt")
    assert 2 not in steps, steps                     # held step GC'd
    assert read_published(model + ".ckpt") == 3      # pointer valid...
    assert 3 in steps
    assert verify_step_dir(model + ".ckpt", 3, "size") is None
    # ...and the walk-back path is unaffected: tear the newest step,
    # restore quarantines it and lands on step 4 — the pointer never
    # moves off 3.
    assert truncate_checkpoint(model, seed=0)
    ckpt2 = CheckpointState(model, max_to_keep=3, verify="size")
    restored = ckpt2.restore(template=checkpoint_template(cfg))
    ckpt2.close()
    assert restored is not None and int(restored["step"]) == 4
    assert read_published(model + ".ckpt") == 3
    assert verify_step_dir(model + ".ckpt", 3, "size") is None
    del jax  # imported for the device backend side effect only


def test_gate_hold_pauses_retention_and_final_save_spares_pointer(
        tmp_path):
    """The hold/retention interplay end-to-end through the real CLI
    (the slo-soak runs without save_steps, so this is the one test
    that executes the risk arm, the periodic-save pause, and the
    margin=2 reserve): with save_steps minting checkpoints while a
    poisoned burst holds the gate, periodic saves must PAUSE (the
    logged warning) and the mandatory final save on STOP — taken while
    still holding, so the exit publish is skipped too — must NOT evict
    the published last-good step."""
    import subprocess
    import sys
    import time as _time
    from fast_tffm_tpu.checkpoint import (read_published,
                                          verify_step_dir)
    from tools.fmchaos import _corpus_lines, _write_corpus
    wd = str(tmp_path)
    sd = os.path.join(wd, "stream")
    os.makedirs(sd)
    val = os.path.join(wd, "val.txt")
    _write_corpus(val, 200, 1)
    shard_i = [0]

    def write_shard(lines):
        p = os.path.join(sd, f"part-{shard_i[0]:03d}.txt")
        shard_i[0] += 1
        with open(p, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        open(p + ".done", "w").close()

    def flip(line):
        y, rest = line.split(" ", 1)
        return f"{1 - int(y)} {rest}"

    write_shard(_corpus_lines(400, 0))
    cfg_path = os.path.join(wd, "gate.cfg")
    model = os.path.join(wd, "model", "fm")
    log = os.path.join(wd, "trainer.log")
    with open(cfg_path, "w") as fh:
        fh.write(f"""
[General]
vocabulary_size = 200
factor_num = 4
model_file = {model}
log_file = {log}

[Train]
run_mode = stream
stream_dir = {sd}
stream_poll_seconds = 0.05
seal_policy = done
shuffle = false
epoch_num = 1
batch_size = 32
learning_rate = 0.1
log_steps = 0
save_steps = 3
metrics_file = {os.path.join(wd, 'metrics.jsonl')}
metrics_flush_steps = 2
publish_interval_seconds = 0.2
publish_min_auc = 0.7
validation_files = {val}
""")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out_path = os.path.join(wd, "trainer.out")
    ckpt_dir = model + ".ckpt"
    with open(out_path, "w") as out:
        proc = subprocess.Popen(
            [sys.executable, "run_tffm.py", "train", cfg_path],
            cwd=repo, env=env, stdout=out, stderr=subprocess.STDOUT)
    try:
        def tail():
            try:
                return open(out_path).read()[-3000:]
            except OSError:
                return "<no output>"

        def wait_for(fn, what, deadline_s=150.0):
            deadline = _time.monotonic() + deadline_s
            while not fn():
                assert proc.poll() is None, (
                    f"trainer exited before {what}:\n{tail()}")
                assert _time.monotonic() < deadline, (
                    f"timed out waiting for {what}\n{tail()}")
                _time.sleep(0.02)

        wait_for(lambda: read_published(ckpt_dir) is not None,
                 "first publish")
        write_shard([flip(ln) for ln in _corpus_lines(1600, 3)])
        wait_for(lambda: "GATE HELD" in tail(), "gate hold")
        # More poisoned steps while holding: periodic saves keep
        # attempting, and the pause must kick in before retention can
        # touch the published step.
        write_shard([flip(ln) for ln in _corpus_lines(1600, 4)])
        wait_for(lambda: "pausing periodic saves" in tail(),
                 "retention pause")
        pub = read_published(ckpt_dir)
        open(os.path.join(sd, "STOP"), "w").close()
        assert proc.wait(timeout=150) == 0, tail()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    # Still holding at exit: the exit publish was skipped...
    text = open(out_path).read()
    assert "exit publish skipped" not in text  # (no preemption here)
    assert read_published(ckpt_dir) == pub
    # ...and the mandatory final save did NOT evict the last-good
    # step: the pointer names a live, integrity-passing checkpoint.
    assert os.path.isdir(os.path.join(ckpt_dir, str(pub))), (
        f"published step {pub} was GC'd by the final save:\n"
        f"{sorted(os.listdir(ckpt_dir))}")
    assert verify_step_dir(ckpt_dir, pub, "size") is None


# --- SloSpec + evaluator ---------------------------------------------------


def test_slo_spec_config_gauges_roundtrip():
    from fast_tffm_tpu.obs.registry import MetricsRegistry
    cfg = FmConfig(slo_publish_staleness_seconds=30.0, slo_p99_ms=250.0,
                   slo_min_auc=0.8, slo_max_bad_fraction=0.01)
    spec = SloSpec.from_config(cfg)
    assert not spec.empty
    reg = MetricsRegistry()
    spec.emit_gauges(reg)
    g = reg.snapshot()["gauges"]
    assert g == {"slo/publish_staleness_seconds": 30.0,
                 "slo/p99_ms": 250.0, "slo/min_auc": 0.8,
                 "slo/max_bad_fraction": 0.01}
    assert SloSpec.from_summary({"gauges": g}) == spec
    # Unset objectives emit nothing: absence IS the unset marker.
    reg2 = MetricsRegistry()
    SloSpec.from_config(FmConfig()).emit_gauges(reg2)
    assert reg2.snapshot()["gauges"] == {}
    assert SloSpec.from_config(FmConfig()).empty


def _summary(gauges=None, counters=None, hists=None):
    return {"gauges": gauges or {}, "counters": counters or {},
            "hists": hists or {}}


def test_evaluate_slos_pass_fail_skip():
    spec = SloSpec(publish_staleness_seconds=5.0, p99_ms=100.0,
                   min_auc=0.8, max_bad_fraction=0.01)
    rows = evaluate_slos(spec, _summary(
        gauges={"stream/last_publish_age_seconds": 2.0,
                "quality/auc": 0.9},
        counters={"train/examples": 980.0,
                  "pipeline/bad_lines": 20.0},
        hists={"serve/request_latency_ms": {"p99": 42.0}}))
    by = {r.objective: r for r in rows}
    assert len(rows) == 4
    assert by["publish staleness"].status == "PASS"
    assert by["serve latency p99"].status == "PASS"
    assert by["validation AUC"].status == "PASS"
    assert by["bad-line fraction"].status == "FAIL"  # 20/1000 > 0.01
    assert by["bad-line fraction"].measured == pytest.approx(0.02,
                                                             abs=1e-6)
    assert overall(rows) == "FAIL"
    # Missing data is SKIP, never a silent pass.
    rows2 = evaluate_slos(spec, _summary())
    assert {r.status for r in rows2} == {"SKIP"}
    assert overall(rows2) == "PASS"  # nothing FAILED; table shows SKIP
    # NaN quality FAILS a quality bound.
    rows3 = evaluate_slos(SloSpec(min_auc=0.5), _summary(
        gauges={"quality/auc": float("nan")}))
    assert rows3[0].status == "FAIL"
    # An unset spec evaluates nothing.
    assert evaluate_slos(SloSpec(), _summary()) == []
    assert overall([]) == "EMPTY"


def test_bad_fraction_prefers_train_examples_denominator():
    """A gated stream sweeps validation at EVERY publish, inflating
    pipeline/examples; the bad-fraction denominator must be the
    TRAINED stream, or repeated sweeps dilute a real violation."""
    from fast_tffm_tpu.obs.slo import measured_bad_fraction
    m = measured_bad_fraction(_summary(counters={
        "pipeline/bad_lines": 10.0,
        "train/examples": 990.0,
        "pipeline/examples": 990.0 + 200 * 240.0,  # + 200 sweeps
    }))
    assert m == pytest.approx(0.01)
    # Streams without a train loop (predict-only) fall back to the
    # pipeline counter rather than SKIPping.
    m2 = measured_bad_fraction(_summary(counters={
        "pipeline/bad_lines": 1.0, "pipeline/examples": 99.0}))
    assert m2 == pytest.approx(0.01)
    assert measured_bad_fraction(_summary()) is None


def test_slo_auc_fallback_to_validation_gauge():
    spec = SloSpec(min_auc=0.5)
    rows = evaluate_slos(spec, _summary(
        gauges={"validation/auc": 0.7}))
    assert rows[0].status == "PASS" and rows[0].measured == 0.7
    # quality/auc wins when both exist (the fresher publish-time gauge)
    rows = evaluate_slos(spec, _summary(
        gauges={"validation/auc": 0.7, "quality/auc": 0.4}))
    assert rows[0].status == "FAIL" and rows[0].measured == 0.4


def test_render_slo_table_and_empty():
    spec = SloSpec(min_auc=0.8)
    rows = evaluate_slos(spec, _summary(gauges={"quality/auc": 0.9}))
    text = render_slo(spec, rows)
    assert "validation AUC" in text and ">= 0.8" in text
    assert "PASS" in text and "overall: PASS" in text
    assert "no SLO objectives configured" in render_slo(SloSpec(), [])


def _write_metrics(path, gauges=(), counters=(), latencies=()):
    from fast_tffm_tpu.obs.registry import MetricsRegistry
    from fast_tffm_tpu.obs.sink import JsonlSink
    from fast_tffm_tpu.serve.server import LATENCY_BUCKETS_MS
    reg = MetricsRegistry()
    for k, v in dict(gauges).items():
        reg.set(k, v)
    for k, v in dict(counters).items():
        reg.count(k, v)
    for v in latencies:
        reg.observe("serve/request_latency_ms", v,
                    bounds=LATENCY_BUCKETS_MS)
    sink = JsonlSink(str(path))
    sink.emit_metrics(10, reg.snapshot())
    sink.close()


def test_fmstat_slo_cli(tmp_path, capsys):
    from tools.fmstat import main as fmstat_main
    m = tmp_path / "m.jsonl"
    _write_metrics(
        m,
        gauges={"slo/publish_staleness_seconds": 30.0,
                "slo/p99_ms": 500.0, "slo/min_auc": 0.8,
                "slo/max_bad_fraction": 0.01,
                "stream/last_publish_age_seconds": 1.5,
                "quality/auc": 0.93},
        counters={"pipeline/examples": 1000.0},
        latencies=[3.0, 4.0, 120.0])
    assert fmstat_main(["slo", str(m), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["overall"] == "PASS"
    assert len(out["objectives"]) == 4
    assert out["spec"]["min_auc"] == 0.8
    assert "health" in out
    # Human table form.
    assert fmstat_main(["slo", str(m)]) == 0
    text = capsys.readouterr().out
    assert "overall: PASS" in text and "health:" in text
    # A failing objective exits 1 — the scriptable deployment check.
    bad = tmp_path / "bad.jsonl"
    _write_metrics(bad, gauges={"slo/min_auc": 0.8,
                                "quality/auc": 0.5})
    assert fmstat_main(["slo", str(bad)]) == 1
    # A DECLARED objective with no supporting data exits 2 (not 0): a
    # monitor must not read green when the measuring shard went
    # missing. --allow-skip opts back into 0 for split-stream setups.
    skipped = tmp_path / "skip.jsonl"
    _write_metrics(skipped, gauges={"slo/p99_ms": 100.0})
    assert fmstat_main(["slo", str(skipped)]) == 2
    assert fmstat_main(["slo", str(skipped), "--allow-skip"]) == 0
    # A stream with NO slo/* gauges at all (rotated/truncated metrics
    # file) is the silent-green hazard in its purest form: exit 2.
    empty = tmp_path / "empty.jsonl"
    _write_metrics(empty, counters={"train/examples": 10.0})
    assert fmstat_main(["slo", str(empty)]) == 2
    assert fmstat_main(["slo", str(empty), "--allow-skip"]) == 0
    capsys.readouterr()


def test_fmstat_slo_cli_config_spec(tmp_path, capsys):
    """--config reads the spec from a config file instead of the
    stream's gauges — evaluating yesterday's stream against today's
    objectives."""
    from tools.fmstat import main as fmstat_main
    m = tmp_path / "m.jsonl"
    _write_metrics(m, gauges={"quality/auc": 0.75})
    cfgp = tmp_path / "slo.cfg"
    cfgp.write_text("[SLO]\nslo_min_auc = 0.9\n")
    assert fmstat_main(["slo", str(m), "--config", str(cfgp)]) == 1
    capsys.readouterr()


# --- Prometheus exposition -------------------------------------------------


def test_prometheus_text_format_pin():
    from fast_tffm_tpu.obs.prom import metric_name, prometheus_text
    from fast_tffm_tpu.obs.registry import MetricsRegistry
    assert metric_name("serve/request_latency_ms") == \
        "fm_serve_request_latency_ms"
    assert metric_name("a-b.c d") == "fm_a_b_c_d"
    reg = MetricsRegistry()
    reg.count("serve/requests", 3)
    reg.set("serve/served_step", 41.0)
    for v in (0.6, 1.5, 1.5):
        reg.observe("serve/queue_depth", v, bounds=(1.0, 2.0))
    text = prometheus_text(reg.snapshot())
    assert text == (
        "# TYPE fm_serve_requests counter\n"
        "fm_serve_requests 3\n"
        "# TYPE fm_serve_served_step gauge\n"
        "fm_serve_served_step 41\n"
        "# TYPE fm_serve_queue_depth histogram\n"
        'fm_serve_queue_depth_bucket{le="1"} 1\n'
        'fm_serve_queue_depth_bucket{le="2"} 3\n'
        'fm_serve_queue_depth_bucket{le="+Inf"} 3\n'
        "fm_serve_queue_depth_sum 3.6\n"
        "fm_serve_queue_depth_count 3\n")


def test_prometheus_nonfinite_and_float_values():
    from fast_tffm_tpu.obs.prom import prometheus_text
    text = prometheus_text({"counters": {},
                            "gauges": {"g/nan": float("nan"),
                                       "g/inf": float("inf"),
                                       "g/f": 0.25},
                            "hists": {}})
    assert "fm_g_nan NaN" in text
    assert "fm_g_inf +Inf" in text
    assert "fm_g_f 0.25" in text


# --- fmstat --follow -------------------------------------------------------


def test_fmstat_follow_renders_and_tolerates_missing(tmp_path):
    from tools.fmstat import _follow
    m = tmp_path / "live.jsonl"
    out = io.StringIO()
    # Nothing there yet: the watch loop waits instead of dying.
    _follow([str(m)], interval=0.0, out=out, iterations=1)
    assert "waiting for" in out.getvalue()
    _write_metrics(m, counters={"train/examples": 64.0,
                                "train/steps": 2.0})
    out2 = io.StringIO()
    _follow([str(tmp_path / "live.jsonl*")], interval=0.0, out=out2,
            iterations=2)
    body = out2.getvalue()
    assert body.count("-- fmstat --follow") == 2
    assert "verdict:" in body and "examples" in body


# --- GATE-HELD in the verdict ladder --------------------------------------


def _verdict_summary(health=(), crash=(), gauges=None, counters=None,
                     run_ends=1):
    return {"meta": {}, "metas": [], "runs": 1, "events": 1,
            "spans": 0, "run_starts": 1, "run_ends": run_ends,
            "health_events": list(health), "crash_events": list(crash),
            "counters": counters or {}, "hists": {},
            "gauges": gauges or {}, "gauges_by_process": {},
            "scalars": []}


_HOLD = {"status": "gate_held", "step": 75, "auc": 0.1,
         "reasons": ["AUC 0.1 below publish_min_auc 0.7"]}


def test_gate_held_verdict_and_ranking():
    from fast_tffm_tpu.obs.attribution import health_verdict
    hv = health_verdict(_verdict_summary(health=[_HOLD]))
    assert hv["verdict"] == "GATE-HELD (x1)"
    assert "step 75" in hv["detail"]
    # Severity ladder: CRASHED / STALLED outrank a hold...
    hv = health_verdict(_verdict_summary(
        health=[_HOLD], crash=[{"error": "boom"}]))
    assert hv["verdict"] == "CRASHED"
    hv = health_verdict(_verdict_summary(
        health=[_HOLD, {"status": "stalled", "stalled_seconds": 9,
                        "stacks_file": "x"}]))
    assert hv["verdict"] == "STALLED"
    # ...but a hold outranks (and usually explains) STALE PUBLISH.
    hv = health_verdict(_verdict_summary(
        health=[_HOLD],
        gauges={"stream/publish_interval_seconds": 1.0,
                "stream/last_publish_age_seconds": 100.0}))
    assert hv["verdict"] == "GATE-HELD (x1)"


def test_health_notes_for_informational_kinds():
    from fast_tffm_tpu.obs.attribution import health_verdict
    hv = health_verdict(_verdict_summary(
        health=[{"status": "bad_input", "file": "x", "count": 3},
                {"status": "collective_slow"},
                {"status": "some_future_kind"}]))
    assert hv["verdict"] == "OK"
    assert "bad_input" in hv["detail"]
    assert "collective_slow" in hv["detail"]
    assert "some_future_kind" in hv["detail"]  # unrecognized → loud


def test_quality_section_renders():
    from fast_tffm_tpu.obs.attribution import attribution, render
    s = _verdict_summary(
        counters={"quality/evals": 4.0, "quality/eval_seconds": 0.4,
                  "quality/examples": 960.0,
                  "quality/gate_held": 1.0},
        gauges={"quality/auc": 0.91, "quality/loss": 0.33,
                "quality/calibration": 1.02})
    att = attribution(s)
    assert att["quality_evals"] == 4.0
    assert att["quality_auc"] == 0.91
    text = render(s)
    assert "QUALITY (per-publish eval + gate)" in text
    assert "publishes gate-held" in text
    # And absent on a stream that never ran the loop.
    assert "QUALITY" not in render(_verdict_summary())


def test_math_isnan_guard_in_results():
    """evaluate_slos treats NaN measurements as failures without
    raising — the comparison path must be explicit, not coincidental."""
    rows = evaluate_slos(SloSpec(p99_ms=10.0), _summary(
        hists={"serve/request_latency_ms": {"p99": float("nan")}}))
    assert rows[0].status == "FAIL"
    assert math.isnan(rows[0].measured)
