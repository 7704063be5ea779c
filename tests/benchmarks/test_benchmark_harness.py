"""Fast CPU tests of the benchmark's own code (benchmarks/): the
reading rule, the corpus and weights the reference shares with no one,
the reference against the program's arithmetic, each model family's file
against finite differences, the one description of the model, the
control that must fail, the harness taking a new cell and a new model
family as files, and the last line. (What BENCHMARK.json must hold is
test_benchmark_json.py's.)

Nothing here describes a TPU topology; subprocess runs see one CPU
device (the one-chip path), in-process runs see conftest's eight (the
mesh path)."""

import hashlib
import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import tiny_tree
from benchmarks import (check, control, corpus, harness, peaks, readings,
                        reference, trace_reduce, weights)
from benchmarks.drivers import predict as predict_driver
from benchmarks.drivers import train as train_driver

REPO = tiny_tree.REPO
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "check"}


@pytest.fixture(autouse=True)
def _work_root_of_its_own(tmp_path, monkeypatch):
    """In-process runs of one cell from several test workers at once
    would share <checkout>/.bench_work/<cell>, and each run empties
    it first."""
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path / "work"))


# ---- the reading rule ------------------------------------------------

def _syncs(times, per=8):
    return [(t, i * per) for i, t in enumerate(times)]


def test_readings_cut_by_the_window_edges_are_dropped():
    rd = readings.readings_between_syncs(_syncs([0, 1, 2, 3, 4, 5, 6]))
    r = readings.rate_from_readings(rd, start=0.5, end=5.5)
    assert r["n"] == 4 and r["dropped"] == 2 and r["span"] == (1, 5)
    assert r["median"] == r["rate"] == pytest.approx(8.0)


def test_the_span_is_whole_cycles_and_the_rate_keeps_the_barriers():
    """An epoch of 3 readings whose last carries a 0.5 s barrier: the
    span is whole epochs wherever the window's edge falls, all work
    over all time (the end-to-end rate) holds every barrier, and the
    median reading (the per-layer steady_rate) sees the steady step."""
    t, times = 10.0, [10.0]
    for i in range(11):
        t += 1.5 if i % 3 == 2 else 1.0
        times.append(t)
    rd = readings.readings_between_syncs(_syncs(times))
    for end in (18.2, 19.9, 20.4):            # 7 or 8 readings inside
        r = readings.rate_from_readings(rd, 10.0, end, cycle=3)
        assert r["n"] == 6 and r["span"] == (10.0, 17.0)
        assert r["rate"] == pytest.approx(6 * 8 / 7.0)
        assert r["median"] == pytest.approx(8.0)
    with pytest.raises(ValueError, match="a cycle is 3"):
        readings.rate_from_readings(rd, 10.0, 12.5, cycle=3)


def test_a_stall_moves_the_rate_and_not_the_median():
    t, times = 0.0, [0.0]
    for i in range(15):
        t += 3.0 if i == 7 else 1.0          # one reading stalls 2 s
        times.append(t)
    rd = readings.readings_between_syncs(_syncs(times))
    r = readings.rate_from_readings(rd, 0.0, t)
    # the end-to-end rate is all work over all time: the stall is in it
    assert r["rate"] == pytest.approx(15 * 8 / 17.0)
    # the per-layer steady_rate is the pace between stalls
    assert r["median"] == pytest.approx(8.0)


def test_end_to_end_values_are_the_drivers_by_name(tiny_root):
    """The driver gives its end-to-end values by name; the harness
    adds setup_s and refuses a cell's metric nobody gave."""
    run = _run_for(harness.load_cell("tiny-train", tiny_root))
    run.setup["setup_s"] = 3.25
    line = json.loads(harness.finish(
        run, {"platform": "cpu", "kind": "cpu", "count": 1},
        {train_driver.E2E_RATE: 1234.5}, [], 0.0, attempted=8, failed=0,
        tracer=None, ctx={}))
    assert line["metrics"] == {} and line["correct"] is True  # rehearsal
    with pytest.raises(harness.RunFailed, match="gave no value"):
        harness.finish(run, {"platform": "cpu", "kind": "cpu", "count": 1},
                       {predict_driver.E2E_RATE: 1.0}, [], 0.0, 8, 0,
                       None, {})


def test_too_few_readings_or_a_clock_that_stands_still_raise():
    rd = readings.readings_between_syncs(_syncs([0, 1, 2]))
    with pytest.raises(ValueError, match="readings lie wholly inside"):
        readings.rate_from_readings(rd, 0, 2)
    with pytest.raises(ValueError, match="must advance"):
        readings.readings_between_syncs([(1.0, 8), (1.0, 16)])


def _record(msg, *args):
    return logging.LogRecord("fast_tffm_tpu", logging.INFO, __file__, 1,
                             msg, args, None)


def _run_for(cell, seconds=1.0, trace=False):
    import time
    return harness.Run(cell=cell, seed=7, seconds=seconds, trace=trace,
                       rehearse=True, t0=time.monotonic())


def test_deferred_loss_lines_fail_the_run(tiny_root):
    h = train_driver.SyncHandler(
        _run_for(harness.load_cell("tiny-train", tiny_root)), 4)
    with pytest.raises(harness.RunFailed, match="deferred"):
        h.emit(_record("scalar fetch costs %.0f ms on this device link; "
                       "deferring loss log lines to epoch boundaries", 9.0))


def test_window_opens_after_warmup_and_closes_on_a_sync_point(
        tiny_root, monkeypatch):
    now = [100.0]
    monkeypatch.setattr(train_driver.time, "monotonic", lambda: now[0])
    run = _run_for(harness.load_cell("tiny-train", tiny_root), seconds=2.0)
    h = train_driver.SyncHandler(run, warmup_steps=8)
    line = "step %d epoch %d loss %.6f examples/sec %.0f"
    for step in (4, 8, 12, 16):
        h.emit(_record(line, step, 0, 0.69, 1.0))
        h.emit(_record("some other line"))
        now[0] += 0.9
    assert h.t_start == pytest.approx(100.9)          # the step-8 line
    with pytest.raises(train_driver.WindowClosed):
        h.emit(_record(line, 20, 0, 0.69, 1.0))       # 103.6 >= 102.9
    assert [s for _, s, _ in h.syncs] == [4, 8, 12, 16, 20]


# ---- peaks and the roofline arithmetic -------------------------------

def test_roofline_share_by_hand():
    # 300,000 distinct rows of 17 f32, table + accumulator read and
    # written: 300000*17*4*4 = 81.6 MB, plus a 4.26 MB batch.
    b = peaks.train_step_min_bytes(300_000, 17, 8192 * 520)
    assert b == 300_000 * 17 * 16 + 8192 * 520 == 85_859_840
    # at 819 GB/s that is 104.83 us; of a 227 ms step, 0.0462 %.
    pct = peaks.roofline_share_pct(b, 0.227, "TPU v5 lite")
    assert pct == pytest.approx(100 * (85_859_840 / 819e9) / 0.227)
    assert pct == pytest.approx(0.04618, rel=1e-3)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.roofline_share_pct(1.0, 1.0, "cpu")


# ---- corpus, weights, reference --------------------------------------

@pytest.mark.parametrize("model_type", ["fm", "ffm"])
def test_corpus_text_parses_to_what_the_generator_recorded(
        tmp_path, model_type):
    from fast_tffm_tpu.data.hashing import murmur64
    from fast_tffm_tpu.data.parser import parse_lines
    tok = np.frombuffer(b"C07=00ab12cd", np.uint8)[None, :]
    assert int(corpus.murmur64_fixed(tok)[0]) == murmur64(b"C07=00ab12cd")
    feats = tiny_tree.TINY_FFM["features"]
    c = corpus.generate(feats, model_type, 4096, 300, 2 ** 31 + 5,
                        str(tmp_path), 2, "t")
    lines = []
    for p in c.files:
        with open(p) as fh:
            lines += fh.read().splitlines()
    assert len(lines) == 300 and c.lines_per_file == [150, 150]
    blk = parse_lines(lines, vocabulary_size=4096, hash_feature_id=True,
                      field_aware=model_type == "ffm", field_num=4)
    L = c.rows.shape[1]
    assert (blk.ids.reshape(300, L) == c.rows).all()
    assert np.abs(blk.vals.reshape(300, L) - c.vals).max() < 1e-6
    assert (blk.labels == c.labels).all()
    if model_type == "ffm":
        assert (blk.fields.reshape(300, L) == c.fields[None, :]).all()
    # the same seed gives the same corpus; another seed another one
    again = corpus.generate(feats, model_type, 4096, 300, 2 ** 31 + 5,
                            str(tmp_path / "b"), 2, "t")
    other = corpus.generate(feats, model_type, 4096, 300, 6,
                            str(tmp_path / "c"), 2, "t")
    assert (again.rows == c.rows).all() and (other.rows != c.rows).any()


def test_device_table_equals_the_rows_numpy_makes():
    t = np.asarray(weights.make_table(1001, 5, 2 ** 31 + 9, 0.01,
                                      total_rows=1024))
    assert t.shape == (1024, 5) and not t[1000:].any()
    ids = np.array([0, 1, 17, 999, 1000])
    assert (weights.table_rows_numpy(ids, 5, 2 ** 31 + 9, 0.01, 1001)
            == t[ids]).all()
    assert 0.0095 < np.abs(t[:1000]).max() <= 0.01
    assert abs(t[:1000].mean()) < 5e-4


@pytest.fixture
def tree_families(tiny_root, monkeypatch):
    """The tree's references/ beside the repo's for this process: the
    order-3 file exists only there (subprocess runs from the tree find
    it by themselves)."""
    import benchmarks.references as pkg
    monkeypatch.setattr(pkg, "__path__", list(pkg.__path__) + [
        os.path.join(tiny_root, "benchmarks", "references")])
    monkeypatch.setattr(reference, "REFERENCES_DIR", os.path.join(
        tiny_root, "benchmarks", "references"))
    yield
    sys.modules.pop("benchmarks.references.fm_order3_tiny", None)


def _model(family, model_type, order, F, k):
    return dict(
        model_type=model_type, order=order, factor_num=k, field_num=F,
        row_dim=k * F + 1 if model_type == "ffm" else k + 1,
        loss_type="logistic", factor_lambda=1e-3, bias_lambda=1e-4,
        learning_rate=0.05, adagrad_init=0.1, reference_family=family)


FAMILIES = [("fm_order2", "fm", 2, 0, 4), ("ffm", "ffm", 2, 5, 3),
            ("fm_order3", "fm", 3, 0, 4),
            ("fm_order3_tiny", "fm", 3, 0, 4)]


def test_every_family_file_of_the_directory_is_tried_below():
    assert reference.families() == sorted(f for f, *_ in FAMILIES[:3])
    assert reference.families() == sorted(
        f[:-3] for f in os.listdir(os.path.join(REPO, "benchmarks",
                                                "references"))
        if f.endswith(".py") and f != "__init__.py")


@pytest.mark.parametrize("family,model_type,order,F,k", FAMILIES)
def test_reference_follows_the_programs_step_arithmetic(
        tree_families, family, model_type, order, F, k):
    import jax.numpy as jnp
    from fast_tffm_tpu.models.fm import ModelSpec, train_step_body
    rng = np.random.default_rng(0)
    V, B, L = 50, 16, 5
    model = _model(family, model_type, order, F, k)
    D = model["row_dim"]
    # order 3 at a range where its cubic term weighs (tiny_tree.py)
    T = rng.uniform(-.1, .1, (V + 1, D)).astype(np.float32) * (
        3 if order == 3 else 1)
    T[-1] = 0
    spec = ModelSpec(model_type=model_type, order=order, factor_num=k,
                     field_num=F, vocabulary_size=V, loss_type="logistic",
                     factor_lambda=1e-3, bias_lambda=1e-4,
                     learning_rate=0.05, kernel="xla", dedup="device")
    rows = rng.integers(0, V, (B, L))
    x = rng.uniform(.5, 2, (B, L)).round(3)
    x[0, 3:], rows[0, 3:] = 0, V                      # padding cells
    y = (rng.random(B) < .3).astype(np.float64)
    fields = np.arange(L) % max(F, 1)
    t, a = jnp.asarray(T), jnp.full(T.shape, 0.1, jnp.float32)
    ref = reference.ReferenceTrainer(model, np.arange(V + 1), T)
    first = None
    for _ in range(3):
        t, a, loss, _ = train_step_body(
            spec, t, a, jnp.asarray(y, jnp.float32), jnp.ones(B), None,
            jnp.asarray(rows, jnp.int32), jnp.asarray(x, jnp.float32),
            jnp.asarray(np.broadcast_to(fields, (B, L)), jnp.int32)
            if model_type == "ffm" else None)
        assert ref.step(rows, x, y, np.ones(B), fields) == pytest.approx(
            float(loss), rel=2e-6)
        first = float(loss) if first is None else first
    assert np.abs(np.asarray(t) - ref.table).max() < 1e-7
    if order == 3:
        # and the second-order file in its place is far off: the
        # degree-3 term is no rounding at this range
        low = reference.ReferenceTrainer(
            dict(model, reference_family="fm_order2"), np.arange(V + 1), T)
        assert abs(low.step(rows, x, y, np.ones(B), fields)
                   - first) > 1e-4 * first


@pytest.mark.parametrize("family,model_type,order,F,k", FAMILIES)
def test_a_familys_backward_is_the_gradient_of_its_score(
        tree_families, family, model_type, order, F, k):
    """Central finite differences of ``score`` in float64, every entry
    of the gathered rows, against ``backward``; and the bfloat16
    control path changes the score (a family that ignores ``quant``
    would make the control pass)."""
    rng = np.random.default_rng(5)
    U, B, L = 9, 6, 5
    model = _model(family, model_type, order, F, k)
    D = model["row_dim"]
    P = rng.uniform(-.4, .4, (U, D))
    inv = rng.integers(0, U, (B, L))
    x = rng.uniform(.5, 2, (B, L)).round(3)
    x[0, 3:] = 0
    fields = np.arange(L) % max(F, 1)
    c = rng.normal(size=B)                  # dLoss/dscore, any direction

    def f(Q):
        return reference.scores_and_row_grads(model, Q, inv, x, fields)[0]

    score, backward = reference.scores_and_row_grads(model, P, inv, x,
                                                     fields)
    g = backward(c)
    assert g.shape == (U, D)
    fd, h = np.empty_like(P), 1e-6
    for u in range(U):
        for d in range(D):
            E = np.zeros_like(P)
            E[u, d] = h
            fd[u, d] = c @ (f(P + E) - f(P - E)) / (2 * h)
    assert np.abs(g - fd).max() < 1e-6 * max(1.0, np.abs(fd).max())
    low, _ = reference.scores_and_row_grads(model, P, inv, x, fields,
                                            "bf16")
    assert 1e-5 < np.abs(low - score).max() < 0.1 * np.abs(score).max()


# ---- the one description of the model ---------------------------------

def test_model_of_holds_every_field_the_step_depends_on():
    """``ModelSpec`` is what the program's compiled step closes over:
    each of its fields is in the description (``MODEL_FIELDS``) or is
    named as what does not define the mathematics (``MODEL_APART``). A
    field added to the step and to neither list fails here."""
    import dataclasses
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.models.fm import ModelSpec
    spec_fields = {f.name for f in dataclasses.fields(ModelSpec)}
    assert spec_fields <= set(harness.MODEL_FIELDS) | set(harness.MODEL_APART)
    assert not set(harness.MODEL_FIELDS) & set(harness.MODEL_APART)
    assert {"order", "row_dim", "adagrad_init"} <= set(harness.MODEL_FIELDS)
    cfg = FmConfig(vocabulary_size=100, factor_num=4, order=3,
                   adagrad_init=0.2)
    model = harness.model_of(cfg, {"reference_family": "fm_order2"})
    assert set(model) == set(harness.MODEL_FIELDS) | {"reference_family"}
    assert (model["order"], model["row_dim"], model["adagrad_init"]) == (
        3, 5, 0.2)
    for name in harness.MODEL_FIELDS:
        assert name in harness.model_of.__doc__, name


@pytest.mark.parametrize("config,cfg_kw,said", [
    ({}, {}, "names no reference_family"),
    ({"reference_family": "no_such_family"}, {}, "no reference family"),
    ({"reference_family": "fm_order2"},
     {"model_type": "ffm", "field_num": 3}, "another model"),
])
def test_no_family_is_an_error_that_names_the_directory(config, cfg_kw,
                                                        said):
    from fast_tffm_tpu.config import FmConfig
    cfg = FmConfig(vocabulary_size=100, factor_num=4, **cfg_kw)
    with pytest.raises(harness.RunFailed, match=said) as e:
        harness.model_of(cfg, config)
    if "another model" not in said:
        assert "benchmarks/references/" in str(e.value)
        assert "fm_order2" in str(e.value)      # what is there


@pytest.mark.parametrize("workload", ["tiny-train", "tiny-ffm-train",
                                      "tiny-predict", "tiny-fm3-train"])
def test_the_bf16_control_comes_out_not_correct(tiny_root, tmp_path,
                                                tree_families, workload):
    """The reference computed in bfloat16 in the program's place fails
    at least one of the cell's numbers at its limit (PERF.md gives the
    readings at each cell's own size)."""
    cell = harness.load_cell(workload, tiny_root)
    limits = cell.config["check_limits"][cell.kind]
    for seed in (1, 2, 3):
        nums = control.control_numbers(cell, seed, str(tmp_path / str(seed)))
        assert any(v > limits[k] for k, v in nums.items()
                   if k in limits), nums


# ---- the harness: cells as files, the last line, broken paths --------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_tree.make(str(tmp_path_factory.mktemp("tree")))


def _digest(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            if "__pycache__" in base:
                continue
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


def _bench(root, *args):
    p = subprocess.run([sys.executable, "-m", "benchmarks.run", *args],
                       cwd=root, env=tiny_tree.env(), capture_output=True,
                       text=True, timeout=600)
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


def _h2d_is_what_the_feed_ships(tiny_root, shown):
    """``h2d_bytes_per_example`` against the arrays a step is fed: the
    program's own pipeline over the run's corpus and configuration
    (the run leaves both in its work directory), every array of a
    batch's feed counted, per example. On one device the train step
    takes the host unique, so a feed holds ``uniq_ids`` [U] beside the
    padded rectangles; the raw-id wire (8 B a cell) would read less."""
    from fast_tffm_tpu.config import load_config
    from fast_tffm_tpu.data.pipeline import batch_iterator
    from fast_tffm_tpu.models.fm import batch_args
    cfg = load_config(os.path.join(tiny_root, ".bench_work", "tiny-train",
                                   "run.cfg"))
    per_example, raw = set(), None
    for b in batch_iterator(cfg, cfg.train_files, training=True, epochs=3,
                            raw_ids=False):
        feed = {k: v for k, v in batch_args(b).items() if v is not None}
        assert {"uniq_ids", "local_idx", "vals", "labels",
                "weights"} == set(feed)
        per_example.add(sum(v.nbytes for v in feed.values())
                        / len(b.labels))
        raw = (sum(v.nbytes for v in feed.values())
               - feed["uniq_ids"].nbytes) / len(b.labels)
    got = shown["h2d_bytes_per_example"]["value"]
    assert raw < min(per_example) <= got <= max(per_example)
    assert 0.5 <= shown["uniq_slot_fill"]["value"] <= 1.0


def test_a_cell_added_as_files_runs_without_editing_any(tiny_root):
    """A configuration, a traffic file, a per-layer metric and a cell
    that exist only as new files and new BENCHMARK.json entries run
    end to end (one CPU device: the one-chip path, host unique)."""
    before = _digest(REPO)
    after = _digest(tiny_root)
    assert all(after[k] == v for k, v in before.items())   # none edited
    assert sorted(set(after) - set(before)) == sorted(
        os.path.join("benchmarks", *p.split("/")) for p in (
            "configs/tiny-fm.json", "configs/tiny-ffm.json",
            "configs/tiny-fm3.json",
            "configs/tiny-fm3-order2-reference.json",
            "traffic/tiny-train.json", "traffic/tiny-predict.json",
            "layer_metrics/tiny_steps_per_s.json",
            "references/fm_order3_tiny.py"))
    rc, out, err = _bench(tiny_root, "--workload", "tiny-train", "--seed",
                          str(2 ** 31 + 11), "--seconds", "1.5",
                          "--trace", "1", "--rehearse-cpu")
    assert rc == 0, err[-3000:]
    last = json.loads(out[-1])
    assert RESULT_KEYS <= set(last) and "breakdown" in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}  # a rehearsal
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
            "window_s", "rehearsal"} <= set(last["device"])
    assert 0 < last["device"]["busy_s"] <= last["device"]["window_s"]
    assert len(last["breakdown"]["device_ops"]) <= 10
    shown = json.loads(next(l for l in out if l.startswith("metrics: "))
                       [len("metrics: "):])
    assert {"tiny_steps_per_s", "step_device_ms", "input_wait_share",
            "h2d_bytes_per_example", "setup_start_s",
            "setup_compile_s"} <= set(shown)
    _h2d_is_what_the_feed_ships(tiny_root, shown)
    assert any(l.startswith("check loss_rel_gap_max") for l in out)
    assert any(l.startswith("check span_examples_credited_not_counted: 0 ")
               for l in out)
    # what the line reports end to end is all work over all time
    said = next(l for l in out if l.startswith("all work over all time"))
    import re
    n, cycles = map(int, re.search(r"of (\d+) readings, (\d+) cycles",
                                   said).groups())
    assert cycles >= 1 and n == cycles * 2   # 4 batches x 2 passes / 4
    # a trace with no device plane is read only where the caller says
    # it comes from a CPU (the rehearsal above did)
    pb = harness.TraceWindow(_run_for(harness.load_cell(
        "tiny-train", tiny_root)))
    pb.dir = os.path.join(tiny_root, ".bench_work", "tiny-train", "trace")
    with pytest.raises(ValueError, match="no /device: plane"):
        trace_reduce.reduce(pb.xplane())
    assert trace_reduce.reduce(pb.xplane(), host_ops=True).busy_s > 0


def test_predict_cell_runs_and_checks_its_scores(tiny_root):
    rc, out, err = _bench(tiny_root, "--workload", "tiny-predict",
                          "--seed", "5", "--seconds", "3", "--trace",
                          "0", "--rehearse-cpu")
    assert rc == 0, err[-3000:]
    last = json.loads(out[-1])
    assert set(last) == RESULT_KEYS and last["correct"] is True
    assert any(l.startswith("check score_abs_gap_max") for l in out)


PREDICT_LAYER = set(tiny_tree.PREDICT_LAYER)


@pytest.mark.parametrize("trace,names", [
    (0, {"predict_examples_per_s", "setup_s"}),
    (1, PREDICT_LAYER | {"setup_start_s", "setup_compile_s"})])
def test_the_repos_predict_traffic_runs_on_the_tiny_table(tiny_root, trace,
                                                          names):
    """``traffic/predict-sweep.json``, the file the cell
    ``fm16-predict-sweep`` will run (its passes, its one short warm
    call, its checked lines), under the tiny configuration: the
    metrics the result line would carry on a chip are that cell's, by
    name, and every phase's reader finds its span in the trace."""
    moves = {}
    for f in os.listdir(os.path.join(REPO, "benchmarks", "layer_metrics")):
        with open(os.path.join(REPO, "benchmarks", "layer_metrics", f)) as fh:
            moves[f[:-len(".json")]] = json.load(fh)["moves"]
    assert PREDICT_LAYER == {n for n, m in moves.items()
                             if m == "predict_examples_per_s"}
    rc, out, err = _bench(tiny_root, "--workload", "tiny-predict-sweep",
                          "--seed", str(2 ** 31 + 48), "--seconds", "4",
                          "--trace", str(trace), "--rehearse-cpu")
    assert rc == 0, err[-3000:]
    last = json.loads(out[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert list(last)[-1] == "check" and set(last["check"]) == {
        "score_lines_missing", "score_abs_gap_max"}
    assert err.strip().splitlines()[-1].startswith("check score_abs_gap_max")
    shown = json.loads(next(l for l in out if l.startswith("metrics: "))
                       [len("metrics: "):])
    assert set(shown) == names


@pytest.mark.parametrize("workload,correct", [
    ("tiny-fm3-train", True),
    ("tiny-fm3-order2-reference-train", False)])
def test_a_model_family_added_as_files_reaches_the_check(tiny_root,
                                                         workload, correct):
    """FM of order 3: benchmarks/references/ has no file for it, the
    tree brings one (power sums; tiny_tree.ORDER3_REFERENCE) and no
    file of the repo's is edited (the digest test above). With its own
    reference the order-3 program is correct; the same configuration
    naming the second-order file is not, on all three numbers: `order`
    and the family reach the check."""
    rc, out, err = _bench(tiny_root, "--workload", workload, "--seed",
                          str(2 ** 31 + 31), "--seconds", "1.5", "--trace",
                          "0", "--rehearse-cpu")
    assert rc == 0, err[-3000:]
    last = json.loads(out[-1])
    assert last["correct"] is correct and last["failed"] == 0
    checks = {l.split()[1].rstrip(":"): l.split()[-1] for l in out
              if l.startswith("check ")}
    gaps = [k for k in checks if "gap" in k]
    assert len(gaps) == 3
    assert all(checks[k] == ("ok" if correct else "FAILED") for k in gaps)
    assert all(v == "ok" for k, v in checks.items() if k not in gaps)


def test_the_control_of_a_family_added_as_files_fails(tiny_root):
    """``python3 -m benchmarks.control`` from the tree, as a builder
    runs it: the order-3 reference in bfloat16 in the program's place
    fails at least one limit on every seed."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.control", "--workload",
         "tiny-fm3-train", "--seeds", "1,2,3"], cwd=tiny_root,
        env=tiny_tree.env(), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()]
    assert [l["seed"] for l in lines] == [1, 2, 3]
    assert all(l["correct"] is False and l["fails"] for l in lines)


def test_a_configuration_that_names_no_family_fails_at_once(tiny_root,
                                                            tmp_path):
    """Before anything is timed, with exit code 1 and no result line,
    as a device with no peaks does."""
    import shutil
    root = str(tmp_path / "tree")
    shutil.copytree(tiny_root, root,
                    ignore=shutil.ignore_patterns(".bench_work"))
    path = os.path.join(root, "benchmarks", "configs", "tiny-fm3.json")
    with open(path) as fh:
        config = json.load(fh)
    for family, said in ((None, "names no reference_family"),
                         ("fm_order4", "no reference family 'fm_order4'")):
        config.pop("reference_family")
        if family:
            config["reference_family"] = family
        with open(path, "w") as fh:
            json.dump(config, fh)
        rc, out, err = _bench(root, "--workload", "tiny-fm3-train",
                              "--seed", "1", "--seconds", "600", "--trace",
                              "0", "--rehearse-cpu")
        assert rc == 1 and said in err and "benchmarks/references/" in err
        assert not any(l.startswith("{") for l in out)
        config["reference_family"] = "x"


def test_no_chip_no_result(tiny_root):
    rc, out, err = _bench(tiny_root, "--workload", "tiny-train", "--seed",
                          "1", "--seconds", "1", "--trace", "0")
    assert rc != 0 and "no accelerator" in err
    assert not any(l.startswith("{") for l in out)
    rc, _, err = _bench(tiny_root, "--workload", "no-such-cell", "--seed",
                        "1", "--seconds", "1", "--trace", "0")
    assert rc != 0 and "no workload" in err


def _unchanged_state(step):
    """The step computes its loss and hands its state back untouched
    (copies taken first: the real step donates its inputs)."""
    def broken(*args, **kwargs):
        kept = [a + 0 for a in args if hasattr(a, "shape")][:2]
        out = step(*args, **kwargs)
        return (kept[0], kept[1]) + tuple(out[2:])
    return broken


def _half_the_batch(step):
    def broken(*args, **kwargs):
        kwargs = dict(kwargs)
        w = np.asarray(kwargs["weights"]).copy()
        w[: len(w) // 2] = 0
        kwargs["weights"] = w
        return step(*args, **kwargs)
    return broken


def _half_the_batch_in_the_data_plane(step):
    """The same fault above the probe: the feed itself reaches the
    step with half its examples zero-weighted, as a data plane that
    drops them would hand it over. The reference follows the feed, so
    only the count of real examples against the batch catches it."""
    return _half_the_batch(step)


_half_the_batch_in_the_data_plane.above_probe = True


@pytest.mark.parametrize("breaker,correct", [
    (None, True), (_unchanged_state, False), (_half_the_batch, False),
    (_half_the_batch_in_the_data_plane, False)])
def test_a_broken_timed_path_comes_out_not_correct(tiny_root, breaker,
                                                   correct):
    """The rest of a run past the look for a chip, in this process
    (eight CPU devices: the mesh path, host dedup), with the step
    broken underneath: state returned unchanged, half the batch left
    out. The sound step passes the same check."""
    import jax
    cell = harness.load_cell("tiny-train", tiny_root)
    device = {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
    line = train_driver.run(_run_for(cell, seconds=6.0), device, breaker)
    assert json.loads(line)["correct"] is correct


def test_examples_credited_but_not_counted_come_out_not_correct(
        tiny_root, monkeypatch):
    """The rate credits steps x batch; where the program's own count
    of real examples over the span is short of that, not correct."""
    import jax
    real = train_driver.telemetry_window.window_delta
    monkeypatch.setattr(
        train_driver.telemetry_window, "window_delta",
        lambda ctx, counter: real(ctx, counter) - 64)
    cell = harness.load_cell("tiny-train", tiny_root)
    device = {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
    line = train_driver.run(_run_for(cell, seconds=6.0), device)
    assert json.loads(line)["correct"] is False


def test_an_altered_table_makes_predict_not_correct(tiny_root):
    import jax
    cell = harness.load_cell("tiny-predict", tiny_root)
    device = {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
    line = predict_driver.run(_run_for(cell, seconds=5.0), device,
                              breaker=lambda t: t * 1.5)
    assert json.loads(line)["correct"] is False


# ---- the contract's own shape -----------------------------------------

def test_trace_reduction_on_a_recorded_tpu_trace():
    """benchmarks/testdata/tiny_train_tpu.xplane.pb: 25 steps of the
    tiny FM on a TPU v5 lite (PR 24, cut to the device plane and two
    host lines). The numbers were read off the file's events by hand
    (a separate loop over ProfileData) before the reduction ran."""
    t = trace_reduce.reduce(os.path.join(
        REPO, "benchmarks", "testdata", "tiny_train_tpu.xplane.pb"))
    assert len(t.devices) == 1
    assert t.busy_s == pytest.approx(1499437e-9, rel=1e-6)
    assert t.window_s == pytest.approx(108445855e-9, rel=1e-6)
    assert 100 * t.busy_s / t.window_s == pytest.approx(1.3827, rel=1e-3)
    runs = t.program_runs(["fm_train_step"])
    assert len(runs) == 25
    assert all(busy <= m.end - m.start + 1e-12 for m, busy in runs)
    # the median execution lasts 62.702 us; 59.93 us of it an
    # operation is running
    assert t.program_device_ms(["fm_train_step"]) == pytest.approx(
        0.059932, rel=1e-4)
    assert t.program_device_ms(["fm_score"]) is None
    top = t.breakdown()["device_ops"]
    assert top[0][0].startswith("%fusion.8 = f32[4097,5]")
    assert top[0][1] == pytest.approx(225464e-9, rel=1e-6)
    assert top[1][0].startswith("%fusion.9 = f32[4097,5]")
    assert top[1][1] == pytest.approx(220617e-9, rel=1e-6)
    gaps = dict(t.breakdown()["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s,
                                               rel=1e-6)
    assert max(gaps, key=gaps.get) == "python_between_runtime_calls"
