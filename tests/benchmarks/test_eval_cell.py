"""The cell that trains to a quality target (``kind: train_eval``,
``drivers/train_eval.py``, ISSUE 44), at a size the CPU holds: the
cell end to end by ``--rehearse-cpu``, the probed score calls against
the float64 reference, the planted faults its checks must catch, the
AUC's reference, the probe, the new readers and the roofline's bytes."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import tiny_eval_tree
import tiny_tree
from benchmarks import auc_reference, harness, score_bytes
from benchmarks.drivers import train_eval
from benchmarks.readers import (loop_idle_by_phase, score_roofline,
                                telemetry_window)
from benchmarks.trace_reduce import DeviceTrace, Op, Trace

REPO = tiny_tree.REPO
CELL = tiny_eval_tree.CELL
SEED = 2 ** 31 + 11
EXACT = ("validation_examples_not_in_corpus", "sweeps_in_span_not_epochs",
         "sweep_examples_short", "swept_rows_not_of_last_step")
SWEEP_CHECKS = EXACT + ("score_abs_gap_max", "auc_binned_abs_gap")


@pytest.fixture(scope="module")
def eval_root(tmp_path_factory):
    return tiny_eval_tree.make(str(tmp_path_factory.mktemp("eval")))


@pytest.fixture(autouse=True)
def _work_root_of_its_own(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path / "work"))


def _checks(said):
    """name -> (value, limit, ok) of every ``check`` line."""
    out = {}
    for l in said.splitlines():
        if l.startswith("check "):
            name, rest = l[len("check "):].split(": ", 1)
            value, rest = rest.split(" (limit ", 1)
            limit, verdict = rest.split(") ")
            out[name] = (float(value), float(limit), verdict == "ok")
    return out


# ---- the cell end to end -------------------------------------------------

def test_the_cell_runs_by_rehearse_cpu(eval_root):
    """One CPU device: the one-chip path, ``TrainStep`` on the host
    unique's slots and ``fm_score`` on the host unique's fitted slots
    too (PR 45), two programs on one table. Every sweep check holds,
    the probed calls agree with the reference, the new metrics read
    the stream and the trace, and the score program is ready before
    the window opens."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(SEED), "--seconds", "1.5", "--trace", "1",
         "--rehearse-cpu"], cwd=eval_root, env=tiny_tree.env(),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = p.stdout.strip().splitlines()
    last = json.loads(out[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}   # a rehearsal
    checks = _checks(p.stdout)
    assert set(SWEEP_CHECKS) <= set(checks)
    assert all(checks[name][:2] == (0.0, 0.0) for name in EXACT)
    # float32 against float64 on the same rows: rounding, far under 2e-5
    assert checks["score_abs_gap_max"][0] < 2e-6
    assert {"loss_rel_gap_max", "grad_norm_gap_worst_leaf",
            "update_norm_gap_worst_leaf_3_steps",
            "span_examples_credited_not_counted"} <= set(checks)
    assert all(ok for _, _, ok in checks.values())
    controls = [l for l in out if l.startswith("control: the ")]
    assert len(controls) == 2
    assert all(l.endswith("it fails, as it must") for l in controls)
    sweeps = next(l for l in out if l.startswith("the span's sweeps: "))
    assert all(f" {p_} " in sweeps for p_ in train_eval.SWEEP_PHASES)
    shown = json.loads(next(l for l in out if l.startswith("metrics: "))
                       [len("metrics: "):])
    lines = 3 * 64
    assert shown["validation_examples_per_sweep"]["value"] == lines
    assert 0 < shown["validation_share"]["value"] < 100
    assert shown["validation_s_per_sweep"]["value"] > 0
    assert shown["validation_score_device_ms"]["value"] > 0
    assert shown["compiles_per_epoch"]["value"] == 0.0
    for name in ("idle_in_validation_first_batch",
                 "idle_in_validation_drain", "idle_in_validation_dispatch"):
        assert 0 <= shown[name]["value"] <= 100, name
    # the training plane's fill, with three sweeps' batches kept out of it
    assert shown["cell_fill"]["value"] == 5 / 8
    assert {"step_device_ms", "uniq_slot_fill", "host_build_s_per_batch",
            "epoch_barrier_s", "steady_rate.train"} <= set(shown)
    events = harness.read_telemetry(os.path.join(
        eval_root, ".bench_work", CELL, "metrics.jsonl"))
    snaps = [e["counters"] for e in events if e.get("event") == "metrics"
             and "counters" in e]
    assert snaps[-1]["validation/sweeps"] >= snaps[-1]["train/epochs"] - 1
    assert snaps[-1]["validation_plane/batches"] == 3 * snaps[-1][
        "validation/sweeps"]
    assert snaps[-1]["pipeline/batches"] <= snaps[-1]["train/steps"] + 8


# ---- the faults the checks must catch --------------------------------------

class _Capture:
    """Keeps what the driver handed ``sweep_checks``, so that a test
    can ask again with something standing in the program's place."""

    def __init__(self, monkeypatch):
        self.args = None
        real = train_eval.sweep_checks

        def keep(*args, **kw):
            self.args = args
            return real(*args, **kw)
        monkeypatch.setattr(train_eval, "sweep_checks", keep)
        self.again = real


def _run_in_process(eval_root, eval_breaker=None, breaker=None, **train):
    import jax
    cell = harness.load_cell(CELL, eval_root)
    cell.config["program"]["Train"].update(train)
    run = harness.Run(cell=cell, seed=SEED, seconds=2.0, trace=False,
                      rehearse=True, t0=time.monotonic())
    device = {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
    return train_eval.run(run, device, breaker, eval_breaker)


def _skip_alternate_sweeps(evaluate):
    last = []

    def broken(*args, **kwargs):
        if len(last) % 2:
            last.append(last[-1])       # logged again, nothing swept
        else:
            last.append(evaluate(*args, **kwargs))
        return last[-1]
    return broken


class _PreviousIntervalsTable:
    """The scorer is handed the table as it stood when the interval
    began: ``step`` (below the probe) copies the table an interval's
    first step is given, ``evaluate`` sweeps that copy."""

    def __init__(self, interval: int):
        self.interval, self.calls, self.stale = interval, 0, None

    def step(self, step):
        def wrapped(table, *args, **kwargs):
            import jax.numpy as jnp
            if self.calls % self.interval == 0:
                self.stale = jnp.array(table, copy=True)
            self.calls += 1
            return step(table, *args, **kwargs)
        return wrapped

    def evaluate(self, evaluate):
        def broken(cfg, table, *args, **kwargs):
            return evaluate(cfg, self.stale, *args, **kwargs)
        return broken


@pytest.mark.parametrize("fault,failing", [
    ("none", None),
    ("the sweep cut short (validation_max_batches = 1)",
     "sweep_examples_short"),
    ("a sweep skipped on alternate intervals", "sweeps_in_span_not_epochs"),
    ("the scorer fed the previous interval's table",
     "swept_rows_not_of_last_step"),
])
def test_a_fault_of_the_sweep_comes_out_not_correct(eval_root, capsys,
                                                    fault, failing):
    """In this process (eight CPU devices: the mesh path, the sharded
    scorer on the host unique's batches) with the fault planted in the
    program's configuration or underneath the probes."""
    kw = {}
    if fault.startswith("the sweep cut short"):
        kw["validation_max_batches"] = 1
    elif fault.startswith("a sweep skipped"):
        kw["eval_breaker"] = _skip_alternate_sweeps
    elif fault.startswith("the scorer fed"):
        stale = _PreviousIntervalsTable(interval=8)
        kw.update(eval_breaker=stale.evaluate, breaker=stale.step)
    line = _run_in_process(eval_root, **kw)
    checks = _checks(capsys.readouterr().out)
    assert json.loads(line)["correct"] is (failing is None)
    if failing is None:
        assert set(SWEEP_CHECKS) <= set(checks)
        assert all(ok for _, _, ok in checks.values())
    else:
        assert checks[failing][2] is False and checks[failing][0] > 0
        # the training checks do not see it
        assert all(checks[k][2] for k in (
            "grad_norm_gap_worst_leaf", "update_norm_gap_worst_leaf_3_steps",
            "feed_examples_not_in_corpus"))


@pytest.mark.parametrize("stand_in,failing", [
    ("the reference in bfloat16 in the program's place",
     "score_abs_gap_max"),
    ("labels of another seed under the AUC", "auc_binned_abs_gap"),
])
def test_a_stand_in_comes_out_not_correct(eval_root, monkeypatch, capsys,
                                          stand_in, failing):
    """The sound run's own record, checked again with something else
    where the program's scores or the generator's labels stood."""
    kept = _Capture(monkeypatch)
    assert json.loads(_run_in_process(eval_root))["correct"] is True
    capsys.readouterr()
    model, heldout, probe = kept.args[:3]
    lines = train_eval.fed_lines(heldout, probe.feeds)
    if failing == "score_abs_gap_max":
        kw = {"scores": train_eval.reference_of_calls(
            model, heldout, probe, lines, quant="bf16")}
    else:
        other = np.random.default_rng([SEED + 7, 0xC0FFEE]).random(
            len(lines)) < 0.3
        assert (other != heldout.labels[lines]).any()
        kw = {"labels": other.astype(np.uint8)}
    sound, _ = kept.again(*kept.args)
    checks, _ = kept.again(*kept.args, **kw)
    assert harness.print_checks(sound) is True
    assert harness.print_checks(checks) is False
    failed = [c["name"] for c in checks if c["value"] > c["limit"]]
    assert failed == [failing]


def test_both_cells_train_on_the_same_batches(eval_root, monkeypatch):
    """On one seed the cell is fed ``tiny-train``'s own training batches
    (as ``fm16-train-eval`` is fed ``fm16-train-zipf``'s): the checked
    steps' feeds are equal to the bit and so are their losses, so the
    two cells' difference is the sweep."""
    import jax
    from benchmarks import check
    from benchmarks.drivers import train as train_driver
    seen = []
    real = check.train_checks

    def keep(model, rows, vr, seed, corpus, probe, *a, **k):
        seen.append((probe.feeds, probe.losses, corpus.signatures()))
        return real(model, rows, vr, seed, corpus, probe, *a, **k)
    monkeypatch.setattr(check, "train_checks", keep)
    device = {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
    for name, driver in (("tiny-train", train_driver), (CELL, train_eval)):
        cell = harness.load_cell(name, eval_root)
        run = harness.Run(cell=cell, seed=SEED, seconds=1.0, trace=False,
                          rehearse=True, t0=time.monotonic())
        assert json.loads(driver.run(run, device))["correct"] is True
    (feeds_a, losses_a, sigs_a), (feeds_b, losses_b, sigs_b) = seen
    assert (sigs_a == sigs_b).all() and losses_a == losses_b
    assert len(feeds_a) == len(feeds_b) == 3
    for a, b in zip(feeds_a, feeds_b):
        assert set(a) == set(b)
        assert all((a[k] == b[k]).all() for k in a)


def test_a_sweep_that_never_reached_the_scorer_or_ran_short_is_counted():
    """``sweep_checks`` on a built record: the counts that decide."""
    class Held:
        labels = np.zeros(64, np.uint8)
        rows = np.arange(64 * 3).reshape(64, 3)
        millis = np.full((64, 3), 1000, np.int32)
    probe = train_eval.SweepProbe(2)
    limits = {"score_abs_gap_max": 2e-5, "auc_binned_abs_gap_max": 1e-3}
    # nothing recorded at all: every exact check reads what is missing
    checks, info = train_eval.sweep_checks({}, Held, probe, limits, 2,
                                           (None, 0), None)
    got = {c["name"]: c["value"] for c in checks}
    assert info is None and set(got) == set(EXACT)
    assert got["validation_examples_not_in_corpus"] == 64
    assert got["sweeps_in_span_not_epochs"] == 2
    assert got["sweep_examples_short"] == 64
    assert got["swept_rows_not_of_last_step"] == 64
    # the program's counter and the probe's count are both held
    probe.sweeps = [{"t0": 0, "t1": 1, "auc": 0.5, "n": 64, "calls": 2,
                     "step": 8},
                    {"t0": 2, "t1": 3, "auc": 0.5, "n": 32, "calls": 1,
                     "step": 16}]
    checks, _ = train_eval.sweep_checks({}, Held, probe, limits, 2,
                                        (1, 2), 96)
    got = {c["name"]: c["value"] for c in checks}
    assert got["sweeps_in_span_not_epochs"] == 1
    assert got["sweep_examples_short"] == 32


# ---- the probe ---------------------------------------------------------------

def test_the_probe_records_the_first_sweep_and_counts_the_rest():
    import jax.numpy as jnp
    steps = train_eval.LastStateProbe(1)
    step = steps.wrap(lambda t, a, **kw: (t + 1.0, a, jnp.float32(0.5), None))
    feed = dict(local_idx=np.arange(8, dtype=np.int32).reshape(2, 4) % 5,
                vals=np.ones((2, 4), np.float32),
                labels=np.zeros(2, np.float32),
                weights=np.ones(2, np.float32), uniq_ids=None)
    table = jnp.zeros((6, 3))
    for _ in range(3):
        table = step(table, table, **feed)[0]
    assert steps.steps_run == 3 and steps.calls == 1
    assert np.asarray(steps.last_table)[0, 0] == 3.0
    probe = train_eval.SweepProbe(2)
    probe.steps = steps
    scored = []

    def make_batch_scorer(spec, mesh=None, backend=None):
        def score(tbl, args):
            scored.append(dict(args))
            args.pop("uniq_ids")            # a scorer may consume them
            return jnp.zeros(2)
        return score

    def evaluate(cfg, tbl, files, **kw):
        score = build(None)
        for _ in range(3):
            score(tbl, dict(local_idx=feed["local_idx"], vals=feed["vals"],
                            uniq_ids=None))
        return 0.75, 6

    build = probe.wrap_builder(make_batch_scorer)
    probed = probe.wrap_evaluate(evaluate)
    assert probed(None, table, ()) == (0.75, 6)
    table = step(table, table, **feed)[0]
    assert probed(None, table, ()) == (0.75, 6)
    assert [(s["calls"], s["n"], s["auc"], s["step"])
            for s in probe.sweeps] == [(3, 6, 0.75, 3), (3, 6, 0.75, 4)]
    assert len(probe.feeds) == len(probe.scores) == 3    # the first sweep's
    assert len(probe.rows) == 2 and len(scored) == 6
    ids, rows = probe.rows[0]
    assert list(ids) == [0, 1, 2, 3, 4] and (rows == 3.0).all()
    assert (probe.rows_of_last_step == rows).all()
    assert set(probe.feeds[0]) == {"local_idx", "vals"}
    with pytest.raises(harness.RunFailed, match="outside evaluate"):
        train_eval.SweepProbe(1).wrap_builder(make_batch_scorer)(None)(
            table, {})


def test_a_padding_example_is_no_missing_line():
    class Held:
        labels = np.array([1, 0, 1], np.uint8)
        rows = np.array([[5, 9], [7, 9], [5, 2]])
        millis = np.array([[1000, 250], [1000, 500], [1000, 125]], np.int32)
    feed = {"local_idx": np.array([[7, 9, 99], [5, 2, 99], [99, 99, 99],
                                   [5, 9, 99], [4, 4, 99]], np.int32),
            "vals": np.array([[1, .5, 0], [1, .125, 0], [0, 0, 0],
                              [1, .25, 0], [1, 1, 0]], np.float32)}
    assert list(train_eval.fed_lines(Held, [feed])) == [1, 2, -2, 0, -1]


# ---- the AUC's reference -----------------------------------------------------

def _auc_by_pairs(scores, labels):
    pos = [s for s, y in zip(scores, labels) if y]
    neg = [s for s, y in zip(scores, labels) if not y]
    return sum((p > n) + 0.5 * (p == n) for p in pos for n in neg) / (
        len(pos) * len(neg))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_exact_auc_is_the_rank_statistic_with_ties_at_one_half(seed):
    from fast_tffm_tpu.metrics import exact_auc as the_programs
    rng = np.random.default_rng(seed)
    scores = np.round(rng.normal(size=300), 1)        # many ties
    labels = rng.random(300) < 0.3
    want = _auc_by_pairs(scores, labels)
    assert auc_reference.exact_auc(scores, labels) == pytest.approx(
        want, abs=1e-15)
    assert auc_reference.exact_auc(scores, labels) == pytest.approx(
        the_programs(scores, labels), abs=1e-12)
    assert auc_reference.exact_auc(np.ones(10), np.arange(10) < 4) == 0.5
    assert auc_reference.exact_auc([1, 2, 3, 4], [0, 0, 1, 1]) == 1.0
    assert np.isnan(auc_reference.exact_auc([1, 2], [1, 1]))
    with pytest.raises(ValueError, match="NaN"):
        auc_reference.exact_auc([1, float("nan")], [0, 1])
    with pytest.raises(ValueError, match="labels"):
        auc_reference.exact_auc([1, 2, 3], [0, 1])


def test_the_reference_shares_no_code_with_the_program():
    with open(auc_reference.__file__) as fh:
        src = fh.read()
    assert "import fast_tffm_tpu" not in src
    assert "from fast_tffm_tpu" not in src


def test_the_binned_estimator_sits_near_it_at_the_cells_size():
    """2^14 bins on 442,368 scores drawn as a young model's are (logits
    near the prior's; a spread of 0.15 gives the chip's readings, whose
    root mean square over eight seeds is 1.6e-6: the error is the
    within-bin ties', about 2.5e-7 over the spread): the limit of the
    cell's configuration holds the binning's error with room, and
    labels in another order fall far outside it."""
    from fast_tffm_tpu.metrics import StreamingAUC
    cell = harness.load_cell("fm16-train-eval")
    limit = cell.config["check_limits"]["train_eval"][
        "auc_binned_abs_gap_max"]
    n = cell.traffic["heldout_batches"] * 8192
    gaps = []
    for seed in (5, 6, 7):
        rng = np.random.default_rng(seed)
        scores = rng.normal(-1.1, 0.15, size=n).astype(np.float32)
        labels = rng.random(n) < 0.25
        auc = StreamingAUC()
        auc.update(scores, labels, np.ones(n))
        gaps.append(abs(auc.result()
                        - auc_reference.exact_auc(scores, labels)))
        other = labels[np.random.default_rng(0).permutation(n)]
        assert abs(auc.result()
                   - auc_reference.exact_auc(scores, other)) > 5 * limit
    assert max(gaps) < limit / 2, gaps


# ---- the roofline's bytes and the readers --------------------------------------

def test_a_score_call_must_move_four_megabytes():
    """ISSUE 44's reckoning: 19.4k distinct rows of 17 float32, 8,192 x
    39 cells of an id and a value, 8,192 scores: about 4 MB and 5 us at
    819 GB/s."""
    b = score_bytes.score_call_min_bytes(19_400, 17, 8192 * 39, 8192)
    assert b == 19_400 * 68 + 319_488 * 8 + 32_768 == 3_907_872
    assert 4.5e-6 < b / 819e9 < 5.0e-6
    assert score_bytes.score_call_min_bytes(0, 17, 0, 0) == 0


def _score_trace(ms=10.0, program="jit_fm_score(7)"):
    s = ms / 1e3
    ops = [Op("fusion.1", 1.0, 1.0 + s, {}), Op("fusion.1", 2.0, 2.0 + s, {})]
    mods = [Op(program, 1.0, 1.0 + s, {}), Op(program, 2.0, 2.0 + s, {}),
            Op("jit_fm_train_step(3)", 3.0, 3.001, {})]
    return Trace([DeviceTrace("/device:TPU:0", ops, mods)], [], 0.0, 4.0)


def test_the_roofline_reader_reads_the_probed_call_against_the_trace():
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           "validation_score_roofline.json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "score_roofline" and spec["unit"] == "%"
    call = {"distinct_rows": 19_400.0, "cells": 319_488.0,
            "examples": 8192.0}
    ctx = {"trace": _score_trace(10.0), "row_dim": 17, "score_call": call,
           "device_kind": "TPU v5 lite"}
    got = score_roofline.read(ctx, **spec["args"])
    assert got == pytest.approx(100 * (3_907_872 / 819e9) / 0.010)
    assert 0.04 < got < 0.06                # the issue's "about 0.05%"
    # a later scorer ten times faster reads ten times the share
    ctx["trace"] = _score_trace(1.0, "jit_fm_packed_score(2)")
    assert score_roofline.read(ctx, **spec["args"]) == pytest.approx(10 * got)
    # nothing to read: no probed call, or no execution of the programs
    assert score_roofline.read(dict(ctx, score_call=None),
                               **spec["args"]) is None
    ctx["trace"] = _score_trace(1.0, "jit_other(2)")
    assert score_roofline.read(ctx, **spec["args"]) is None
    with pytest.raises(KeyError, match="no published peaks"):
        score_roofline.read(dict(ctx, trace=_score_trace(),
                                 device_kind="cpu"), **spec["args"])


def _stream(tmp_path, counters_a, counters_b):
    path = str(tmp_path / "metrics.jsonl")
    with open(path, "w") as fh:
        for step, c in ((136, counters_a), (392, counters_b)):
            fh.write(json.dumps({"event": "metrics", "step": step,
                                 "counters": c}) + "\n")
    return {"telemetry_path": path, "window_steps": (136, 392),
            "window_wall_s": 4.0}


def test_the_counter_metrics_read_the_sweeps_and_nothing_on_the_parent(
        tmp_path):
    def read(ctx, name):
        with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                               name + ".json")) as fh:
            spec = json.load(fh)
        assert spec["reader"] == "telemetry_window"
        assert spec["moves"] == "train_examples_per_s_per_chip"
        return telemetry_window.read(ctx, **spec["args"])
    a = {"train/validation_seconds": 1.0, "validation/sweeps": 1,
         "validation/examples": 442_368}
    b = {"train/validation_seconds": 2.6, "validation/sweeps": 3,
         "validation/examples": 3 * 442_368}
    ctx = _stream(tmp_path, a, b)
    assert read(ctx, "validation_share") == pytest.approx(40.0)
    assert read(ctx, "validation_s_per_sweep") == pytest.approx(0.8)
    assert read(ctx, "validation_examples_per_sweep") == 442_368
    # the parent commit counts the enclosure and neither sweeps nor
    # examples: the share is read, the other two are left out
    (tmp_path / "p").mkdir()
    ctx = _stream(tmp_path / "p", {"train/validation_seconds": 1.0},
                  {"train/validation_seconds": 2.6})
    assert read(ctx, "validation_share") == pytest.approx(40.0)
    assert read(ctx, "validation_s_per_sweep") is None
    assert read(ctx, "validation_examples_per_sweep") is None


def test_the_idle_metrics_read_the_sweeps_leaves():
    """The chip idle through a sweep's first batch and its drain, busy
    under the dispatches: each leaf gets its own part, and a program
    from before the leaves reads nothing."""
    loop = [("python3", Op(n, a, b, {})) for n, a, b in (
        ("train/step", 0.0, 0.5), ("train/epoch_barrier", 1.0, 9.0),
        ("validation/open", 1.0, 1.5), ("validation/first_batch", 1.5, 3.5),
        ("validation/score_dispatch", 3.5, 4.0),
        ("validation/input_wait", 4.0, 4.5),
        ("validation/score_dispatch", 4.5, 5.0),
        ("validation/drain", 5.0, 8.0), ("validation/auc", 8.0, 8.5),
        ("train/step", 9.0, 9.5))]
    chip = DeviceTrace("/device:TPU:0", [Op("op", 0.0, 1.5, {}),
                                         Op("op", 3.5, 6.0, {}),
                                         Op("op", 9.0, 10.0, {})], [])
    trace = Trace([chip], loop, 0.0, 10.0)
    want = {"idle_in_validation_first_batch": 20.0,
            "idle_in_validation_drain": 20.0,
            "idle_in_validation_dispatch": 0.0}
    ctx = {"trace": trace}
    for name, share in want.items():
        with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                               name + ".json")) as fh:
            spec = json.load(fh)
        assert spec["reader"] == "loop_idle_by_phase"
        assert loop_idle_by_phase.read(ctx, **spec["args"]) == pytest.approx(
            share), name
    before = Trace([chip], [e for e in loop
                            if not e[1].name.startswith("validation/")],
                   0.0, 10.0)
    assert loop_idle_by_phase.read({"trace": before},
                                   "validation/drain") is None


# ---- the files ------------------------------------------------------------------

def test_the_configuration_is_the_parent_configurations_with_a_sweep():
    cell = harness.load_cell("fm16-train-eval")
    parent = harness.load_cell("fm16-train-zipf")
    for key in ("program", "features", "reference_family"):
        assert cell.config[key] == parent.config[key], key
    assert cell.config["precision"].startswith(parent.config["precision"])
    assert cell.config["check_limits"]["train"] == parent.config[
        "check_limits"]["train"]
    assert set(cell.config["check_limits"]["train_eval"]) == {
        "score_abs_gap_max", "auc_binned_abs_gap_max"}
    assert cell.config["reduced"] == ["vocabulary_size", "corpus_lines"]
    assert set(parent.config["assumed"]) < set(cell.config["assumed"])
    assert len(cell.config["guarantees"]) == 4 and cell.chips == 1
    v = cell.config["validation"]
    assert v["eval_examples_per_trained_example"] == 0.425 == round(
        89_137_319 / 209_759_885, 3)
    # the training traffic is fm16-train-zipf's to the key, so that on
    # one seed both cells train on the same batches
    tr = cell.traffic
    for key in ("corpus_batches", "corpus_files", "corpus_passes",
                "steps_per_reading", "checked_steps", "trace_seconds"):
        assert tr[key] == parent.traffic[key], key
    batch = cell.config["program"]["Train"]["batch_size"]
    interval = tr["corpus_batches"] * tr["corpus_passes"]
    assert tr["heldout_batches"] * batch == 442_368 and interval == 128
    assert round(tr["heldout_batches"] / interval, 3) == 0.422
    # one whole interval and its sweep before the window opens
    assert tr["warmup_readings"] * tr["steps_per_reading"] > interval
    assert tr["kind"] == "train_eval" and tr["checked_score_calls"] == 3


def test_what_the_pr_wrote_into_the_benchmark_is_within_its_form():
    """The driver refused this PR once for a configuration's ``why`` of
    201 characters: every line of text an entry holds is 1 to 200
    printable characters, and an entry holds just the keys of its kind."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}}
    for kind, allowed in keys.items():
        for entry in spec[kind]:
            assert set(entry) <= allowed, (entry["name"], set(entry))
            for key in ("why", "source", "layer"):
                text = entry.get(key)
                if text is None:
                    continue
                assert 1 <= len(text) <= 200, (entry["name"], key, len(text))
                assert text.isprintable(), (entry["name"], key)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536


def test_the_cell_is_on_the_lists_the_issue_names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    on = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]
          if "fm16-train-eval" in m.get("workloads", ())}
    zipf = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]
            if "fm16-train-zipf" in m.get("workloads", ())}
    ten = {"bookkeeping_s_per_step", "loop_unnamed_share",
           "barrier_flush_s", "pipeline_open_s", "first_batch_s",
           "idle_unnamed", "idle_in_bookkeeping", "idle_in_barrier_flush",
           "idle_in_pipeline_open", "idle_in_first_batch"}
    own = set(tiny_eval_tree.metrics())
    assert on == (zipf - ten) | own and ten <= zipf
    assert own == {
        "validation_share", "validation_s_per_sweep",
        "validation_examples_per_sweep", "validation_score_device_ms",
        "validation_gather_ms", "idle_in_validation_first_batch",
        "idle_in_validation_drain", "idle_in_validation_dispatch",
        "validation_score_roofline",
        "validation_uniq_slot_fill"}                # PR 45, by ISSUE 45
    for m in spec["per_layer"]:
        if m["name"] in own:
            assert m["moves"] == "train_examples_per_s_per_chip"
            assert m["workloads"] == ["fm16-train-eval"]
            with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                                   m["name"] + ".json")) as fh:
                own_file = json.load(fh)
            assert {k: own_file[k] for k in m if k != "workloads"} == {
                k: v for k, v in m.items() if k != "workloads"}
    # predict's file is left to predict
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           "score_device_ms.json")) as fh:
        assert json.load(fh)["moves"] == "predict_examples_per_s"
    # what test_benchmark_json.py's hold_cell holds of every cell
    held = harness.load_cell("fm16-train-eval")
    assert os.path.exists(os.path.join(REPO, "benchmarks", "drivers",
                                       held.kind + ".py"))
    assert {m["name"] for m in held.end_to_end} == {
        "train_examples_per_s_per_chip", "setup_s"}
    assert held.kind in held.config["check_limits"]
    assert os.path.exists(os.path.join(
        REPO, "benchmarks", "references",
        held.config["reference_family"] + ".py"))
    cell = next(w for w in spec["workloads"]
                if w["name"] == "fm16-train-eval")
    assert len(cell["why"]) <= 200
    assert cell["config"] == "fm-k16-criteo1tb-eval"
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    assert len(config["source"]) <= 200
