"""The cell whose lines have bags of tokens (benchmarks/corpus_bags.py,
drivers/train_bags.py, control_bags.py), at a size the CPU holds: the
generator against the program's own parse, the cell end to end with two
widths and two step programs in one job, the faults its check must
catch, and the control that must fail."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import tiny_bags_tree
import tiny_tree
from benchmarks import control, corpus_bags, harness
from benchmarks.drivers import train_bags

REPO = tiny_tree.REPO
FEATURES = tiny_bags_tree.TINY_BAGS["features"]


@pytest.fixture(scope="module")
def bags_root(tmp_path_factory):
    return tiny_bags_tree.make(str(tmp_path_factory.mktemp("bags")))


@pytest.fixture(autouse=True)
def _work_root_of_its_own(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path / "work"))


def _lines(corpus):
    out = []
    for p in corpus.files:
        with open(p) as fh:
            out += fh.read().splitlines()
    return out


# ---- the generator -----------------------------------------------------

@pytest.mark.parametrize("parser", ["python", "c++"])
def test_bags_text_parses_to_what_the_generator_recorded(tmp_path, parser):
    """Line for line: the cells a line has, their rows, their values
    to the thousandth, its label; pad cells are row 0, value 0."""
    from fast_tffm_tpu.data.cparser import parse_lines_fast
    from fast_tffm_tpu.data.parser import parse_lines
    parse = parse_lines if parser == "python" else parse_lines_fast
    c = corpus_bags.generate(FEATURES, "fm", 4096, 300, 2 ** 31 + 5,
                             str(tmp_path), 3, "t")
    lines = _lines(c)
    assert len(lines) == 300 and c.lines_per_file == [100, 100, 100]
    blk = parse(lines, 4096, hash_feature_id=True)
    live = c.millis > 0
    assert c.rows.shape == (300, corpus_bags.width(FEATURES)) == (300, 21)
    assert (blk.sizes == live.sum(axis=1)).all()
    assert (live[:, :-1] >= live[:, 1:]).all()      # cells lead, pads trail
    assert not c.rows[~live].any() and not c.fields.any()
    assert (blk.ids == c.rows[live]).all()
    assert np.abs(blk.vals - c.vals[live]).max() < 1e-6
    assert (blk.labels == c.labels).all() and blk.truncated == 0
    # three ids at 1, then a bag of n words at round(1000 / n) / 1000
    assert (c.millis[:, :3] == 1000).all()
    n_query = np.rint(1000.0 / c.millis[:, 3])
    assert ((1 <= n_query) & (n_query <= 6)).all()
    first = c.millis[np.arange(300), 3]
    for j in range(6):
        inside = j < n_query
        assert (c.millis[inside, 3 + j] == first[inside]).all()
    sizes = live.sum(axis=1)
    assert sizes.min() >= 5 and sizes.max() <= 21 and len(set(sizes)) > 5


def test_same_seed_same_corpus_and_only_fm(tmp_path):
    a, b, c = (corpus_bags.generate(FEATURES, "fm", 4096, 200, seed,
                                    str(tmp_path / name), 2, "t")
               for seed, name in ((2 ** 31 + 5, "a"), (2 ** 31 + 5, "b"),
                                  (6, "c")))
    assert (a.rows == b.rows).all() and (a.millis == b.millis).all()
    assert _lines(a) == _lines(b) and _lines(a) != _lines(c)
    with pytest.raises(ValueError, match="FM's"):
        corpus_bags.generate(FEATURES, "ffm", 4096, 10, 1, str(tmp_path),
                             1, "t")


def test_the_generator_stands_in_for_a_call_only():
    from benchmarks import corpus
    kept = corpus.generate
    with corpus_bags.in_place_of_generate():
        assert corpus.generate is corpus_bags.generate
    assert corpus.generate is kept


def test_the_repos_schema_is_the_issues():
    """The configuration's lengths as ISSUE 35 sized them: 16 to 112
    cells a line, mean near 50.8, the widest line of a batch of 8,192
    on the 96 or the 112 rung (NumPy, no text written at this size)."""
    cell = harness.load_cell("fm8-train-bags")
    f = cell.config["features"]
    assert corpus_bags.width(f) == 112 and len(f["id_cardinalities"]) == 12
    assert sum(f["id_cardinalities"]) == 54_686_453
    rng = np.random.default_rng(0)
    n = 8192 * 16
    cells = 12 + sum(np.clip(np.rint(rng.lognormal(
        np.log(b["median"]), b["sigma"], n)), 1, b["cap"]) for b in f["bags"])
    assert 50.0 < cells.mean() < 51.5 and cells.min() >= 16
    widest = cells.reshape(16, 8192).max(axis=1)
    assert ((widest > 80) & (widest <= 112)).all()
    assert cell.config["check_limits"]["train"] == cell.config[
        "check_limits"]["train_bags"]
    assert set(cell.config["program"]["General"]) == {
        "vocabulary_size", "hash_feature_id", "factor_num", "model_type"}
    assert "max_features_per_example" not in json.dumps(
        cell.config["program"])


# ---- the cell end to end ----------------------------------------------

def _bench(root, *args):
    p = subprocess.run([sys.executable, "-m", "benchmarks.run", *args],
                       cwd=root, env=tiny_tree.env(), capture_output=True,
                       text=True, timeout=600)
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


def test_the_bags_cell_runs_two_widths_and_two_programs(bags_root):
    """One CPU device: the one-chip path, ``TrainStep``. The job ships
    batches at the 16 and the 24 rung, holds a program each, the probe
    checks a step at both, and the three metrics read the stream."""
    rc, out, err = _bench(bags_root, "--workload", tiny_bags_tree.CELL,
                          "--seed", str(2 ** 31 + 11), "--seconds", "1.5",
                          "--trace", "1", "--rehearse-cpu")
    assert rc == 0, err[-3000:]
    last = json.loads(out[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}   # a rehearsal
    widths = next(l for l in out if l.startswith("widths: "))
    assert "16:" in widths and "24:" in widths
    assert any(l.startswith("check widths_shipped_not_checked: 0 ")
               for l in out)
    assert sum(l.startswith("steps at width ") for l in out) == 2
    shown = json.loads(next(l for l in out if l.startswith("metrics: "))
                       [len("metrics: "):])
    assert 8.0 < shown["cells_per_example"]["value"] < 12.0
    assert shown["truncated_cells_per_example"]["value"] == 0.0
    assert 0.05 < shown["program_switches_per_step"]["value"] < 0.95
    assert 0.3 < shown["cell_fill"]["value"] < 0.7
    assert {"step_device_ms", "uniq_slot_fill", "h2d_bytes_per_example",
            "compiles_per_epoch"} <= set(shown)
    # two programs made ready, both in the warm-up; no state re-laid
    events = harness.read_telemetry(os.path.join(
        bags_root, ".bench_work", tiny_bags_tree.CELL, "metrics.jsonl"))
    snaps = [e["counters"] for e in events if e.get("event") == "metrics"
             and "counters" in e]
    assert snaps[-1]["train/step_programs"] == 2
    warm = tiny_bags_tree.TINY_TRAFFIC["warmup_readings"]
    assert snaps[warm]["train/step_programs"] == 2
    assert snaps[-1]["train/state_relayouts"] == 0
    assert snaps[-1]["train/program_switches"] > 0


def test_the_tree_edits_no_file_of_the_repo(bags_root):
    import hashlib

    def digest(root):
        out = {}
        for base, _, files in os.walk(os.path.join(root, "benchmarks")):
            for f in files:
                if "__pycache__" not in base:
                    with open(os.path.join(base, f), "rb") as fh:
                        out[os.path.relpath(os.path.join(base, f), root)] \
                            = hashlib.sha1(fh.read()).hexdigest()
        return out
    before, after = digest(REPO), digest(bags_root)
    assert all(after[k] == v for k, v in before.items())
    new = sorted(os.path.basename(k) for k in set(after) - set(before))
    assert "tiny-fm-bags.json" in new and "tiny-bags.json" in new
    assert not [k for k in new if k.endswith(".py")
                and k != "fm_order3_tiny.py"]


# ---- the faults the check must catch -----------------------------------

def _ones_for_values(step):
    """Every real cell valued 1 where the line said 1/len."""
    def broken(*args, **kwargs):
        v = np.asarray(kwargs["vals"])
        return step(*args, **dict(kwargs, vals=(v != 0).astype(v.dtype)))
    return broken


def _wide_batch_through_the_narrow_shapes(step):
    """The 24-wide batch cut to the 16-wide program's shapes."""
    def broken(*args, **kwargs):
        if kwargs["vals"].shape[1] > 16:
            kwargs = dict(kwargs,
                          vals=np.asarray(kwargs["vals"])[:, :16],
                          local_idx=np.asarray(kwargs["local_idx"])[:, :16])
        return step(*args, **kwargs)
    return broken


def _run_in_process(bags_root, breaker=None, cut_at=None):
    import time
    import jax
    cell = harness.load_cell(tiny_bags_tree.CELL, bags_root)
    if cut_at:
        cell.config["program"]["Train"][
            "max_features_per_example"] = cut_at
    run = harness.Run(cell=cell, seed=2 ** 31 + 11, seconds=3.0,
                      trace=False, rehearse=True, t0=time.monotonic())
    device = {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
    return train_bags.run(run, device, breaker)


@pytest.mark.parametrize("fault,failing", [
    ("none", None),
    ("a builder that cuts lines below the widest",
     "feed_examples_not_in_corpus"),
    ("a cell value of 1 where 1/len was written",
     "grad_norm_gap_worst_leaf"),
    ("the wider batch through the narrower program's shapes",
     "grad_norm_gap_worst_leaf"),
])
def test_a_fault_of_the_two_width_job_comes_out_not_correct(
        bags_root, capsys, fault, failing):
    """In this process (eight CPU devices: the mesh path) with the
    fault underneath the probe, or in the program's own builder."""
    breaker = {"a cell value of 1 where 1/len was written": _ones_for_values,
               "the wider batch through the narrower program's shapes":
               _wide_batch_through_the_narrow_shapes}.get(fault)
    line = _run_in_process(bags_root, breaker,
                           cut_at=12 if fault.startswith("a builder") else 0)
    said = capsys.readouterr().out
    assert json.loads(line)["correct"] is (failing is None)
    assert "check widths_shipped_not_checked: 0 " in said
    if failing:
        assert any(l.startswith(f"check {failing}") and l.endswith("FAILED")
                   for l in said.splitlines())


def test_a_width_the_window_ran_and_no_checked_step_had_fails():
    probe = train_bags.WidthProbe(floor=3, n_widths=2, most=24)
    probe.widths = [16, 16, 16, 16, 24, 16, 24, 16]
    probe.calls = 3                       # checked: the first three
    assert probe.check((4, 8)) == {"name": "widths_shipped_not_checked",
                                   "value": 1, "limit": 0}
    probe.calls = 5
    assert probe.check((4, 8))["value"] == 0


def test_the_probe_goes_on_until_both_widths_are_checked():
    import jax.numpy as jnp
    calls = []

    def step(table, acc, **kw):
        calls.append(kw["local_idx"].shape[1])
        return table + 1.0, acc, jnp.float32(0.5), None

    def feed(w):
        return dict(local_idx=np.zeros((2, w), np.int32),
                    vals=np.ones((2, w), np.float32),
                    labels=np.zeros(2, np.float32),
                    weights=np.ones(2, np.float32), uniq_ids=None)
    probe = train_bags.WidthProbe(floor=3, n_widths=2, most=6)
    probed = probe.wrap(step)
    t = jnp.zeros((4, 3))
    for w in (16, 16, 16, 16, 24, 16, 24):
        t = probed(t, t, **feed(w))[0]
    assert probe.calls == 5 and len(probe.feeds) == 5
    assert probe.after_last is not None and probe.widths == calls
    assert len(probe.widths) == 7
    # one width only: the probe stops at the most it may check
    probe = train_bags.WidthProbe(floor=3, n_widths=2, most=6)
    probed = probe.wrap(step)
    for _ in range(9):
        t = probed(t, t, **feed(16))[0]
    assert probe.calls == 6 and probe.after_last is not None


# ---- the control --------------------------------------------------------

def test_the_bf16_control_of_the_bags_cell_fails(bags_root, tmp_path,
                                                 capsys, monkeypatch):
    """control.control_numbers with the bags generator in place: the
    reference in bfloat16 fails a limit on every seed; and the command
    line of control_bags finds the limits under the traffic's kind."""
    from benchmarks import control_bags
    cell = harness.load_cell(tiny_bags_tree.CELL, bags_root)
    limits = cell.config["check_limits"][cell.kind]
    for seed in (1, 2, 3):
        with corpus_bags.in_place_of_generate():
            nums = control.control_numbers(cell, seed,
                                           str(tmp_path / str(seed)))
        assert any(v > limits[k] for k, v in nums.items() if k in limits)
    monkeypatch.setattr(harness, "load_cell",
                        lambda name, root=bags_root: cell)
    assert control_bags.main(["--workload", tiny_bags_tree.CELL,
                              "--seeds", "4"]) == 0
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["correct"] is False and said["fails"]
