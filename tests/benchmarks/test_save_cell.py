"""The cell that saves while it trains (``kind: train_save``,
``drivers/train_save.py``, ISSUE 52), at a size the CPU holds: the cell
end to end by ``--rehearse-cpu``, each fault the save's checks exist
for planted and caught, the control, the work directory gone on every
path, ``benchmarks/save_reference.py`` on built arrays, and the
entries and files the cell is made of. Nothing here describes a TPU
topology."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import tiny_save_tree
import tiny_tree
from benchmarks import harness, save_reference, weights
from benchmarks.drivers import train_save

REPO = tiny_tree.REPO
CELL = tiny_save_tree.CELL
SEED = 2 ** 31 + 13
SAVE_CHECKS = list(train_save.SAVE_CHECKS)
NEW_METRICS = ["save_pause_s_per_save", "save_snapshot_s_per_save",
               "save_settle_s_per_save", "save_snapshot_bytes_per_s",
               "save_commit_s", "rate_under_write_share",
               "save_device_bytes_extra", "idle_in_checkpoint_pause"]
# PR 37's ten host-loop lists are held EQUAL to four cells
# (test_loop_idle_by_phase.py): a `benchmark` PR's to widen.
HELD_TO_FOUR = ["bookkeeping_s_per_step", "loop_unnamed_share",
                "barrier_flush_s", "pipeline_open_s", "first_batch_s",
                "idle_unnamed", "idle_in_bookkeeping",
                "idle_in_barrier_flush", "idle_in_pipeline_open",
                "idle_in_first_batch"]


@pytest.fixture(scope="module")
def save_root(tmp_path_factory):
    return tiny_save_tree.make(str(tmp_path_factory.mktemp("save")))


@pytest.fixture(autouse=True)
def _work_root_of_its_own(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path / "work"))


@pytest.fixture
def one_device(monkeypatch):
    """An in-process run takes the one-chip path, the cell's own (the
    suite has eight CPU devices)."""
    import jax
    monkeypatch.setattr(jax, "device_count", lambda: 1)


def _spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


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


# ---- the entries and the files ------------------------------------------

def test_the_cell_is_one_chip_on_the_configuration_the_issue_names():
    spec = _spec()
    cell = next(w for w in spec["workloads"] if w["name"] == "fm16-train-save")
    assert cell == dict(cell, config="fm-k16-criteo1tb-ckpt",
                        traffic="train-save", chips=1)
    assert "over-weighs" in cell["why"]
    assert "save_pause_s_per_save" in cell["why"]
    assert spec["workloads"][-1] == cell            # appended, not inserted
    loaded = harness.load_cell("fm16-train-save")
    assert loaded.kind == "train_save"
    tr = loaded.traffic
    zipf = harness.load_cell("fm16-train-zipf").traffic
    for key in ("corpus_batches", "corpus_files", "corpus_passes",
                "steps_per_reading", "checked_steps", "trace_seconds"):
        assert tr[key] == zipf[key], key
    epoch = tr["corpus_batches"] * tr["corpus_passes"]
    warm = tr["warmup_readings"] * tr["steps_per_reading"]
    assert (tr["save_steps"], tr["saves_in_window"]) == (6144, 1)
    assert warm == 6016 == tr["save_steps"] - epoch     # one epoch before


def test_the_configuration_is_fm16s_plus_the_save():
    spec = _spec()
    entry = spec["configs"][-1]
    assert entry["name"] == "fm-k16-criteo1tb-ckpt"
    assert len(entry["source"]) <= 200 and "Check-N-Run" in entry["source"]
    assert entry["reduced"] == ["vocabulary_size", "corpus_lines",
                                "save_interval"]
    with open(os.path.join(REPO, entry["file"])) as fh:
        ckpt = json.load(fh)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "fm-k16-criteo1tb.json")) as fh:
        base = json.load(fh)
    assert ckpt["program"]["General"] == base["program"]["General"]
    assert ckpt["program"]["Train"] == dict(
        base["program"]["Train"], save_steps=6144, ckpt_verify="size")
    assert ckpt["features"] == base["features"]
    assert ckpt["check_limits"]["train"] == base["check_limits"]["train"]
    # control.py looks a cell's limits up under its traffic's kind: the
    # three train limits are repeated there, as train_stream's are
    assert ckpt["check_limits"]["train_save"] == dict(
        base["check_limits"]["train"], **{k: 0 for k in SAVE_CHECKS})
    assert "over-weighs" in ckpt["reduced_why"]["save_interval"]
    assert set(ckpt["guarantees"]) >= {"snapshot", "one_in_flight",
                                       "visibility", "wait_true", "contract"}
    assert ckpt["program"]["Train"]["save_steps"] == harness.load_cell(
        "fm16-train-save").traffic["save_steps"]


def test_the_cell_is_on_the_lists_the_issue_names():
    spec = _spec()
    by_name = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == ["fm16-train-save"], name
        assert m["moves"] == "train_examples_per_s_per_chip"
        with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                               name + ".json")) as fh:
            own = json.load(fh)
        assert {k: own[k] for k in m if k != "workloads"} == {
            k: v for k, v in m.items() if k != "workloads"}
        assert own["reader"] in ("telemetry_window", "context_value",
                                 "loop_idle_by_phase")
    assert [m["name"] for m in spec["per_layer"][-len(NEW_METRICS):]
            ] == NEW_METRICS
    assert len({by_name[n]["layer"] for n in NEW_METRICS}) == 1
    for name, m in by_name.items():
        wl = m.get("workloads") or []
        if "fm16-train-zipf" in wl and name not in HELD_TO_FOUR:
            assert wl[-1] == "fm16-train-save", name
        if name in HELD_TO_FOUR:
            assert "fm16-train-save" not in wl, name
    cell = harness.load_cell("fm16-train-save")
    assert {"train_examples_per_s_per_chip", "setup_s"} == {
        m["name"] for m in cell.end_to_end}
    assert {"step_roofline", "step_device_ms", "compiles_per_epoch",
            *NEW_METRICS} <= {m["name"] for m in cell.per_layer}


# ---- the cell end to end -------------------------------------------------

def _rehearse(root, *extra, seconds="1.5", trace="1"):
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(SEED), "--seconds", seconds, "--trace", trace,
         "--rehearse-cpu", *extra], cwd=root, env=tiny_tree.env(),
        capture_output=True, text=True, timeout=600)


def test_the_cell_runs_by_rehearse_cpu(save_root):
    """One CPU device: the one-chip path, the host snapshot in row
    blocks. Every check is printed with its limit and holds, the
    control fails, the new metrics read the stream, the context and
    the trace, nothing compiles in the window and the work directory's
    model is gone."""
    p = _rehearse(save_root)
    assert p.returncode == 0, p.stderr[-3000:]
    out = p.stdout.strip().splitlines()
    last = json.loads(out[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}   # a rehearsal
    checks = _checks(p.stdout)
    assert set(SAVE_CHECKS) <= set(checks)
    assert all(checks[name][:2] == (0.0, 0.0) for name in SAVE_CHECKS)
    assert {"loss_rel_gap_max", "grad_norm_gap_worst_leaf",
            "update_norm_gap_worst_leaf_3_steps",
            "span_examples_credited_not_counted"} <= set(checks)
    assert all(ok for _, _, ok in checks.values())
    assert set(SAVE_CHECKS) <= set(last["check"])
    control = [l for l in out if l.startswith("control: the state one ")]
    assert len(control) == 1
    assert control[0].endswith("it fails, as it must")
    assert " reads saved_rows_not_of_step 0 " not in control[0]
    saves = next(l for l in out if l.startswith("the span's saves: 1;"))
    assert all(f" {c.split('/')[1]} " in saves
               for c in train_save.SAVE_COUNTERS)
    shown = json.loads(next(l for l in out if l.startswith("metrics: "))
                       [len("metrics: "):])
    assert set(NEW_METRICS) <= set(shown)
    assert shown["compiles_per_epoch"]["value"] == 0.0
    assert shown["save_pause_s_per_save"]["value"] >= shown[
        "save_snapshot_s_per_save"]["value"] > 0
    assert shown["save_settle_s_per_save"]["value"] >= 0
    assert shown["save_snapshot_bytes_per_s"]["value"] > 0
    assert shown["save_commit_s"]["value"] > 0
    assert shown["rate_under_write_share"]["value"] > 0
    assert shown["save_device_bytes_extra"]["value"] == 0
    assert 0 < shown["idle_in_checkpoint_pause"]["value"] <= 100
    work = os.path.join(save_root, ".bench_work", CELL)
    assert os.path.isdir(work) and not os.path.exists(
        os.path.join(work, "model"))
    events = harness.read_telemetry(os.path.join(work, "metrics.jsonl"))
    snap = [e["counters"] for e in events if e.get("event") == "metrics"
            and "counters" in e][-1]
    table_bytes = 2 * 4096 * 2 * 5 * 4      # [ckpt_rows, D] f32, twice
    assert snap["checkpoint/saves"] == 1
    assert snap["checkpoint/snapshot_bytes"] == table_bytes


# ---- the faults the checks must catch --------------------------------------

def _run_in_process(root, seconds=1.0, **kw):
    train = kw.pop("train", {})
    traffic = kw.pop("traffic", {})
    cell = harness.load_cell(CELL, root)
    cell.config["program"]["Train"].update(train)
    cell.traffic.update(traffic)
    run = harness.Run(cell=cell, seed=SEED, seconds=seconds, trace=False,
                      rehearse=True, t0=time.monotonic())
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return run, train_save.run(run, device, **kw)


class _OneStepLate:
    """The snapshot is taken after the NEXT step: the save the loop
    asks for is put off, and made, under the step's number, once the
    step after it has run."""

    def __init__(self, monkeypatch):
        from fast_tffm_tpu import train as program
        self.owed, late = None, self
        real_step = program.StepLoop.step

        def step(loop, *args, **kwargs):
            real_step(loop, *args, **kwargs)
            if late.owed is not None:
                save, epoch, wait, kw = late.owed
                late.owed = None
                now, loop.global_step = loop.global_step, loop.global_step - 1
                try:
                    save(loop, epoch, wait, **kw)
                finally:
                    loop.global_step = now
        monkeypatch.setattr(program.StepLoop, "step", step)

    def save(self, save):
        def broken(loop, epoch, wait, **kw):
            self.owed = (save, epoch, wait, kw)
        return broken


class _StateOfTheStart:
    """The state handed to the save is the one the job began with."""

    def __init__(self):
        self.start = None

    def step(self, step):
        def wrapped(table, acc, *args, **kwargs):
            import jax.numpy as jnp
            if self.start is None:
                self.start = (jnp.array(table, copy=True),
                              jnp.array(acc, copy=True))
            return step(table, acc, *args, **kwargs)
        return wrapped

    def save(self, save):
        def broken(loop, epoch, wait, **kw):
            kept = loop.table, loop.acc
            loop.table, loop.acc = self.start
            try:
                save(loop, epoch, wait, **kw)
            finally:
                loop.table, loop.acc = kept
        return broken


def _tail_off_contract(monkeypatch):
    from fast_tffm_tpu import train as program
    real = program.ckpt_state

    def broken(cfg, table, acc, into=None):
        t, a = real(cfg, table, acc, into=into)
        t[cfg.num_rows + 3, 1] = 1.0
        return t, a
    monkeypatch.setattr(program, "ckpt_state", broken)


def _biggest_file(step_dir):
    files = [os.path.join(r, f) for r, _, fs in os.walk(step_dir)
             for f in fs]
    return max(files, key=os.path.getsize)


def _cut_an_array_file_short(directory, step):
    path = _biggest_file(os.path.join(directory, str(step)))
    with open(path, "r+b") as fh:
        fh.truncate(os.path.getsize(path) // 2)


def _edit_the_manifest(directory, step):
    path = os.path.join(directory, f"manifest-{step}.json")
    with open(path) as fh:
        man = json.load(fh)
    rel = max(man["files"], key=lambda k: man["files"][k]["size"])
    man["files"][rel]["crc32"] ^= 1
    with open(path, "w") as fh:
        json.dump(man, fh)


@pytest.mark.parametrize("fault,failing", [
    ("none", []),
    ("a snapshot one step late", ["saved_rows_not_of_step"]),
    ("a state returned unchanged", ["saved_rows_not_of_step"]),
    ("a pad tail that is not its contract", ["saved_untouched_rows_off"]),
    ("an array file cut short", ["manifest_mismatches"]),
    ("a manifest that disagrees", ["manifest_mismatches"]),
    ("a second save in the span", ["saves_in_span_not_one"]),
    ("a save off its schedule", ["save_off_schedule"]),
])
def test_a_fault_of_the_save_comes_out_not_correct(save_root, monkeypatch,
                                                   one_device, capsys, fault,
                                                   failing):
    """In this process, with the fault planted in the program's
    configuration, underneath the probes, or in the committed step
    behind the program's back. The training checks do not see it; the
    work directory's model is gone on the failing paths too."""
    kw = {}
    if fault == "a snapshot one step late":
        kw["save_breaker"] = _OneStepLate(monkeypatch).save
    elif fault == "a state returned unchanged":
        stale = _StateOfTheStart()
        kw.update(save_breaker=stale.save, breaker=stale.step)
    elif fault == "a pad tail that is not its contract":
        _tail_off_contract(monkeypatch)
    elif fault == "an array file cut short":
        kw["after_commit"] = _cut_an_array_file_short
    elif fault == "a manifest that disagrees":
        kw["after_commit"] = _edit_the_manifest
    elif fault == "a second save in the span":
        # a save an epoch: the held step is long pruned when it is read
        kw.update(train={"save_steps": 8}, traffic={"save_steps": 8})
    elif fault == "a save off its schedule":
        # the program saves every 2,044 steps where the cell states 2,048
        kw.update(train={"save_steps": 2044})
    run, line = _run_in_process(save_root, **kw)
    said = capsys.readouterr().out
    checks = _checks(said)
    assert json.loads(line)["correct"] is (not failing)
    assert set(SAVE_CHECKS) <= set(checks)
    failed = [k for k in SAVE_CHECKS if not checks[k][2]]
    if fault in ("an array file cut short", "a second save in the span"):
        # the reader may or may not get past the torn file, and finds
        # no step that was pruned; these never hold
        assert set(failing) <= set(failed)
    else:
        assert failed == failing
    assert all(checks[k][0] > 0 for k in failed)
    assert all(checks[k][2] for k in (
        "grad_norm_gap_worst_leaf", "update_norm_gap_worst_leaf_3_steps",
        "feed_examples_not_in_corpus", "span_examples_credited_not_counted"))
    control = [l for l in said.splitlines() if l.startswith("control: ")]
    if fault == "a snapshot one step late":
        # exactly what the control stands for: it PASSES here
        assert control and control[0].endswith("IT PASSES")
    elif fault not in ("an array file cut short",
                       "a second save in the span"):
        assert control and control[0].endswith("it fails, as it must")
    assert not os.path.exists(os.path.join(run.work_dir, "model"))


def test_a_run_that_raises_leaves_no_model_behind(save_root, one_device):
    """A save that fails (the parent's, at the cell's size, for want of
    memory) ends the run as a RunFailed, and the model directory goes."""
    def no_room(save):
        def broken(loop, epoch, wait, **kw):
            os.makedirs(os.path.join(os.path.dirname(
                loop.s.cfg.model_file), "left"), exist_ok=True)
            raise MemoryError("RESOURCE_EXHAUSTED: 6.0G asked, 3.75G free")
        return broken
    cell = harness.load_cell(CELL, save_root)
    run = harness.Run(cell=cell, seed=SEED, seconds=1.0, trace=False,
                      rehearse=True, t0=time.monotonic())
    with pytest.raises(harness.RunFailed, match="RESOURCE_EXHAUSTED"):
        train_save.run(run, {"platform": "cpu", "kind": "cpu", "count": 1},
                       save_breaker=no_room)
    assert not os.path.exists(os.path.join(run.work_dir, "model"))


def test_both_cells_train_on_the_same_batches(save_root, monkeypatch,
                                              one_device):
    """On one seed the cell is fed ``tiny-train``'s own batches (as
    ``fm16-train-save`` is fed ``fm16-train-zipf``'s): the checked
    steps' feeds and losses are equal to the bit, so the two cells'
    difference is the save."""
    from benchmarks import check
    from benchmarks.drivers import train as train_driver
    seen = []
    real = check.train_checks

    def keep(model, rows, vr, seed, corpus, probe, *a, **k):
        seen.append((probe.feeds, probe.losses, corpus.signatures()))
        return real(model, rows, vr, seed, corpus, probe, *a, **k)
    monkeypatch.setattr(check, "train_checks", keep)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    for name, driver in (("tiny-train", train_driver), (CELL, train_save)):
        cell = harness.load_cell(name, save_root)
        run = harness.Run(cell=cell, seed=SEED, seconds=1.0, trace=False,
                          rehearse=True, t0=time.monotonic())
        assert json.loads(driver.run(run, device))["correct"] is True
    (feeds_a, losses_a, sigs_a), (feeds_b, losses_b, sigs_b) = seen
    assert (sigs_a == sigs_b).all() and losses_a == losses_b
    for a, b in zip(feeds_a, feeds_b):
        assert set(a) == set(b)
        assert all((a[k] == b[k]).all() for k in a)


# ---- the reference on built arrays -----------------------------------------

NUM_ROWS, DIM, INIT = 5000, 5, 0.1


def _sound(seed=7):
    rows = save_reference.contract_rows(NUM_ROWS)
    table = np.zeros((rows, DIM), np.float32)
    table[:NUM_ROWS] = weights.table_rows_numpy(
        np.arange(NUM_ROWS), DIM, seed, 0.01, NUM_ROWS)
    acc = np.full((rows, DIM), INIT, np.float32)
    touched = np.array([3, 17, 99, 4000], np.int64)
    table[touched] += 0.5
    acc[touched] += 0.25
    return {"table": table, "acc": acc, "step": 24, "epoch": 2,
            "vocab": NUM_ROWS - 1}, touched


def _untouched_off(saved, touched, seed=7):
    return save_reference.untouched_rows_off(
        saved, touched, NUM_ROWS, DIM, seed, 0.01, INIT, 2048)


def test_the_reference_passes_a_sound_step_and_counts_each_fault():
    saved, touched = _sound()
    of_step = saved["table"][touched].copy(), saved["acc"][touched].copy()
    assert save_reference.contract_rows(NUM_ROWS) == 8192
    assert save_reference.rows_not_of_step(saved, touched, *of_step) == 0
    assert _untouched_off(saved, touched) == 0
    assert save_reference.scalars_off(
        saved, {"step": 24, "epoch": 2, "vocab": NUM_ROWS - 1}) == 0
    # one bit of one touched row, in either array
    later = of_step[0].copy()
    later[2, 4] = np.nextafter(later[2, 4], np.float32(1))
    assert save_reference.rows_not_of_step(saved, touched, later,
                                           of_step[1]) == 1
    assert save_reference.rows_not_of_step(saved, touched, of_step[0],
                                           of_step[1] + 1) == len(touched)
    assert save_reference.rows_not_of_step(None, touched, *of_step) == 4
    # -0.0 is not 0.0: the comparison is of bits
    assert save_reference.rows_differ(np.zeros((1, 2), np.float32),
                                      -np.zeros((1, 2), np.float32)).all()
    # what no step can have written
    for name, row, off in (("table", NUM_ROWS - 1, 1), ("table", 8191, 1),
                           ("acc", NUM_ROWS + 5, 1), ("acc", NUM_ROWS - 1, 1)):
        broken, _ = _sound()
        broken[name][row, 0] += 1.0
        assert _untouched_off(broken, touched) == off, (name, row)
    sample = save_reference.untouched_sample(touched, NUM_ROWS, 2048, 7)
    assert len(sample) > 1000 and not np.isin(sample, touched).any()
    assert sample.max() < NUM_ROWS - 1
    broken, _ = _sound()
    broken["table"][sample[5]] = 0.0
    broken["acc"][sample[9], 2] = 0.2
    assert _untouched_off(broken, touched) == 2
    assert _untouched_off(saved, touched, seed=8) > 1000    # another table
    # a shape or a dtype that is not the contract's
    cut = dict(saved, table=saved["table"][:NUM_ROWS])
    assert _untouched_off(cut, touched) > len(sample)
    assert _untouched_off(dict(saved, acc=saved["acc"].astype(np.float64)),
                          touched) > len(sample)
    assert _untouched_off(None, touched) == len(sample)
    assert save_reference.scalars_off(
        saved, {"step": 25, "epoch": 2, "vocab": NUM_ROWS}) == 2
    assert save_reference.scalars_off(None, {}) == 3


def test_the_reference_reads_a_step_without_the_programs_checkpoint_module(
        tmp_path):
    """``read_step`` on a step the program saved: host NumPy in the
    contract's shape; and the module imports nothing of checkpoint.py."""
    from fast_tffm_tpu.checkpoint import CheckpointState, ckpt_state
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.models.fm import init_accumulator, init_table
    with open(save_reference.__file__) as fh:
        source = fh.read()
    assert "import fast_tffm_tpu" not in source
    assert "from fast_tffm_tpu" not in source
    cfg = FmConfig(vocabulary_size=4999, factor_num=4,
                   model_file=str(tmp_path / "m" / "fm"))
    table, acc = init_table(cfg), init_accumulator(cfg)
    ckpt = CheckpointState(cfg.model_file)
    ckpt.save(24, *ckpt_state(cfg, table, acc),
              vocabulary_size=cfg.vocabulary_size, wait=True, epoch=2)
    ckpt.close()
    saved = save_reference.read_step(ckpt.directory, 24)
    assert type(saved["table"]) is np.ndarray
    assert saved["table"].shape == saved["acc"].shape == (8192, 5)
    assert (saved["table"][:5000] == np.asarray(table)).all()
    assert (saved["acc"][5000:] == np.float32(cfg.adagrad_init)).all()
    assert (int(saved["step"]), int(saved["epoch"]), int(saved["vocab"])
            ) == (24, 2, 4999)
    with pytest.raises(Exception):
        save_reference.read_step(ckpt.directory, 25)


def test_the_share_under_the_write_reads_the_readings_it_names():
    rd = [(t, t + 1.0, 100.0) for t in range(0, 4)]         # before: 100/s
    rd += [(4.0, 7.0, 100.0)]                               # holds the pause
    rd += [(7.0, 9.0, 100.0), (9.0, 11.0, 100.0)]           # under the write
    rd += [(11.0 + t, 12.0 + t, 100.0) for t in range(3)]   # after it
    first = {"t_call": 4.5, "t_return": 6.5, "t_commit": 10.0}
    assert train_save.under_write_share(rd, first, 0.0, 14.0) == 50.0
    # committed before the next reading began: that reading stands for it
    quick = dict(first, t_commit=6.6)
    assert train_save.under_write_share(rd, quick, 0.0, 14.0) == 50.0
    # not committed inside the window: every reading after the return
    late = {"t_call": 4.5, "t_return": 6.5}
    assert train_save.under_write_share(rd, late, 0.0, 14.0) == 100.0
    assert train_save.under_write_share(rd, None, 0.0, 14.0) is None
    assert train_save.under_write_share(rd, first, 5.0, 14.0) is None
    # a traced run's profiler wrote its trace out inside one reading
    rd[5] = (7.0, 9.0, 1.0)
    assert train_save.under_write_share(rd, first, 0.0, 14.0,
                                        skip=(7.5, 8.9)) == 50.0
