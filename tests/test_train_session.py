"""The train session in parts (PR 29): the benchmark's seams reached
from outside ``tests/benchmarks/``, one step body under both run
modes, and the shape and layering of ``fast_tffm_tpu/train.py`` held
by its source."""

import ast
import os
import re

import jax
import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN_PY = os.path.join(REPO, "fast_tffm_tpu", "train.py")

# No function of train.py is longer than this (train(), the elastic
# driver, is the longest at 288), and none nests functions more than
# one level deep.
MAX_FUNCTION_LINES = 300
# What the file was before it was split; it may only get shorter.
LINES_BEFORE_THE_SPLIT = 2460


def _corpus(n_lines, seed, vocab=200, width=4):
    rng = np.random.default_rng(seed)
    return "".join(
        " ".join([str(int(rng.random() < 0.5))]
                 + [f"{i}:1" for i in rng.choice(vocab, width, replace=False)])
        + "\n" for _ in range(n_lines))


# ---- (1) the seams --------------------------------------------------------

@pytest.mark.parametrize("path", ["one_device", "one_device_packed", "mesh"])
def test_train_builds_state_and_step_through_the_rebound_names(
        tmp_path, monkeypatch, path):
    """``benchmarks/drivers/train.py`` gets its probe and its seeded
    weights in by rebinding ``make_train_step`` / ``init_table`` on
    ``fast_tffm_tpu.train``, ``make_packed_train_step`` on
    ``models.fm`` and ``make_sharded_train_step`` /
    ``init_sharded_state`` on ``parallel.sharded``. The builder of
    state and step has to look every one of them up when it runs, and
    call the step with keyword batch arguments."""
    import fast_tffm_tpu.models.fm as fm_mod
    import fast_tffm_tpu.parallel.sharded as sharded_mod
    import fast_tffm_tpu.train as train_mod
    called = {"state": 0, "build": 0, "steps": 0, "kwargs": set()}

    def counted_builder(orig):
        def build(*a, **k):
            called["build"] += 1
            step = orig(*a, **k)

            def probed(*args, **kwargs):
                called["steps"] += 1
                called["kwargs"] |= set(kwargs)
                return step(*args, **kwargs)
            return probed
        return build

    def counted_state(orig):
        def init(*a, **k):
            called["state"] += 1
            return orig(*a, **k)
        return init

    if path == "mesh":
        assert jax.device_count() > 1  # the suite's eight CPU devices
        seams = [(sharded_mod, "make_sharded_train_step", counted_builder),
                 (sharded_mod, "init_sharded_state", counted_state)]
    else:
        monkeypatch.setattr(jax, "device_count", lambda: 1)
        builder = ((fm_mod, "make_packed_train_step")
                   if path == "one_device_packed"
                   else (train_mod, "make_train_step"))
        seams = [builder + (counted_builder,),
                 (train_mod, "init_table", counted_state)]
    for mod, name, wrap in seams:
        monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    (tmp_path / "d.txt").write_text(_corpus(64, seed=3))
    cfg = FmConfig(
        vocabulary_size=200, factor_num=2, batch_size=16,
        train_files=(str(tmp_path / "d.txt"),), epoch_num=1,
        shuffle=False, log_steps=0,
        wire_format="packed" if path == "one_device_packed" else "padded",
        model_file=str(tmp_path / "m" / "fm"))
    train_mod.train(cfg)
    assert called["state"] == 1
    assert called["build"] >= 1
    assert called["steps"] == 4          # 64 lines in batches of 16
    assert {"labels", "weights"} <= called["kwargs"]


# ---- (2) one step body, two loops -----------------------------------------

def _run_mode(tmp_path, mode, corpus_lines):
    from fast_tffm_tpu.obs.attribution import summarize
    from fast_tffm_tpu.train import train
    work = tmp_path / mode
    (work / "data").mkdir(parents=True)  # the stream reads every file here
    data = work / "data" / "a.txt"
    data.write_text(corpus_lines)
    common = dict(
        vocabulary_size=200, factor_num=2, batch_size=16, shuffle=False,
        seed=0, learning_rate=0.1, log_steps=2, host_threads=1,
        metrics_file=str(work / "metrics.jsonl"),
        metrics_flush_steps=2, log_file=str(work / "t.log"),
        model_file=str(work / "m" / "fm"))
    if mode == "epochs":
        cfg = FmConfig(train_files=(str(data),), epoch_num=1, **common)
    else:
        (work / "data" / "a.txt.done").touch()
        (work / "data" / "STOP").touch()
        cfg = FmConfig(run_mode="stream", stream_dir=str(work / "data"),
                       stream_poll_seconds=0.01, seal_policy="done",
                       **common)
    table = np.asarray(train(cfg))
    text = (work / "t.log").read_text()
    lines = re.findall(
        r"step (\d+) epoch (\d+) loss (\S+) examples/sec", text)
    done = re.search(r"training done: (\d+) steps", text)
    return cfg, table, lines, int(done.group(1)), summarize(
        [cfg.metrics_file])["counters"]


def _batches(cfg, mode):
    from fast_tffm_tpu.data import stream as sl
    from fast_tffm_tpu.data.pipeline import batch_iterator
    if mode == "epochs":
        return list(batch_iterator(cfg, cfg.train_files, training=True,
                                   epochs=1, seed=cfg.seed))
    tr = sl.StreamTracker(cfg.stream_dir, 0.01, "done")
    src = sl.StreamSource(cfg, tr)
    out = []
    try:
        while True:
            b = src.next_batch(block=True)
            if b is sl.DONE:
                return out
            out.append(b)
    finally:
        src.close()


def test_epoch_and_stream_loops_train_the_same_steps(tmp_path):
    """One small one-file corpus through ``run_mode = epochs`` (one
    epoch, no shuffle) and through ``run_mode = stream`` over the same
    file, sealed: the two loops drive one ``StepLoop.step``, so they
    count the same steps, examples and wire bytes, owe one loss line
    per ``log_steps`` steps with the same losses, and end at the same
    table, bit for bit. That needs the two data planes to cut the same
    batches from the file, which is asserted first (64 lines in
    batches of 16: no short tail for either to treat its own way)."""
    corpus = _corpus(64, seed=11)
    runs = {m: _run_mode(tmp_path, m, corpus) for m in ("epochs", "stream")}
    cut = {m: _batches(runs[m][0], m) for m in runs}
    assert len(cut["epochs"]) == len(cut["stream"]) == 4
    for a, b in zip(cut["epochs"], cut["stream"]):
        assert a.num_real == b.num_real == 16
        for name in ("labels", "weights", "uniq_ids", "local_idx", "vals"):
            np.testing.assert_array_equal(getattr(a, name),
                                          getattr(b, name), err_msg=name)
    (_, t_e, lines_e, steps_e, c_e), (_, t_s, lines_s, steps_s, c_s) = (
        runs["epochs"], runs["stream"])
    assert steps_e == steps_s == 4
    assert c_e["train/steps"] == c_s["train/steps"] == 4
    assert c_e["train/examples"] == c_s["train/examples"] == 64
    assert c_e["train/h2d_bytes"] == c_s["train/h2d_bytes"] > 0
    # one line per log_steps steps, the same steps and losses in both
    assert [int(s) for s, _, _ in lines_e] == [2, 4]
    assert lines_e == lines_s
    np.testing.assert_array_equal(t_e, t_s)


# ---- (3) shape, by source -------------------------------------------------

def _functions(tree):
    """(qualname, node, depth of function nesting) of every def."""
    out = []

    def visit(node, prefix, depth):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append((prefix + child.name, child, depth))
                visit(child, prefix + child.name + ".", depth + 1)
            elif isinstance(child, ast.ClassDef):
                visit(child, prefix + child.name + ".", depth)
            else:
                visit(child, prefix, depth)
    visit(tree, "", 0)
    return out


def _calls_of(node, name):
    return [c for c in ast.walk(node) if isinstance(c, ast.Call) and (
        (isinstance(c.func, ast.Attribute) and c.func.attr == name)
        or (isinstance(c.func, ast.Name) and c.func.id == name))]


def test_train_py_keeps_its_shape():
    """The session stays in parts: no function of ``train.py`` over
    MAX_FUNCTION_LINES, none nested more than one level deep, no
    ``nonlocal`` (state lives on ``_Session`` and ``StepLoop``), the
    file shorter than it was; and the step is one body: ``dispatch``
    (the old ``_wire_step``) has one call site, in ``StepLoop.step``,
    which the epoch loop and the stream loop each call once."""
    with open(TRAIN_PY) as fh:
        src = fh.read()
    tree = ast.parse(src)
    assert src.count("\n") < LINES_BEFORE_THE_SPLIT
    assert not [n for n in ast.walk(tree) if isinstance(n, ast.Nonlocal)]
    funcs = _functions(tree)
    too_long = [(q, n.end_lineno - n.lineno + 1) for q, n, _ in funcs
                if n.end_lineno - n.lineno + 1 > MAX_FUNCTION_LINES]
    assert too_long == []
    assert [q for q, _, depth in funcs if depth > 1] == []
    by_name = {q: n for q, n, _ in funcs}
    for part in ("_train_session", "_restore", "_build_state_and_step",
                 "StepLoop.step", "StepLoop.dispatch", "_run_epochs",
                 "_run_stream", "_finish"):
        assert part in by_name, part
    holders = [q for q, n, _ in funcs if _calls_of(n, "dispatch")]
    assert holders == ["StepLoop.step"]
    assert len(_calls_of(by_name["StepLoop.step"], "dispatch")) == 1
    steppers = sorted(q for q, n, _ in funcs if _calls_of(n, "step"))
    assert steppers == ["_run_epochs", "_stream_step"]
    # the builder of state and step is where the benchmark looks
    built = {c.func.id for c in ast.walk(by_name["_build_state_and_step"])
             if isinstance(c, ast.Call) and isinstance(c.func, ast.Name)}
    assert {"make_train_step", "init_table", "make_packed_train_step",
            "make_sharded_train_step", "init_sharded_state"} <= built


# ---- (4) layering, by source ----------------------------------------------

def test_nothing_below_train_py_imports_it():
    """``train.py`` is the top of the package: ``lookup.py``,
    ``predict.py``, ``checkpoint.py``, ``serve/`` and the rest import
    none of it (the checkpoint's format helpers they used to reach for
    live in ``checkpoint.py``). Entry points (``run_tffm.py``,
    ``tools/``, the benchmark) may."""
    pkg = os.path.join(REPO, "fast_tffm_tpu")
    offenders = []
    for d, _, names in os.walk(pkg):
        for n in names:
            path = os.path.join(d, n)
            if not n.endswith(".py") or path == TRAIN_PY:
                continue
            with open(path) as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    base = node.module or ""
                    mods = [base] + [f"{base}.{a.name}" for a in node.names]
                else:
                    continue
                if any(m == "fast_tffm_tpu.train"
                       or m.startswith("fast_tffm_tpu.train.")
                       or (isinstance(node, ast.ImportFrom) and node.level
                           and m in ("train", ".train"))
                       for m in mods):
                    offenders.append(
                        f"{os.path.relpath(path, REPO)}:{node.lineno}")
    assert offenders == []
    import fast_tffm_tpu.checkpoint as ckpt_mod
    for helper in ("ckpt_state", "checkpoint_template",
                   "resume_start_epoch", "check_restored_vocab"):
        assert callable(getattr(ckpt_mod, helper))
