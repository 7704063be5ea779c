"""The one-device train state's layout (models/fm.py ``TrainStep``,
ISSUE 28): the step compiles its first bucket with the layout of table
and accumulator left to the compiler, holds what the compiler chose,
re-lays a state that arrives otherwise ONCE, and hands its results to
the next call as they are. On the CPU the compiler's choice is the
default, so the forced cases pin a column-major layout on a fresh step
object to drive the re-lay path; the compile-only tests at the bottom
ask the TPU's own compiler, for a described v5e, what it chooses at the
benchmark's two one-chip sizes."""

import contextlib
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from fast_tffm_tpu.checkpoint import CheckpointState
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import (_fit_slots, _ladder_fit,
                                         batch_iterator)
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.models.fm import (ModelSpec, TrainStep, batch_args,
                                     init_accumulator, init_table,
                                     make_batch_scorer, train_step_body)
from fast_tffm_tpu.obs.telemetry import RunTelemetry, activate
from fast_tffm_tpu.checkpoint import checkpoint_template, ckpt_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTER = "train/state_relayouts"
COLUMN_MAJOR = Layout(major_to_minor=(1, 0), tiling=())
MODELS = ("fm", "ffm")
LAYOUTS = ("compilers", "column_major")


def _corpus(tmp_path, model, n=64, seed=5):
    """Blocks of 16 lines, alternately narrow (the 4 rung) and wide
    (the 8 rung): two ladder rungs, so two programs of one step."""
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(n):
        lo, hi = (1, 5) if (i // 16) % 2 == 0 else (5, 9)
        ids = rng.choice(300, size=int(rng.integers(lo, hi)), replace=False)
        field = (lambda: f"{int(rng.integers(0, 3))}:") if model == "ffm" \
            else (lambda: "")
        lines.append(" ".join(
            ["1" if rng.random() < 0.4 else "0"]
            + [f"{field()}{j}:{rng.random():.4f}" for j in ids]))
    path = tmp_path / f"{model}.txt"
    path.write_text("\n".join(lines) + "\n")
    return str(path)


def _cfg(tmp_path, model, **kw):
    more = dict(model_type="ffm", field_num=3) if model == "ffm" else {}
    return FmConfig(vocabulary_size=300, factor_num=4, batch_size=16,
                    shuffle=False, bucket_ladder=(4, 8),
                    max_features_per_example=8, learning_rate=0.1,
                    factor_lambda=1e-3, bias_lambda=1e-3,
                    model_file=str(tmp_path / "m" / "fm"), **more, **kw)


def _setup(tmp_path, model):
    cfg = _cfg(tmp_path, model)
    spec = ModelSpec.from_config(cfg)
    batches = list(batch_iterator(cfg, [_corpus(tmp_path, model)],
                                  training=True, epochs=1))
    assert {b.vals.shape[-1] for b in batches} == {4, 8}
    return cfg, spec, batches


def _step(spec, layout):
    """A step object of this test's own (make_train_step's is shared by
    the process); ``column_major`` pins the layout the compiler would
    otherwise choose."""
    step = TrainStep(spec)
    if layout == "column_major":
        step._layout = COLUMN_MAJOR
    return step


def _relayouts(tel):
    return tel.registry.snapshot()["counters"].get(COUNTER, 0)


@pytest.fixture
def tel(tmp_path):
    t = RunTelemetry(str(tmp_path / "metrics.jsonl"), meta={})
    with activate(t):
        yield t
    t.close()


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("model", MODELS)
def test_four_steps_equal_a_plain_jit_bit_for_bit(tmp_path, tel, model,
                                                  layout):
    """Table, accumulator and loss after each of four steps over two
    rungs are those of ``jax.jit(train_step_body)``; the state is
    re-laid before the first step or never, and a step's results go
    into the next step untouched."""
    cfg, spec, batches = _setup(tmp_path, model)
    step = _step(spec, layout)
    plain = jax.jit(lambda t, a, **b: train_step_body(spec, t, a, **b))
    table, acc = init_table(cfg, 3), init_accumulator(cfg)
    want_t, want_a = init_table(cfg, 3), init_accumulator(cfg)
    counts = []
    for b in batches[:4]:
        fed = table
        table, acc, loss, _ = step(table, acc, **batch_args(b))
        want_t, want_a, want_loss, _ = plain(want_t, want_a,
                                             **batch_args(b))
        counts.append(_relayouts(tel))
        assert fed.is_deleted()         # donated, directly or re-laid
        np.testing.assert_array_equal(np.asarray(table),
                                      np.asarray(want_t))
        np.testing.assert_array_equal(np.asarray(acc), np.asarray(want_a))
        assert float(loss) == float(want_loss)
        assert table.format.layout == acc.format.layout == step._layout
    assert len(step._programs) == 2
    assert counts == [2 if layout == "column_major" else 0] * 4


def test_the_compilers_choice_is_logged_once_and_held(tmp_path, caplog):
    cfg, spec, batches = _setup(tmp_path, "fm")
    step = _step(spec, "compilers")
    logger = fm.get_logger()
    logger.addHandler(caplog.handler)
    try:
        table, acc = init_table(cfg), init_accumulator(cfg)
        for b in batches:
            table, acc, _, _ = step(table, acc, **batch_args(b))
    finally:
        logger.removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("train state layout:")]
    assert lines == ["train state layout: float32[301, 5]{1,0}, the "
                     "compiler's choice for this step's gather and "
                     "scatters, as the runtime lays it out anyway"]
    assert step._layout == init_table(cfg).format.layout


def test_a_numpy_state_and_a_traced_call_still_work(tmp_path):
    """Callers that hand the step host arrays (tests), and callers that
    trace it inside a program of their own (the benchmark's scope
    test), get the plain step."""
    cfg, spec, batches = _setup(tmp_path, "fm")
    step = _step(spec, "column_major")
    args = batch_args(batches[0])
    t0, a0 = np.asarray(init_table(cfg)), np.asarray(init_accumulator(cfg))
    t1, a1, loss, _ = step(t0, a0, **args)
    t2, a2, loss2, _ = jax.jit(lambda t, a: step(t, a, **args))(t0, a0)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    assert float(loss) == float(loss2)


def test_layouts_that_differ_fail_by_name(tmp_path, monkeypatch):
    """A compiler that laid the accumulator out otherwise than the
    table would make every step copy one of them: refused, not run."""
    cfg, spec, batches = _setup(tmp_path, "fm")
    step = _step(spec, "compilers")

    class Program:
        def __init__(self, program):
            (t, a, *rest), kw = program.input_formats
            self.input_formats = (
                (t, Format(COLUMN_MAJOR, a.sharding), *rest), kw)
            self.output_formats = program.output_formats

    lower = jax.stages.Lowered.compile
    monkeypatch.setattr(jax.stages.Lowered, "compile",
                        lambda self, *a, **k: Program(lower(self, *a, **k)))
    with pytest.raises(RuntimeError, match="different layouts.*'acc'"):
        step(init_table(cfg), init_accumulator(cfg),
             **batch_args(batches[0]))


@pytest.mark.parametrize("model", MODELS)
def test_other_readers_take_a_relaid_state_as_they_find_it(tmp_path,
                                                            model):
    """Validation scoring, a jitted row gather (the benchmark's probe),
    and a checkpoint save and restore read a column-major table and
    accumulator to the same values as the default layout's."""
    cfg, spec, batches = _setup(tmp_path, model)
    step = _step(spec, "column_major")
    table, acc = init_table(cfg, 7), init_accumulator(cfg)
    for b in batches[:2]:
        table, acc, _, _ = step(table, acc, **batch_args(b))
    assert table.format.layout == COLUMN_MAJOR
    plain_t, plain_a = jnp.array(np.asarray(table)), jnp.array(
        np.asarray(acc))
    assert plain_t.format.layout != COLUMN_MAJOR

    def scores(t):
        args = batch_args(batches[2])
        del args["labels"], args["weights"]
        return np.asarray(make_batch_scorer(spec)(t, args))
    np.testing.assert_array_equal(scores(table), scores(plain_t))

    gather = jax.jit(lambda t, i: t[i])
    ids = np.array([0, 5, 17, 300, 300], np.int32)
    np.testing.assert_array_equal(np.asarray(gather(acc, ids)),
                                  np.asarray(gather(plain_a, ids)))

    ckpt = CheckpointState(cfg.model_file)
    ckpt.save(2, *ckpt_state(cfg, table, acc),
              vocabulary_size=cfg.vocabulary_size, wait=True)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    got_t = restored["table"][:cfg.num_rows]
    got_a = restored["acc"][:cfg.num_rows]
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(plain_t))
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(plain_a))
    # the restored slice arrives in the default layout and is re-laid
    assert got_t.format.layout != COLUMN_MAJOR
    t3, a3, loss, _ = step(got_t, got_a, **batch_args(batches[3]))
    want = jax.jit(lambda t, a, **b: train_step_body(spec, t, a, **b))(
        plain_t, plain_a, **batch_args(batches[3]))
    assert t3.format.layout == a3.format.layout == COLUMN_MAJOR
    np.testing.assert_array_equal(np.asarray(t3), np.asarray(want[0]))
    assert float(loss) == float(want[2])


_TRAIN_TWICE = """
import sys
import numpy as np
import jax
from jax.experimental.layout import Layout
from fast_tffm_tpu.compile_cache import enable_compilation_cache
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.train import train
assert jax.device_count() == 1
enable_compilation_cache()
model, forced, path, out = sys.argv[1:5]
if forced == "column_major":
    init = fm.TrainStep.__init__
    def pinned(self, spec):
        init(self, spec)
        self._layout = Layout(major_to_minor=(1, 0), tiling=())
    fm.TrainStep.__init__ = pinned
more = dict(model_type="ffm", field_num=3) if model == "ffm" else {}
def cfg(epochs):
    return FmConfig(vocabulary_size=300, factor_num=4, batch_size=16,
                    shuffle=False, bucket_ladder=(4, 8),
                    max_features_per_example=8, learning_rate=0.1,
                    train_files=(path,), validation_files=(path,),
                    epoch_num=epochs, model_file=out + "/m/fm",
                    metrics_file=out + "/metrics.jsonl",
                    metrics_flush_steps=1, log_steps=1, **more)
train(cfg(1))
table = train(cfg(3))       # restores the first run's save, then saves
np.save(out + "/table.npy", np.asarray(table))
"""


@pytest.mark.parametrize("model", MODELS)
def test_train_resumes_from_a_checkpoint_slice_in_either_layout(tmp_path,
                                                                model):
    """``train()`` on one device (a subprocess: this process has
    eight): a run, then a run that restores its checkpoint, validates,
    saves and exports; with the compiler's layout, with a column-major
    one pinned, and with that again in a process that finds the first
    one's programs in the persistent cache (an executable read back
    from there labels its results with the default layout whatever
    they have, compile_cache.uncached). Same table to the bit; the
    stream counts 0 re-lays, or 2 a session; the log names the layout
    the compiler chose."""
    from fast_tffm_tpu.obs.sink import read_events
    path = _corpus(tmp_path, model)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    tables = {}
    for run_name in ("compilers", "column_major", "column_major.warm"):
        layout = run_name.split(".")[0]
        out = tmp_path / run_name
        out.mkdir()
        run = subprocess.run(
            [sys.executable, "-c", _TRAIN_TWICE, model, layout, path,
             str(out)], cwd=REPO, env=env, capture_output=True, text=True,
            timeout=300)
        assert run.returncode == 0, run.stdout + run.stderr
        assert run.stderr.count("restored checkpoint") == 1, run.stderr
        chosen = run.stderr.count("train state layout: float32[301, ")
        assert chosen == (1 if layout == "compilers" else 0), run.stderr
        last = [e for e in read_events(str(out / "metrics.jsonl"))
                if e.get("event") == "metrics"][-1]
        assert last["counters"][COUNTER] == (
            0 if layout == "compilers" else 2)
        tables[run_name] = np.load(out / "table.npy")
        assert np.isfinite(tables[run_name]).all()
    for run_name in ("column_major", "column_major.warm"):
        np.testing.assert_array_equal(tables["compilers"],
                                      tables[run_name])


# ---- what the TPU's compiler chooses, asked without a chip -----------------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A program compiled for a described chip can be written to the
    persistent cache but not read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


# The two one-chip cells of BENCHMARK.json: rows, batch, rung, and the
# slots a batch needs (22 fields ride the 24 rung and 39 features the
# 40 rung since PR 34; since PR 36 their 9.0k and 19.3k distinct rows
# ride the quarter-octave rungs 10,240 and 20,480, not 16,384 and
# 32,768).
CELLS = {
    "ffm4-train-zipf": (ModelSpec("ffm", 2, 4, 22, 1 << 23, "logistic",
                                  0.0, 0.0, 0.01), 8192, 24, 8960, 10240),
    "fm16-train-zipf": (ModelSpec("fm", 2, 16, 0, 1 << 26, "logistic",
                                  0.0, 0.0, 0.01), 8192, 40, 19260, 20480),
}


def _adagrad_walks_the_slots_twice(text, rows, dim):
    """The ``adagrad`` scope of an optimized step (ISSUE 38): two
    custom fusions, the accumulator's gather and ONE scatter whose two
    operands and two results are the table and the accumulator; the
    step has no other scatter into an array of the state's shape."""
    custom = re.findall(
        r'kind=kCustom, [^\n]*?op_name="[^"]*/adagrad/([^"/]+)"', text)
    assert sorted(custom) == ["gather", "scatter-add"], custom
    state = rf"f32\[{rows},{dim}\]\S*"
    walks = [ln for ln in text.splitlines()
             if re.search(r"\s(scatter|gather)\(", ln)
             and "/adagrad/" in ln]
    assert len(walks) == 2, walks
    scatter, = [ln for ln in walks if " scatter(" in ln]
    assert re.search(rf"= \({state}, {state}\) scatter\(", scatter), scatter
    assert len(re.search(r" scatter\(([^)]*)\)", scatter).group(1)
               .split(",")) == 5, scatter       # 2 operands, ids, 2 updates
    assert len(re.findall(rf"{state}\)? scatter\(", text)) == 1


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_v5e_step_has_no_copy_of_the_whole_state(one_chip, cell):
    """Compiled for a described v5e at the cell's real size through the
    step's own ``compile``: no ``copy`` whose result has the table's
    shape, both state arguments aliased to their results, and for FM's
    17 columns the layout the runtime gives the state anyway (nothing
    to re-lay), for FFM's 89 another one; ``adagrad`` is a gather and
    one two-operand scatter. A compile says nothing of times."""
    spec, B, L, need, U = CELLS[cell]
    assert L == _ladder_fit(spec.field_num or 39, FmConfig().bucket_ladder)
    assert U == _fit_slots(need, B, L, fixed_shape=False, uniq_bucket=0)
    rows, dim = spec.vocabulary_size + 1, spec.row_dim

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state = sd((rows, dim), jnp.float32)
    batch = (sd((B,), jnp.float32), sd((B,), jnp.float32),
             sd((U,), jnp.int32), sd((B, L), jnp.int32),
             sd((B, L), jnp.float32),
             sd((B, L), jnp.int32) if spec.model_type == "ffm" else None)
    step = TrainStep(spec)
    with _no_persistent_cache():
        text = step.compile(state, state, *batch).as_text()
    copies = re.findall(rf"= f32\[{rows},{dim}\]\S* copy\(", text)
    assert not copies, copies
    alias = re.search(r"input_output_alias=\{(.*?)\}, \w+=", text).group(1)
    assert "{0}: (0, {}" in alias and "{1}: (1, {}" in alias, alias
    _adagrad_walks_the_slots_twice(text, rows, dim)
    device = one_chip._device_assignment[0]
    default = Layout.from_pjrt_layout(device.client.get_default_layout(
        jnp.dtype(jnp.float32), (rows, dim), device))
    if spec.model_type == "fm":
        assert step._layout == default
    else:
        assert step._layout != default
        assert step._layout.major_to_minor == (0, 1)    # a row contiguous


# The cell whose lines are of unequal length (ISSUE 35): FM k=8, 2^26
# rows, and ONE job ships [8192, 96] and [8192, 112], so it holds two
# programs, the second compiled with the first's choice pinned.
BAGS = (ModelSpec("fm", 2, 8, 0, 1 << 26, "logistic", 0.0, 0.0, 0.01),
        8192, 28672)      # 25.3k distinct rows: the rung under 32,768


@pytest.mark.parametrize("widths", [(96, 112), (112, 96)])
def test_v5e_second_width_takes_the_firsts_layout(one_chip, widths):
    """Whichever width a job sees first, the compiler chooses the same
    layout for the state (so the pinned one is what it would choose at
    the other width too: the runtime's own), and neither program copies
    the whole state, breaks the aliasing of its two state arguments or
    walks the slots more than twice in ``adagrad``."""
    spec, B, U = BAGS
    ladder = FmConfig().bucket_ladder
    assert all(w in ladder for w in widths)
    rows, dim = spec.vocabulary_size + 1, spec.row_dim

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state = sd((rows, dim), jnp.float32)
    step = TrainStep(spec)
    with _no_persistent_cache():
        texts = [step.compile(
            state, state, sd((B,), jnp.float32), sd((B,), jnp.float32),
            sd((U,), jnp.int32), sd((B, L), jnp.int32),
            sd((B, L), jnp.float32), None).as_text() for L in widths]
    device = one_chip._device_assignment[0]
    assert step._layout == Layout.from_pjrt_layout(
        device.client.get_default_layout(jnp.dtype(jnp.float32),
                                         (rows, dim), device))
    assert step._relabel is None
    for text in texts:
        assert not re.findall(rf"= f32\[{rows},{dim}\]\S* copy\(", text)
        alias = re.search(r"input_output_alias=\{(.*?)\}, \w+=",
                          text).group(1)
        assert "{0}: (0, {}" in alias and "{1}: (1, {}" in alias, alias
        _adagrad_walks_the_slots_twice(text, rows, dim)


# ---- fm_batch_scores keeps the expanded rows whole (ISSUE 42) --------------

# (order, B, L, U, D) of the three one-chip FM cells: fm16-train-zipf,
# and the two widths of fm8-train-bags and fm3-train-bags.
WHOLE_ROWS = [(2, 8192, 40, 20480, 17), (2, 8192, 96, 28672, 9),
              (2, 8192, 112, 28672, 9), (3, 8192, 96, 28672, 9),
              (3, 8192, 112, 28672, 9)]


@pytest.mark.parametrize("order,B,L,U,D", WHOLE_ROWS)
def test_v5e_fm_grad_keeps_the_expanded_rows_whole(one_chip, order, B, L,
                                                   U, D):
    """The gradient of a logistic loss through ``fm_batch_scores``,
    compiled for a described v5e at the cells' sizes: nothing slices
    the ``[B, L, D]`` rows a 128-lane line holds 9 or 17 to, no array
    has the w column alone (``f32[B,L,1]``) or, at order 2, the k
    factor columns alone as the minor dimension of a ``[B, L, ...]``
    shape, and the rows are re-laid once forward and once backward.
    The function as it was until PR 42 (``w = rows[..., -1]``, ``v =
    rows[..., :-1]``) fails all five cases on each count: a slice of
    ``f32[B,L,D]`` to ``f32[B,L,1]``, a copy of it, a copy of
    ``f32[B,L,k]`` and the backward copy: 0.73 ms of 8.60 a step on the
    chip at ``[8192, 40, 17]``, 1.85 of 15.75 at ``[8192, 96, 9]``. A
    compile says nothing of times."""
    from fast_tffm_tpu.ops.interaction import fm_batch_scores

    def loss(params, local_idx, vals, labels):
        s = fm_batch_scores(params, local_idx, vals, order=order)
        return (jax.nn.softplus(s) - labels * s).sum()

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    with _no_persistent_cache():
        text = jax.jit(jax.grad(loss)).lower(
            sd((U, D), jnp.float32), sd((B, L), jnp.int32),
            sd((B, L), jnp.float32), sd((B,), jnp.float32)
        ).compile().as_text()
    rows = f"f32[{B},{L},{D}]"
    assert text.count(rows) > 4, "the expanded rows are in the program"
    shape_of = dict(re.findall(r"%(\S+) = (\w+\[[\d,]*\])", text))
    sliced = [ln.strip()[:160] for ln in text.splitlines()
              for m in [re.search(r" (?:dynamic-)?slice\(%([^,)\s]+)", ln)]
              if m and shape_of.get(m.group(1)) == rows]
    assert not sliced, sliced
    assert f"f32[{B},{L},1]" not in text
    if order == 2:
        assert f"f32[{B},{L},{D - 1}]" not in text
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(rf"= f32\[{B},{L},\d+\]\S* copy\(", ln)]
    backward = [ln for ln in copies if "transpose(jvp(" in ln]
    assert len(backward) <= 1 and len(copies) - len(backward) <= 1, \
        [ln[:160] for ln in copies]


# ---- ffm_batch_scores' hand-written VJP (ISSUE 55) --------------------------

# B, L, U, F, k of ffm4-train-zipf; the interaction pads F to 24, so its
# arrays are [8192, 24, 96 | 97] (and [8192, 24, 4, 24] for the swap).
FFM_CELL = (8192, 24, 10240, 22, 4)
_ASYNC = ("copy-start", "copy-done", "slice-start", "slice-done",
          "async-start", "async-done")


def _entry_ops(text):
    """(name, result, opcode, op path, line) of every operation of the
    ENTRY computation: the passes the chip runs one after another."""
    entry = text[text.index("ENTRY "):]
    ops = []
    for ln in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", ln)
        if m:
            path = re.search(r'op_name="([^"]*)"', ln)
            ops.append((m.group(1), m.group(2), m.group(3),
                        path.group(1) if path else "", ln))
    return ops


@pytest.fixture(scope="module")
def ffm_programs(one_chip):
    """The gradient of a logistic loss through ``ffm_batch_scores`` and
    the forward-only scorer, compiled for a described v5e at the
    cell's size (7 s each)."""
    from fast_tffm_tpu.ops.interaction import ffm_batch_scores
    B, L, U, F, k = FFM_CELL

    def scores(params, local_idx, fields, vals):
        return ffm_batch_scores(params, F, local_idx, fields, vals)

    def loss(params, local_idx, fields, vals, labels):
        s = scores(params, local_idx, fields, vals)
        return (jax.nn.softplus(s) - labels * s).sum()

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (sd((U, F * k + 1), jnp.float32), sd((B, L), jnp.int32),
            sd((B, L), jnp.int32), sd((B, L), jnp.float32))
    with _no_persistent_cache():
        grad = jax.jit(jax.grad(loss)).lower(
            *args, sd((B,), jnp.float32)).compile().as_text()
        forward = jax.jit(scores).lower(*args).compile().as_text()
    return grad, forward


def _slices_of_the_expanded_rows(text):
    """Every ``slice`` of what ``expand``'s gather wrote, as an
    operation of its own or inside a fusion that is handed the rows
    (S has the rows' shape at this cell, 24 fields on 24 cells, so the
    shape alone does not tell them apart)."""
    ops = _entry_ops(text)
    operands = {op[0]: re.findall(r"%([^,\s)]+)", op[4].split("(", 1)[1]
                                  .split("), ")[0]) for op in ops}
    rows = {op[0] for op in ops
            if re.search(r"jvp\(expand\)/gather|[(/]expand/gather", op[3])}
    assert rows, "expand's gather is in the program"
    for op in ops:       # the same bytes under another name
        if op[2] in ("bitcast", "copy", "reshape", "get-tuple-element") \
                + _ASYNC and operands[op[0]][:1] and \
                operands[op[0]][0] in rows:
            rows.add(op[0])
    found = []
    for name, _, opcode, _, line in ops:
        mine = [i for i, o in enumerate(operands[name]) if o in rows]
        if not mine or name in rows:
            continue
        if opcode in ("slice", "dynamic-slice"):
            found.append(line.strip()[:160])
        called = re.search(r"calls=%(\S+?)[,\s]", line)
        if opcode == "fusion" and called:
            body = text[text.index(f"%{called.group(1)} ("):]
            body = body[:body.index("\n}")]
            for i in mine:
                param = re.search(rf"%(\S+) = \S+ parameter\({i}\)", body)
                found += [ln.strip()[:160] for ln in body.splitlines()
                          if re.search(rf"slice\(%{re.escape(param.group(1))}"
                                       r"[,)]", ln)]
    return found


def _sums(result):
    """Whether a result is one of the interaction's per-field arrays,
    S, P or the swap's ``[B, F, k, F]``, and whether it is batch-minor
    (dimension 0 the minor-most: ``{0,2,1}``, ``{0,3,2,1}``, ...)."""
    m = re.match(r"f32\[8192,(2[24]),(?:(8[89]|9[67])|4,(2[24]))\]"
                 r"\{(\d)", result)
    return (bool(m), bool(m) and m.group(4) == "0")


def test_v5e_ffm_grad_swaps_the_fields_once_and_forward(ffm_programs):
    """What the hand-written VJP is for, read off the compiled gradient
    program (a compile says nothing of times; beside each assertion the
    chip reading it guards, PERF.md section 6, PR 55):

    - ONE trip through batch-minor, forward: one array of S's shape
      there, the swap's ``[B, F, k, F]`` beside it, one copy back (P),
      and NOTHING batch-minor under ``transpose(jvp(interaction))``
      (the parent's backward walked dS there and back in five passes,
      0.82 of ``interaction_ms`` + ``step_unscoped_ms`` 2.92);
    - no ``reshape`` pass of the sums (F padded to 24 makes the split
      ``[F, k*F, B]`` → ``[F, k, F, B]`` a bitcast: 0.20 ms each on the
      chip at F = 22) and no ``pad`` of them (the ones column is a
      fused operand of the backward's matmul, not a pass of 0.21 ms);
    - nothing slices the expanded rows (the linear term rides the
      diagonal's pass; read off S it was 0.16 ms);
    - every pass over the sums or the rows carries ``interaction`` or
      ``expand`` on its op path, so the trace's scopes place it
      (``step_unscoped_ms``; the compiler's own asynchronous copies
      between its two memories have no path and are left out: 0.017 ms
      on the chip, the rows' eviction)."""
    grad, _ = ffm_programs
    B, L, U, F, k = FFM_CELL
    ops = [op for op in _entry_ops(grad) if op[2] not in _ASYNC
           and op[2] not in ("bitcast", "get-tuple-element", "parameter")]
    sums = [op for op in ops if _sums(op[1])[0]]
    assert len(sums) >= 4, "the per-field sums are in the program"
    backward = [op for op in sums if "transpose(jvp(" in op[3]]
    assert not [op[4][:160] for op in backward if _sums(op[1])[1]]
    forward_minor = [op for op in sums if _sums(op[1])[1]
                     and "transpose(jvp(" not in op[3]]
    three_d = [op for op in forward_minor if op[1].count(",") == 4]
    assert len(three_d) <= 1 and len(forward_minor) <= 2, \
        [op[4][:160] for op in forward_minor]
    back = [op for op in sums if op[2] == "copy" and not _sums(op[1])[1]]
    assert len(back) <= 1, [op[4][:160] for op in back]
    assert not [op[4][:160] for op in sums
                if op[2] in ("reshape", "pad") or op[0].startswith("pad")]
    rows = (f"f32[{B},{L},", f"f32[{B * L},")
    assert not _slices_of_the_expanded_rows(grad)
    unplaced = [op[4][:200] for op in ops
                if (_sums(op[1])[0] or op[1].startswith(rows))
                and not re.search(r"interaction|expand", op[3])]
    assert not unplaced, unplaced


def test_v5e_ffm_scorer_makes_no_field_transpose(ffm_programs):
    """The forward-only scorer (validation, predict, serve) runs the
    primal alone: P, the VJP's residual, is dead code there. Its
    program takes S to batch-minor once, for the cross term, as it did
    before PR 55, and brings nothing back: no swap's ``[B, F, k, F]``
    array and no copy of the sums to row-major (0.24 of the train
    step's forward on the chip: the swap and the copy back)."""
    _, forward = ffm_programs
    sums = [op for op in _entry_ops(forward)
            if op[2] not in _ASYNC + ("bitcast",) and _sums(op[1])[0]]
    assert sums, "the per-field sums are in the program"
    minor = [op for op in sums if _sums(op[1])[1]]
    assert len(minor) <= 1, [op[4][:160] for op in minor]
    assert not [op[4][:160] for op in sums
                if op[2] == "copy" and not _sums(op[1])[1]]
