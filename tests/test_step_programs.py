"""A job whose batches ship at two widths (lines of unequal length, ISSUE
35): what the program says of it. ``TrainStep`` counts the programs it
makes ready and the steps whose program is not the last step's, and its
second program takes and returns the state in the first's layout; the
builders (C++ and Python) count the cells they cut at
``max_features_per_example``; the ``train/step`` span carries the width
its batch shipped at, into the stream and into a profiler's trace."""

import glob
import json

import numpy as np
import pytest

import jax

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data import cparser
from fast_tffm_tpu.data.pipeline import batch_iterator
from fast_tffm_tpu.models.fm import (ModelSpec, TrainStep, batch_args,
                                     init_accumulator, init_table)
from fast_tffm_tpu.obs.telemetry import RunTelemetry, activate
from fast_tffm_tpu.obs.trace import span

WIDTHS = ("narrow", "wide", "wide", "narrow", "narrow", "wide")


def _corpus(tmp_path, blocks=WIDTHS, seed=5, longest=9):
    """Blocks of 16 lines, narrow (1 to 4 features: the 4 rung) or wide
    (5 to ``longest - 1``: the 8 rung of the ladder below)."""
    rng = np.random.default_rng(seed)
    lines = []
    for kind in blocks:
        lo, hi = (1, 5) if kind == "narrow" else (5, longest)
        for _ in range(16):
            ids = rng.choice(300, size=int(rng.integers(lo, hi)),
                             replace=False)
            lines.append(" ".join(["1" if rng.random() < 0.4 else "0"]
                                  + [f"{j}:{rng.random():.4f}" for j in ids]))
    path = tmp_path / "two_widths.txt"
    path.write_text("\n".join(lines) + "\n")
    return str(path), lines


def _cfg(tmp_path, **kw):
    kw = dict(dict(bucket_ladder=(4, 8), max_features_per_example=8), **kw)
    return FmConfig(vocabulary_size=300, factor_num=4, batch_size=16,
                    shuffle=False, learning_rate=0.1,
                    model_file=str(tmp_path / "m" / "fm"), **kw)


@pytest.fixture
def tel(tmp_path):
    t = RunTelemetry(str(tmp_path / "metrics.jsonl"), meta={})
    with activate(t):
        yield t
    t.close()


def _counters(tel):
    return tel.registry.snapshot()["counters"]


def test_programs_and_switches_of_a_two_width_job(tmp_path, tel):
    """Six batches at widths 4 8 8 4 4 8: two programs made ready, three
    steps whose program is not the last step's, and the second program
    takes the state as the first left it (nothing re-laid, ever)."""
    cfg = _cfg(tmp_path)
    spec = ModelSpec.from_config(cfg)
    batches = list(batch_iterator(cfg, [_corpus(tmp_path)[0]],
                                  training=True, epochs=1))
    assert [b.vals.shape[1] for b in batches] == [4, 8, 8, 4, 4, 8]
    step = TrainStep(spec)
    table, acc = init_table(cfg, 3), init_accumulator(cfg)
    seen = []
    for b in batches:
        fed = table.format
        table, acc, loss, _ = step(table, acc, **batch_args(b))
        assert table.format == fed and acc.format == fed
        c = _counters(tel)
        seen.append((c.get("train/step_programs", 0),
                     c.get("train/program_switches", 0),
                     c.get("train/state_relayouts", 0)))
    assert seen == [(1, 0, 0), (2, 1, 0), (2, 1, 0), (2, 2, 0), (2, 2, 0),
                    (2, 3, 0)]
    assert np.isfinite(float(loss))
    # one width all along: one program, no switch
    again = TrainStep(spec)
    for b in (batches[0], batches[3], batches[4]):
        table, acc, _, _ = again(table, acc, **batch_args(b))
    c = _counters(tel)
    assert (c["train/step_programs"], c["train/program_switches"]) == (3, 3)


@pytest.mark.parametrize("builder", ["c++", "python"])
def test_cut_cells_are_counted_by_either_builder(tmp_path, tel, builder,
                                                 monkeypatch):
    """Lines of up to 12 features under a cap of 8: the stream's
    ``pipeline/truncated_cells`` is what the lines had past the cap,
    ``pipeline/feature_nnz`` what is left; an uncut corpus reads 0."""
    if builder == "python":
        def gone():
            raise RuntimeError("no C++ in this test")
        monkeypatch.setattr(cparser, "_load", gone)
    path, lines = _corpus(tmp_path, longest=13)
    sizes = np.array([len(l.split()) - 1 for l in lines])
    assert sizes.max() > 8
    cfg = _cfg(tmp_path)
    batches = list(batch_iterator(cfg, [path], training=True, epochs=1))
    assert sum(b.truncated for b in batches) == int(
        np.maximum(sizes - 8, 0).sum()) > 0
    c = _counters(tel)
    assert c["pipeline/truncated_cells"] == np.maximum(sizes - 8, 0).sum()
    assert c["pipeline/feature_nnz"] == np.minimum(sizes, 8).sum()
    assert c["pipeline/examples"] == len(lines)
    before = c["pipeline/truncated_cells"]
    sound = _cfg(tmp_path, max_features_per_example=16,
                 bucket_ladder=(4, 8, 16))
    list(batch_iterator(sound, [path], training=True, epochs=1))
    c = _counters(tel)
    assert c["pipeline/truncated_cells"] == before
    assert c["pipeline/feature_nnz"] == np.minimum(sizes, 8).sum() + sizes.sum()


def test_a_spilled_line_is_counted_once(tmp_path):
    """The fixed-U builder re-feeds the line that closed a batch: its
    cut cells count when it is committed, not when it is rolled back."""
    lines = ["1 " + " ".join(str(10 * i + j) for j in range(6))
             for i in range(8)]
    blob = ("\n".join(lines) + "\n").encode()
    bb = cparser.BatchBuilder(8, 4, 300, max_features_per_example=4,
                              max_uniq=10, num_threads=1)
    cut, n, off = 0, 0, 0
    while off < len(blob):
        full, used = bb.feed(blob, off)
        off += used
        if full or off >= len(blob):
            n += bb.finish()[0]
            cut += bb.truncated
    assert n == 8 and cut == 8 * 2


def test_the_step_span_says_its_width(tmp_path):
    """Fields of a span ride the profiler's annotation as stats and the
    stream's span event as keys; the name stays plain."""
    from jax.profiler import ProfileData
    t = RunTelemetry(str(tmp_path / "m.jsonl"), meta={}, trace_spans=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        with activate(t):
            for step, width in ((1, 96), (2, 112)):
                with span("train/step", seconds="train/dispatch_seconds",
                          step=step, width=width):
                    pass
    finally:
        jax.profiler.stop_trace()
        t.close()
    path = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    found = [dict(e.stats) for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events if e.name == "train/step"]
    assert [(s["step"], s["width"]) for s in found] == [(1, 96), (2, 112)]
    with open(tmp_path / "m.jsonl") as fh:
        spans = [json.loads(l) for l in fh if '"span"' in l]
    assert [(s["step"], s["width"]) for s in spans
            if s.get("name") == "train/step"] == [(1, 96), (2, 112)]


def test_scalars_are_fetched_without_a_stack_program(monkeypatch, tmp_path):
    """A barrier's drain of buffered scalars: no stack is made and
    nothing is compiled (a stack is a program per group size, and was a
    job's first program made ready inside its steady state); arrays of
    one shape are stacked into one transfer."""
    import jax.numpy as jnp
    from fast_tffm_tpu.obs.telemetry import RunTelemetry
    from fast_tffm_tpu.utils import fetch
    stacked = []
    real = jnp.stack
    monkeypatch.setattr(jnp, "stack",
                        lambda xs, *a, **k: (stacked.append(len(xs)),
                                             real(xs, *a, **k))[1])
    scalars = [(jnp.float32(i), i) for i in range(16)]
    rows = [(jnp.arange(4.0) + i, i) for i in range(3)]

    def fetched(pairs):
        got = []
        fetch.bulk_fetch(pairs, lambda v, m: got.append((float(np.sum(v)),
                                                         m)))
        return got
    tel = RunTelemetry(str(tmp_path / "m.jsonl"), meta={})
    try:
        assert fetched(scalars) == [(float(i), i) for i in range(16)]
        compiles = tel.registry.snapshot()["counters"][
            "compile/backend_compiles"]
    finally:
        tel.close()
    assert stacked == [] and compiles == 0
    assert fetched(rows) == [(6.0 + 4 * i, i) for i in range(3)]
    assert stacked == [3]
    assert fetched(scalars + rows)[-1] == (14.0, 2) and stacked == [3, 3]
