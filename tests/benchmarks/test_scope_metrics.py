"""The per-layer metrics that read the program's own tracing seam
(ISSUE 25): ``xplane_meta`` (what ProfileData hides of a trace),
``readers/scope_device_ms`` over the two recorded TPU traces, the
``jax.named_scope`` names on the lowered step and score programs, the
three counter metrics over a tiny run's telemetry stream, and
``readers/span_idle_share``. All on the CPU; the recorded traces are
a TPU v5 lite's."""

import dataclasses
import json
import os
import re

import numpy as np
import pytest

from benchmarks import trace_reduce, xplane_meta
from benchmarks.readers import (scope_device_ms, span_idle_share,
                                telemetry_window)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TESTDATA = os.path.join(REPO, "benchmarks", "testdata")
OLD = os.path.join(TESTDATA, "tiny_train_tpu.xplane.pb")
SCOPED = os.path.join(TESTDATA, "tiny_train_tpu_scoped.xplane.pb")
STEP = ["fm_train_step", "fm_packed_train_step", "fm_sharded_train_step"]
NEW_METRICS = ("dedup_sort_ms", "table_gather_ms", "slot_expand_ms",
               "interaction_ms", "table_scatter_ms", "step_unscoped_ms",
               "loss_sync_share", "epoch_barrier_s", "compiles_per_epoch")


def _metric_file(name):
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           name + ".json")) as fh:
        return json.load(fh)


def _ctx(path):
    return {"trace": trace_reduce.reduce(path), "xplane_path": path}


# ---- xplane_meta -------------------------------------------------------

def test_xplane_meta_finds_the_op_path_and_source_profiledata_hides():
    """PERF.md said the trace's operations carry no jax op path. They
    do, on the event metadata: 53 of the recorded step's 80 operations
    have ``tf_op`` and 50 ``source``; every one has XLA's own
    ``bytes_accessed``, ``flops`` and ``hlo_category``."""
    planes = xplane_meta.read(OLD)
    assert set(planes) == {"/device:TPU:0", "/host:CPU"}
    dev = planes["/device:TPU:0"]
    ops = {n: s for n, s in dev.items() if "hlo_category" in s}
    assert len(ops) == 80
    assert sum("tf_op" in s for s in ops.values()) == 53
    assert sum("source" in s for s in ops.values()) == 50
    assert all(isinstance(s["bytes_accessed"], int) and "flops" in s
               for s in ops.values())
    paths = {s["tf_op"] for s in ops.values() if "tf_op" in s}
    assert ("jit(fm_train_step)/jit(_unique_sorted_mask)/jit(lexsort)/"
            "sort:") in paths
    assert "jit(fm_train_step)/transpose(jvp())/scatter-add:" in paths
    scatter = next(s for n, s in ops.items()
                   if n.startswith("%fusion.8 = f32[4097,5]"))
    assert scatter["tf_op"] == "jit(fm_train_step)/scatter-add:"
    assert scatter["source"].endswith("fast_tffm_tpu/models/fm.py:230")
    assert scatter["bytes_accessed"] == 32832
    assert scatter["display_name"] == "fusion.8"


def test_xplane_meta_names_are_the_events_names():
    """The join the scope reader makes: an operation's event name, as
    ProfileData gives it, is its metadata's name."""
    dev = xplane_meta.read(OLD)["/device:TPU:0"]
    t = trace_reduce.reduce(OLD)
    names = {o.name for o in t.devices[0].ops}
    assert len(names) == 80 and names <= set(dev)


def test_xplane_meta_refuses_a_torn_file(tmp_path):
    with open(OLD, "rb") as fh:
        buf = fh.read()
    p = tmp_path / "torn.pb"
    p.write_bytes(buf[:len(buf) // 2])
    with pytest.raises(ValueError, match="torn"):
        xplane_meta.read(str(p))


def test_cut_keeps_the_device_plane_and_the_host_lines_with_spans(tmp_path):
    dst = str(tmp_path / "cut.pb")
    xplane_meta.cut(OLD, dst, span_prefixes=("shard_args",))
    assert os.path.getsize(dst) < os.path.getsize(OLD)
    assert (xplane_meta.read(dst)["/device:TPU:0"]
            == xplane_meta.read(OLD)["/device:TPU:0"])
    a, b = trace_reduce.reduce(OLD), trace_reduce.reduce(dst)
    assert b.busy_s == a.busy_s and b.window_s == a.window_s
    assert {ln for ln, _ in a.host} == {"main/11197", "python3"}
    assert {ln for ln, _ in b.host} == {"python3"}


# ---- scope_device_ms ---------------------------------------------------

@pytest.mark.parametrize("path,scope", [
    ("jit(fm_train_step)/adagrad/scatter-add:", "adagrad"),
    ("jit(fm_train_step)/jvp(expand)/gather:", "expand"),
    ("jit(fm_train_step)/transpose(jvp(expand))/scatter-add:", "expand"),
    ("jit(fm_train_step)/transpose(jvp(interaction))/pallas_call:",
     "interaction"),
    ("jit(fm_train_step)/dedup/jit(_unique_sorted_mask)/jit(lexsort)/sort:",
     "dedup"),
    ("jit(step)/jit(fm_sharded_train_step)/jvp(loss)/log1p:", "loss"),
    # innermost wins
    ("jit(f)/jvp(loss)/jvp(interaction)/mul:", "interaction"),
    # the last component is the primitive: gather is one, and no scope
    ("jit(fm_train_step)/gather:", None),
    ("jit(fm_train_step)/gather/gather:", "gather"),
    # a function's name is no scope
    ("jit(fm_train_step)/jit(gather)/add:", None),
    ("jit(fm_train_step)/transpose(jvp())/scatter-add:", None),
    ("table:", None), ("acc:", None), ("", None), (None, None),
])
def test_scope_of_an_op_path(path, scope):
    assert scope_device_ms.scope_of(path) == scope


def test_a_stale_trace_reads_none_and_says_why(capsys):
    """The trace from before the scopes (or an executable a compile
    cache kept from then): every scope metric is left out, said once."""
    ctx = _ctx(OLD)
    for name in NEW_METRICS[:6]:
        spec = _metric_file(name)
        assert spec["reader"] == "scope_device_ms"
        assert scope_device_ms.read(ctx, **spec["args"]) is None
    out = capsys.readouterr().out
    assert out.count("carries a scope") == 1
    assert "compile cache" in out


def test_no_step_in_the_trace_reads_none():
    assert scope_device_ms.read(_ctx(OLD), ["fm_score"], "gather") is None


def test_the_scopes_partition_the_step_on_the_scoped_trace(capsys):
    """benchmarks/testdata/tiny_train_tpu_scoped.xplane.pb: the tiny FM
    on a TPU v5 lite after ISSUE 25 (my chip run, PR 25; cut by
    ``xplane_meta.cut``). The six metrics plus ``loss`` are the step's
    device time within 1%, and ``scope: null`` is what is left."""
    ctx = _ctx(SCOPED)
    whole = ctx["trace"].program_device_ms(STEP)
    assert whole is not None and whole > 0
    got = {}
    for name in NEW_METRICS[:6]:
        spec = _metric_file(name)
        got[name] = scope_device_ms.read(ctx, **spec["args"])
        assert got[name] is not None and got[name] >= 0
    loss = scope_device_ms.read(ctx, STEP, "loss")
    assert sum(got.values()) + loss == pytest.approx(whole, rel=0.01)
    named = sum(v for k, v in got.items() if k != "step_unscoped_ms")
    assert got["step_unscoped_ms"] == pytest.approx(
        whole - named - loss, rel=0.05, abs=0.02 * whole)
    # the table's parts each take time on the chip
    for name in ("dedup_sort_ms", "table_gather_ms", "slot_expand_ms",
                 "table_scatter_ms"):
        assert got[name] > 0
    out = capsys.readouterr().out
    assert out.count("scopes together") == 1       # printed once a run
    assert re.search(r"scope adagrad: [\d.]+ ms a step, XLA "
                     r"bytes_accessed [\d.]+ GB, [\d.]+ GB/s", out)


def test_the_scoped_trace_carries_the_programs_spans_on_host_lines():
    """The same run's /host:CPU plane holds the host loop's phases as
    profiler annotations, so an idle gap is named after a phase."""
    t = trace_reduce.reduce(SCOPED)
    names = {e.name for _, e in t.host}
    assert {"train/input_wait", "train/encode", "train/h2d", "train/step",
            "train/loss_sync"} <= names
    assert "train/validation" not in names
    gaps = dict(t.idle_gaps())
    assert any(k.startswith(("train/", "obs/", "pipeline/")) for k in gaps)


# ---- the scopes on the lowered programs --------------------------------

B, L, V, U = 16, 8, 4096 - 1, 64
TRAIN = {"dedup", "gather", "expand", "interaction", "loss", "adagrad"}
SCORE = {"gather", "expand", "interaction"}


def _spec(model, **kw):
    from fast_tffm_tpu.models.fm import ModelSpec
    base = dict(model_type=model, order=2, factor_num=2,
                field_num=3 if model == "ffm" else 0, vocabulary_size=V,
                loss_type="logistic", factor_lambda=1e-4, bias_lambda=1e-4,
                learning_rate=0.1, kernel="xla", dedup="device")
    base.update(kw)
    return ModelSpec(**base)


def _lowered_paths(program, model, kernel="xla"):
    """Op paths (as ``tf_op`` gives them) of one lowered program."""
    import jax
    import jax.numpy as jnp
    from fast_tffm_tpu.models import fm
    spec = _spec(model, kernel=kernel)
    D = spec.row_dim
    table = jnp.zeros((V + 1, D), jnp.float32)
    acc = jnp.ones((V + 1, D), jnp.float32)
    labels = jnp.zeros(B, jnp.float32)
    weights = jnp.ones(B, jnp.float32)
    idx = jnp.zeros((B, L), jnp.int32)
    vals = jnp.ones((B, L), jnp.float32)
    fields = (jnp.zeros((B, L), jnp.int32),) if model == "ffm" else ()
    lengths = jnp.full(B, L, jnp.int32)
    flat_i = jnp.zeros(B * L, jnp.int32)
    flat_v = jnp.ones(B * L, jnp.float32)
    flat_f = (jnp.zeros(B * L, jnp.int32),) if model == "ffm" else ()
    uniq = jnp.arange(U, dtype=jnp.int32)
    if program.startswith("sharded"):
        from fast_tffm_tpu.parallel import sharded
        spec = dataclasses.replace(spec, dedup="host")
        mesh = sharded.make_mesh()
    if program == "padded_train":
        fn = fm.make_train_step(spec)
        args = (table, acc, labels, weights, None, idx, vals) + fields
    elif program == "packed_train":
        fn = fm.make_packed_train_step(spec)
        args = (L, table, acc, labels, weights, None, lengths, flat_i,
                flat_v) + flat_f
    elif program == "sharded_train":
        fn = sharded.make_sharded_train_step(spec, mesh)
        args = (table, acc, labels, weights, uniq, idx, vals) + fields
    elif program == "score":
        fn = fm.make_score_fn(spec)
        args = (table, None, idx, vals) + fields
    elif program == "packed_score":
        fn = fm.make_packed_score_fn(spec)
        args = (L, table, None, lengths, flat_i, flat_v) + flat_f
    else:
        fn = sharded.make_sharded_score_fn(spec, mesh)
        args = (table, uniq, idx, vals) + fields
    static = (0,) if program.startswith("packed") else ()
    txt = jax.jit(lambda *a: fn(*a), static_argnums=static).lower(
        *args).as_text(debug_info=True)
    # inside the called function the paths are relative:
    # "transpose(jvp(expand))/scatter-add"; file names match too and
    # hold no scope
    return set(re.findall(r'loc\("([^"]+)"', txt))


@pytest.mark.parametrize("program,model,kernel", [
    (p, m, "xla")
    for p in ("padded_train", "packed_train", "sharded_train", "score",
              "packed_score", "sharded_score")
    for m in ("fm", "ffm")] + [("padded_train", "fm", "pallas"),
                               ("sharded_train", "fm", "pallas")])
def test_lowered_programs_carry_the_scope_names(program, model, kernel):
    """Every step and score program is built from the scoped helpers,
    so its operations' op paths name the part they belong to — also
    inside ``jvp(...)`` and, backward, ``transpose(jvp(...))``."""
    paths = _lowered_paths(program, model, kernel)
    found = {scope_device_ms.scope_of(p + ":") for p in paths} - {None}
    want = set(TRAIN if program.endswith("train") else SCORE)
    if program.startswith("sharded"):
        want.discard("dedup")           # the mesh step dedups on the host
    assert found == want
    if program.endswith("train"):
        for scope in ("expand", "interaction", "loss"):
            assert any(re.search(rf"(^|/)jvp\({scope}\)/", p)
                       for p in paths), scope
            assert any(re.search(rf"(^|/)transpose\(jvp\({scope}\)\)/", p)
                       for p in paths), scope
        assert any(p.endswith("transpose(jvp(expand))/scatter-add")
                   for p in paths)
        assert any(p.endswith("adagrad/scatter-add") for p in paths)
    if kernel == "pallas":
        # forward and backward kernel; under shard_map the kernel's own
        # path is relative to the mapped body
        inner = "shard_map" if program.startswith("sharded") else \
            "pallas_call"
        for wrap in ("jvp(interaction)", "transpose(jvp(interaction))"):
            assert any(p.endswith(f"{wrap}/{inner}") for p in paths), wrap
        assert any(p.endswith("pallas_call") for p in paths)


# ---- the counter metrics, over a tiny run's own stream -----------------

@pytest.fixture(scope="module")
def tiny_stream(tmp_path_factory):
    from fast_tffm_tpu.train import train
    from tests.test_health_trace import _train_cfg
    cfg = _train_cfg(tmp_path_factory.mktemp("stream"),
                     np.random.default_rng(0), epoch_num=3, log_steps=2)
    train(cfg)
    return cfg.model_file + ".metrics.jsonl"


@pytest.mark.parametrize("name", NEW_METRICS[6:])
def test_counter_metrics_read_from_a_tiny_runs_stream(tiny_stream, name):
    """Epochs of 4 steps, a snapshot every 2: the window from step 2 to
    step 10 holds two epoch barriers."""
    spec = _metric_file(name)
    assert spec["reader"] == "telemetry_window"
    ctx = {"telemetry_path": tiny_stream, "window_steps": (2, 10),
           "window_wall_s": 1.0}
    value = telemetry_window.read(ctx, **spec["args"])
    assert value is not None and value >= 0
    assert telemetry_window.window_delta(ctx, "train/epochs") == 2
    if name == "epoch_barrier_s":
        assert 0 < value < 60
    if name == "loss_sync_share":
        assert value > 0        # four loss lines lie in the window


def test_counters_read_zero_not_absent_before_the_first_barrier(
        tiny_stream):
    """The benchmark's window opens before the run's first epoch
    barrier: what only a barrier feeds must already be in the first
    snapshot, at 0."""
    first = next(e for e in telemetry_window.read_telemetry(tiny_stream)
                 if e.get("event") == "metrics" and e["step"] == 2)
    for counter in ("train/epochs", "train/epoch_barrier_seconds",
                    "train/loss_sync_seconds", "compile/backend_compiles",
                    "compile/cache_misses"):
        assert counter in first["counters"], counter
    assert first["counters"]["train/epochs"] == 0


# ---- BENCHMARK.json and the predict files ------------------------------

def test_benchmark_json_lists_the_nine_among_one_layers_metrics():
    """PR 25's nine are in ``per_layer``, in their order (where in the
    list, and that none is gone, is test_benchmark_json.py's prefix
    rule), each moving the train rate on a layer the list already
    had, with the file it was given then."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    by = {m["name"]: m for m in spec["per_layer"]}
    names = [m["name"] for m in spec["per_layer"]]
    assert [n for n in names if n in NEW_METRICS] == list(NEW_METRICS)
    first = names.index(NEW_METRICS[0])
    layers = {m["layer"] for m in spec["per_layer"][:first]}
    for name in NEW_METRICS:
        assert by[name]["moves"] == "train_examples_per_s_per_chip"
        assert by[name]["layer"] in layers
        assert by[name]["workloads"][:2] == ["fm16-train-zipf",
                                             "ffm4-train-zipf"]
        reader = _metric_file(name)["reader"]
        assert reader == ("scope_device_ms" if name.endswith("_ms")
                          else "telemetry_window")


def test_span_idle_share_reads_each_phases_own_part_of_the_gaps():
    """Idle 1 to 3 and 4 to 4.5 of a window of 5. The first gap lies
    under one phase; the second is two short phases in a row (which
    ``idle_gaps`` gives to neither or to one)."""
    Op = trace_reduce.Op
    dev = trace_reduce.DeviceTrace(
        "/device:TPU:0", [Op("a", 0.0, 1.0, {}), Op("b", 3.0, 4.0, {}),
                          Op("c", 4.5, 5.0, {})], [])
    host = [("main", Op("predict/input_wait", 0.9, 3.1, {})),
            ("main", Op("predict/input_wait", 4.0, 4.1, {})),
            ("main", Op("predict/score_dispatch", 4.1, 4.6, {}))]
    ctx = {"trace": trace_reduce.Trace([dev], host, 0.0, 5.0)}
    for name, want in (("predict_idle_input_wait", 42.0),
                       ("predict_idle_score_dispatch", 8.0),
                       ("predict_idle_setup", 0.0),
                       ("predict_idle_drain", 0.0),
                       ("predict_idle_write_wait", 0.0)):
        spec = _metric_file(name)
        assert spec["reader"] == "span_idle_share"
        assert span_idle_share.read(ctx, **spec["args"]) == pytest.approx(
            want)
