"""One device runs on slots fitted to the batch's distinct rows:
``dedup = auto`` resolves to the host unique on the quarter-octave
ladder for a train step (ISSUE 36) and for a sweep's scorer (ISSUE 45)
alike, the pipeline counts the slots it ships, and the benchmark's two
metric files read that count."""

import dataclasses
import functools
import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data import cparser
from fast_tffm_tpu.data import pipeline
from fast_tffm_tpu.data.pipeline import (UNIQ_LADDER_MIN, RowShards,
                                         _fit_slots, _ladder_fit,
                                         _uniq_ladder, batch_iterator)
from fast_tffm_tpu.models.fm import (ModelSpec, batch_args,
                                     init_accumulator, init_table,
                                     make_train_step, regime_line,
                                     ships_raw_batches, train_step_body)
from fast_tffm_tpu.obs.telemetry import RunTelemetry, activate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, L, VOCAB = 64, 16, 5000


def _zipf_corpus(tmp_path, ffm, n=3 * B, seed=7, field_num=4):
    """Click-log shaped: every example has L features whose ids repeat
    across the batch (Zipf a=1.35, as the benchmark's corpora)."""
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        ids = np.unique(rng.zipf(1.35, size=L) % VOCAB)
        toks = [(f"{int(rng.integers(0, field_num))}:" if ffm else "")
                + f"{i}:{rng.random():.4f}" for i in ids]
        lines.append(" ".join(["1" if rng.random() < 0.3 else "0"] + toks))
    p = tmp_path / "zipf.txt"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def _cfg(path, **kw):
    base = dict(vocabulary_size=VOCAB, factor_num=4, batch_size=B,
                train_files=(path,), shuffle=False,
                bucket_ladder=(4, 8, L), max_features_per_example=L,
                learning_rate=0.1, factor_lambda=1e-4, bias_lambda=1e-4)
    base.update(kw)
    return FmConfig(**base)


# ---- (a) the resolution: the host unique, whatever the use -----------

@pytest.mark.parametrize("devices,lookup,configured,use,want", [
    (1, "device", "auto", "train", "host"),  # one chip trains on fitted slots
    (1, "device", "auto", "score", "host"),  # and sweeps on them (ISSUE 45)
    (8, "device", "auto", "train", "host"),  # a mesh: as before, both uses
    (8, "device", "auto", "score", "host"),
    (1, "host", "auto", "train", "host"),    # offload: as before
    (1, "host", "auto", "score", "host"),
    (1, "device", "device", "train", "device"),  # explicit values keep
    (1, "device", "host", "score", "host"),      # their meaning
])
def test_auto_dedup_resolves_to_the_host_unique(monkeypatch, tmp_path,
                                                devices, lookup,
                                                configured, use, want):
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    path = _zipf_corpus(tmp_path, ffm=False, n=B)
    cfg = _cfg(path, lookup=lookup, dedup=configured)
    spec = ModelSpec.from_config(cfg)
    assert spec.dedup == want
    assert f"dedup={want} " in regime_line(spec, cfg)
    assert ships_raw_batches(spec) is (want == "device")
    # the rule has no use in it: the feed either use asks for is the same
    batch, = batch_iterator(cfg, (path,), training=use == "train",
                            epochs=1, raw_ids=ships_raw_batches(spec))
    if want == "device":
        assert batch.uniq_ids is None
    else:
        assert len(batch.uniq_ids) in _uniq_ladder(B, L)[:-1]


def test_only_serve_asks_for_raw_ids():
    """Raw ids are the caller's choice by its kind, not a rule's by the
    use: of the programs, serve alone overrides the spec's ``dedup`` or
    builds ``raw_ids=True`` by hand (its shapes are compiled ahead over
    B x widths and a request of a few lines has nothing to dedup).
    Every other feed follows ``ships_raw_batches(spec)``."""
    import ast
    roots = [os.path.join(REPO, "fast_tffm_tpu"), os.path.join(REPO, "tools")]
    files = [os.path.join(REPO, f) for f in (
        "chip_smoke.py", "run_tffm.py", "__graft_entry__.py")]
    for root in roots:
        for d, _, names in os.walk(root):
            files += [os.path.join(d, n) for n in names if n.endswith(".py")]
    specs, forced = 0, set()
    for path in files:
        with open(path) as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "from_config"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "ModelSpec"):
                specs += 1
                assert not node.keywords, f"{path}:{node.lineno}"
            for k in node.keywords:
                if (isinstance(k.value, ast.Constant)
                        and (k.arg, k.value.value) in (
                            ("dedup", "device"), ("raw_ids", True))):
                    forced.add(os.path.relpath(path, REPO))
    assert specs >= 11
    assert forced == {os.path.join("fast_tffm_tpu", "serve", "server.py")}


# ---- (b) the step's U, and the same three steps either way -------------

def _pad_is_small(need, U):
    """One device's rung over ``need`` slots: under a quarter of the
    need is padding from 256 slots up, under one smallest rung below."""
    return need <= U < need + max(UNIQ_LADDER_MIN, need / 4)


def _three_steps(cfg, spec):
    table, acc = init_table(cfg, 0), init_accumulator(cfg)
    step = make_train_step(spec)
    losses, batches = [], []
    for b in batch_iterator(cfg, cfg.train_files, training=True,
                            raw_ids=ships_raw_batches(spec)):
        table, acc, loss, _ = step(table, acc, **batch_args(b))
        losses.append(float(loss))
        batches.append(b)
    assert len(batches) == 3
    return np.asarray(table), np.asarray(acc), losses, batches


@pytest.mark.parametrize("model", ["fm", "ffm"])
def test_one_chip_auto_step_runs_on_the_rung_of_distinct_rows(
        monkeypatch, tmp_path, model):
    ffm = model == "ffm"
    cfg = _cfg(_zipf_corpus(tmp_path, ffm),
               **(dict(model_type="ffm", field_num=4) if ffm else {}))
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    auto = ModelSpec.from_config(cfg)
    assert auto.dedup == "host"
    explicit = ModelSpec.from_config(
        dataclasses.replace(cfg, dedup="device"))
    assert explicit.dedup == "device"
    t_a, acc_a, loss_a, fitted = _three_steps(cfg, auto)
    t_d, acc_d, loss_d, raw = _three_steps(cfg, explicit)
    ladder = _uniq_ladder(B, L)
    for f, r in zip(fitted, raw):
        assert r.uniq_ids is None and r.local_idx.shape == (B, L)
        distinct = len(np.unique(r.local_idx[r.local_idx != cfg.pad_id]))
        U = f.uniq_ids.shape[0]
        assert U == _ladder_fit(distinct + 1, ladder)   # + the pad slot
        assert U < B * L + 1 and _pad_is_small(distinct + 1, U)
        # the same rows, each once, pad row first (the builder's order)
        real = f.uniq_ids[f.uniq_ids != cfg.pad_id]
        assert len(real) == distinct == len(np.unique(real))
        np.testing.assert_array_equal(f.uniq_ids[f.local_idx], r.local_idx)
    np.testing.assert_allclose(loss_a, loss_d, rtol=1e-6)
    touched = np.unique(np.concatenate([r.local_idx.ravel() for r in raw]))
    np.testing.assert_allclose(t_a[touched], t_d[touched],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(acc_a[touched], acc_d[touched],
                               rtol=1e-6, atol=1e-7)
    untouched = np.setdiff1d(np.arange(cfg.num_rows), touched)
    np.testing.assert_array_equal(t_a[untouched], t_d[untouched])


# ---- (c) the counter ---------------------------------------------------

@pytest.mark.parametrize("row_shards", [1, 4])
@pytest.mark.parametrize("host_threads", [1, pytest.param(
    4, marks=pytest.mark.skipif(not cparser.available(),
                                reason="C++ parser extension unavailable"))])
def test_uniq_slots_counts_the_slots_shipped(tmp_path, host_threads,
                                             row_shards):
    from fast_tffm_tpu.obs.attribution import attribution
    cfg = _cfg(_zipf_corpus(tmp_path, ffm=False, n=5 * B),
               host_threads=host_threads)
    shards = RowShards.of(cfg, row_shards)
    tel = RunTelemetry(str(tmp_path / "m.jsonl"), meta={"kind": "t"})
    try:
        with activate(tel):
            batches = list(batch_iterator(cfg, cfg.train_files,
                                          training=True,
                                          row_shards=shards))
            snap = tel.registry.snapshot()["counters"]
            raw = list(batch_iterator(cfg, cfg.train_files, training=True,
                                      raw_ids=True))
            after_raw = tel.registry.snapshot()["counters"]
    finally:
        tel.close()
    assert len(batches) == len(raw) == 5
    slots = sum(len(b.uniq_ids) for b in batches)
    rows = sum(int((b.uniq_ids != cfg.pad_id).sum()) for b in batches)
    assert snap["pipeline/uniq_slots"] == slots
    assert snap["pipeline/uniq_rows"] == rows
    for b in batches:
        real = (b.uniq_ids != cfg.pad_id).reshape(row_shards, -1)
        U, fullest = real.size, int(real.sum(axis=1).max())
        if shards is None:
            # one device: a quarter-octave rung over rows + pad slot
            assert _pad_is_small(fullest + 1, U)
        else:
            # a mesh: the doubling rung over the fullest shard's need
            need = row_shards * (fullest + 1)
            assert U & (U - 1) == 0
            assert 0.5 < need / U <= 1.0 or U == UNIQ_LADDER_MIN
    att = attribution({"counters": snap, "gauges": {}, "hists": {}})
    assert att["uniq_slot_fill"] == pytest.approx(rows / slots)
    # raw ids ship no unique table: the sweep adds batches and no slots
    assert after_raw["pipeline/batches"] == 10
    assert after_raw["pipeline/uniq_slots"] == slots
    if host_threads > 1:
        assert snap["pipeline/worker_build_seconds"] > 0


# ---- (d) the benchmark's two metric files ------------------------------

def _stream(tmp_path, counters_by_step):
    p = tmp_path / "metrics.jsonl"
    p.write_text("".join(
        json.dumps({"event": "metrics", "step": s, "counters": c}) + "\n"
        for s, c in counters_by_step.items()))
    return str(p)


@pytest.mark.parametrize("name,with_counter,want", [
    ("uniq_slot_fill", True, 19200 / 32768),
    ("uniq_slot_fill", False, None),          # the parent: raw ids
    ("host_build_s_per_batch", True, 0.04),
    ("host_build_s_per_batch", False, None),  # a serial build
])
def test_new_metric_files_read_the_stream_or_nothing(tmp_path, name,
                                                     with_counter, want):
    from benchmarks.readers import telemetry_window
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           name + ".json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "telemetry_window"
    assert spec["layer"] == "host parse + build (data/)"
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        entry = next(m for m in json.load(fh)["per_layer"]
                     if m["name"] == name)
    # Held as tests/benchmarks/test_benchmark_json.py holds the lists:
    # the cells the entry was accepted with lead its list in their
    # order; a cell appended after them turns nothing red.
    accepted = ["fm16-train-zipf", "ffm4-train-zipf",
                "fm16x4-train-zipf"]            # PR 27 appended the last
    assert entry["workloads"][:len(accepted)] == accepted
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        k: spec[k] for k in ("name", "unit", "better", "source", "layer",
                             "moves")}
    first = {"pipeline/batches": 20, "train/examples": 16}
    last = {"pipeline/batches": 120, "train/examples": 816}
    if with_counter:
        first.update({"pipeline/uniq_rows": 19200 * 20,
                      "pipeline/uniq_slots": 32768 * 20,
                      "pipeline/worker_build_seconds": 1.0})
        last.update({"pipeline/uniq_rows": 19200 * 120,
                     "pipeline/uniq_slots": 32768 * 120,
                     "pipeline/worker_build_seconds": 5.0})
    ctx = {"telemetry_path": _stream(tmp_path, {16: first, 816: last}),
           "window_steps": (16, 816), "window_wall_s": 30.0}
    value = telemetry_window.read(ctx, **spec["args"])
    if want is None:
        assert value is None
    else:
        assert value == pytest.approx(want)


@pytest.mark.parametrize("with_counter,want", [
    (True, 19300 / 20480), (False, None)])   # ISSUE 45's change; its parent
def test_the_sweeps_fill_metric_reads_the_stream_or_nothing(
        tmp_path, with_counter, want):
    """``validation_uniq_slot_fill``: the validation plane's counts,
    which a sweep on raw ids (the parent's, serve's wire) never makes."""
    from benchmarks.readers import telemetry_window
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           "validation_uniq_slot_fill.json")) as fh:
        spec = json.load(fh)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        entry = next(m for m in json.load(fh)["per_layer"]
                     if m["name"] == "validation_uniq_slot_fill")
    assert entry == dict(
        {k: spec[k] for k in ("name", "unit", "better", "source", "layer",
                              "moves")}, workloads=["fm16-train-eval"])
    assert spec["reader"] == "telemetry_window"
    assert spec["layer"] == "host parse + build (data/)"
    first = {"validation_plane/batches": 54, "train/examples": 16}
    last = {"validation_plane/batches": 540, "train/examples": 816}
    if with_counter:
        first.update({"validation_plane/uniq_rows": 19300 * 54,
                      "validation_plane/uniq_slots": 20480 * 54})
        last.update({"validation_plane/uniq_rows": 19300 * 540,
                     "validation_plane/uniq_slots": 20480 * 540})
    ctx = {"telemetry_path": _stream(tmp_path, {16: first, 816: last}),
           "window_steps": (16, 816), "window_wall_s": 30.0}
    value = telemetry_window.read(ctx, **spec["args"])
    assert value is None if want is None else value == pytest.approx(want)


# ---- (e) a benchmark cell on one device, end to end --------------------
# What tests/benchmarks/test_benchmark_harness.py's
# test_a_cell_added_as_files_runs_without_editing_any checked beyond
# the raw-id wire it pins (8*8+8 B an example; CHANGES.md, PR 26), on
# the wire the one-chip step now takes.

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
    try:
        import tiny_tree
    finally:
        sys.path.pop(0)
    return tiny_tree, tiny_tree.make(str(tmp_path_factory.mktemp("tree")))


@pytest.mark.parametrize("workload,cell_bytes", [
    ("tiny-train", 4 + 4),              # a feature's slot index and value
    ("tiny-ffm-train", 4 + 4 + 4),      # and its field
])
def test_a_one_device_cell_ships_the_fitted_unique_and_checks_out(
        tiny_root, workload, cell_bytes):
    tiny_tree, root = tiny_root
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", workload,
         "--seed", str(2 ** 31 + 26), "--seconds", "1.5", "--trace", "1",
         "--rehearse-cpu"], cwd=root, env=tiny_tree.env(),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = p.stdout.strip().splitlines()
    last = json.loads(out[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}   # a rehearsal
    assert 0 < last["device"]["busy_s"] <= last["device"]["window_s"]
    shown = json.loads(next(l for l in out if l.startswith("metrics: "))
                       [len("metrics: "):])
    # B = 64 examples a step at the bucket ladder's lowest rung, L = 8:
    # the padded rectangles and label + weight, then 4 B for each of
    # the U slots of the unique table, U a rung under B*L + 1.
    rect = 8 * cell_bytes + 8
    slots = (shown["h2d_bytes_per_example"]["value"] - rect) * 64 / 4
    assert _uniq_ladder(64, 8)[0] <= slots <= 256 < 64 * 8 + 1
    assert 0.5 <= shown["uniq_slot_fill"]["value"] <= 1.0
    # ISSUE 34's ``cell_fill``: every example has the corpus's features
    # (2 numeric + 3 categorical; FFM 1 + 3) in the 8 columns it ships.
    assert shown["cell_fill"]["value"] == pytest.approx(
        {"tiny-train": 5, "tiny-ffm-train": 4}[workload] / 8)
    if cparser.available():
        assert shown["host_build_s_per_batch"]["value"] > 0
    assert "dedup_sort_ms" not in shown      # no such scope in the step
    assert any(l.startswith("check loss_rel_gap_max") for l in out)
    assert any(l.startswith("check span_examples_credited_not_counted: 0 ")
               for l in out)
    said = next(l for l in out if l.startswith("all work over all time"))
    n, cycles = map(int, re.search(r"of (\d+) readings, (\d+) cycles",
                                   said).groups())
    assert cycles >= 1 and n == cycles * 2   # 4 batches x 2 passes / 4


def test_benchmark_json_lists_every_metric_with_its_file_and_reader():
    """What tests/benchmarks/test_scope_metrics.py's
    test_benchmark_json_lists_the_nine_for_both_train_cells checked
    past its first line: PR 25's nine metrics are all still there, in
    their order, before the two PR 26 appended and PR 27's one. Held as
    tests/benchmarks/test_benchmark_json.py holds the lists: what was
    accepted leads in its order, so an entry or a cell appended after
    it (PR 33's ``shard_slot_fill``) turns nothing red, and a removal
    or an insertion does."""
    from benchmarks import harness
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    names = [m["name"] for m in spec["per_layer"]]
    nine = ["dedup_sort_ms", "table_gather_ms", "slot_expand_ms",
            "interaction_ms", "table_scatter_ms", "step_unscoped_ms",
            "loss_sync_share", "epoch_barrier_s", "compiles_per_epoch"]
    accepted = nine + ["uniq_slot_fill", "host_build_s_per_batch",
                       "collective_exposed_ms"]                # PR 27's
    assert names[7:7 + len(accepted)] == accepted
    assert len(names) == len(set(names))
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    assert [w["name"] for w in spec["workloads"]][:3] == [
        "fm16-train-zipf", "ffm4-train-zipf", "fm16x4-train-zipf"]
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} > {"setup_s"}
        assert {"uniq_slot_fill", "host_build_s_per_batch"} <= {
            m["name"] for m in cell.per_layer}
    for m in spec["per_layer"]:
        assert name.match(m["name"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                               m["name"] + ".json")) as fh:
            own = json.load(fh)
        for k in ("unit", "better", "source", "layer", "moves"):
            assert own[k] == m[k], (m["name"], k)
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "readers", own["reader"] + ".py"))


# ---- (e) ISSUE 36: the ladder, and the same step at either U -----------

SHAPES = [(4, 4), (64, 16), (8192, 24), (8192, 40), (8192, 112),
          (32768, 40)]


@pytest.mark.parametrize("rule", [
    "strictly rising", "starts at UNIQ_LADDER_MIN",
    "every rung a multiple of UNIQ_LADDER_MIN",
    "multiples of 128 from 512 up",
    "ends on the first power of two over B*L",
    "holds every doubling rung",
    "under a quarter of a need is padding from 256 slots up",
    "a mesh feed keeps the doubling rungs",
    "a fixed bucket is kept, or the top rung"])
@pytest.mark.parametrize("batch,width", SHAPES)
def test_uniq_ladder(batch, width, rule):
    lad = _uniq_ladder(batch, width)
    doubling = _uniq_ladder(batch, width, doubling=True)
    top = max(UNIQ_LADDER_MIN,                     # first 2^k > B*L
              1 << (batch * width).bit_length())
    one = functools.partial(_fit_slots, B=batch, L=width,
                            fixed_shape=False, uniq_bucket=0)
    needs = sorted({n for r in lad for n in (r - 1, r, r + 1)
                    if 1 <= n <= batch * width + 1})
    assert {
        "strictly rising": all(a < b for a, b in zip(lad, lad[1:])),
        "starts at UNIQ_LADDER_MIN": lad[0] == UNIQ_LADDER_MIN == 64,
        "every rung a multiple of UNIQ_LADDER_MIN": all(
            r % UNIQ_LADDER_MIN == 0 for r in lad),
        "multiples of 128 from 512 up": all(
            r % 128 == 0 for r in lad if r >= 512),
        "ends on the first power of two over B*L":
            lad[-1] == doubling[-1] == top,
        "holds every doubling rung": set(doubling) <= set(lad) and all(
            r & (r - 1) == 0 and b == 2 * r
            for r, b in zip(doubling, doubling[1:])),
        "under a quarter of a need is padding from 256 slots up": all(
            _pad_is_small(n, one(n)) and one(n) in lad for n in needs),
        "a mesh feed keeps the doubling rungs": all(
            one(n, mesh=True) == _ladder_fit(n, doubling)
            == max(UNIQ_LADDER_MIN, 1 << (n - 1).bit_length())
            for n in needs),
        "a fixed bucket is kept, or the top rung": all(
            _fit_slots(n, batch, width, True, bucket, mesh=mesh)
            == (bucket or top)
            for n in needs[:4] for bucket in (0, 256)
            for mesh in (False, True) if n <= (bucket or top)),
    }[rule]


@pytest.mark.parametrize("need,rung,doubling", [
    (19_261, 20_480, 32_768),      # fm16-train-zipf's mean batch
    (19_523, 20_480, 32_768),      # and its fullest (ISSUE 36's table)
    (8_961, 10_240, 16_384),       # ffm4-train-zipf
    (25_591, 28_672, 32_768),      # fm8-train-bags' fullest
    (4 * 12_401, 57_344, 65_536),  # fm16x4: four shards x the fullest's need
    (20_480, 20_480, 32_768), (20_481, 24_576, 32_768),
    (1, 64, 64), (65, 128, 128), (129, 192, 256), (257, 320, 512)])
def test_the_cells_ride_the_rungs_issue_36_sized(need, rung, doubling):
    fit = functools.partial(_fit_slots, need, 32_768, 40, False, 0)
    assert (fit(), fit(mesh=True)) == (rung, doubling)


@pytest.mark.parametrize("model", ["fm", "ffm"])
def test_the_step_at_the_fitted_rung_equals_the_step_at_the_power_of_two(
        tmp_path, model):
    """Pad slots are no-ops: the same batch through ``train_step_body``
    at its quarter-octave U and padded out to the next power of two
    gives the same loss and scores and, row for row, the same table
    and accumulator."""
    ffm = model == "ffm"
    cfg = _cfg(_zipf_corpus(tmp_path, ffm, n=40),
               **(dict(model_type="ffm", field_num=4) if ffm else {}))
    spec = ModelSpec.from_config(cfg)
    batch, = batch_iterator(cfg, cfg.train_files, training=True)
    U, wide = len(batch.uniq_ids), 256
    assert U == 192           # 150 to 170 rows: between the powers of two
    args = batch_args(batch)
    padded = dict(args, uniq_ids=np.concatenate(
        [batch.uniq_ids, np.full(wide - U, cfg.pad_id, np.int32)]))
    step = jax.jit(functools.partial(train_step_body, spec))
    table, acc = init_table(cfg, 3), init_accumulator(cfg)
    fitted = step(table, acc, **args)
    doubled = step(table, acc, **padded)
    for a, b in zip(fitted, doubled):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    moved = np.flatnonzero((np.asarray(fitted[0]) != np.asarray(table))
                           .any(axis=1))
    real = batch.uniq_ids[batch.uniq_ids != cfg.pad_id]
    assert set(moved) <= set(real) and len(moved) > 0.9 * len(real)


@pytest.mark.skipif(not cparser.available(),
                    reason="C++ parser extension unavailable")
@pytest.mark.parametrize("model", ["fm", "ffm"])
def test_the_python_and_the_cpp_builder_ship_the_same_slots(
        tmp_path, monkeypatch, model):
    ffm = model == "ffm"
    cfg = _cfg(_zipf_corpus(tmp_path, ffm, n=B + 40),
               **(dict(model_type="ffm", field_num=4) if ffm else {}))
    fast = list(batch_iterator(cfg, cfg.train_files, training=True))

    def no_builder(*a, **k):
        raise RuntimeError("forced generic path")
    monkeypatch.setattr(pipeline, "_make_builder", no_builder)
    generic = list(batch_iterator(cfg, cfg.train_files, training=True))
    # a full batch on a doubling rung, a short one between two
    assert [len(a.uniq_ids) for a in fast] == [256, 192]
    for a, b in zip(fast, generic):
        assert len(a.uniq_ids) == len(b.uniq_ids)
        np.testing.assert_array_equal(a.uniq_ids[a.local_idx],
                                      b.uniq_ids[b.local_idx])
