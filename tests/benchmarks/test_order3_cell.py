"""FM of order 3 as the benchmark holds it (ISSUE 41): the family file
``benchmarks/references/fm_order3.py`` on its own, the program against
it through the path the cell drives (``TrainStep`` at order 3, two
widths, pad cells, 1/len values), the long scan (96 and 112 slots), the
wrong-family control, and the reader of the scan's scope. All on the
CPU at small sizes; the configuration's own limits decide."""

import dataclasses
import inspect
import itertools
import json
import os
import re
import statistics

import numpy as np
import pytest

import tiny_tree
from benchmarks import (control, control_family, harness, reference,
                        trace_reduce)
from benchmarks.readers import op_scope_device_ms, scope_device_ms
from benchmarks.references import fm_order2, fm_order3

REPO = tiny_tree.REPO
CELL = "fm3-train-bags"
K = 8
MODEL = {"model_type": "fm", "order": 3, "factor_num": K, "field_num": 0,
         "row_dim": K + 1, "loss_type": "logistic", "factor_lambda": 1e-6,
         "bias_lambda": 1e-6, "learning_rate": 0.05, "adagrad_init": 0.1,
         "reference_family": "fm_order3"}


def _config():
    return harness.load_cell(CELL).config


def _lines(rng, B, L, rows_n, ids=4, longest=None):
    """Rows and values ``[B, L]`` as the bags corpus has them: ``ids``
    one-hot cells, then a bag of 1 to ``longest`` words at 1/len (a
    word may be drawn twice), then pad cells: row 0, value 0."""
    longest = longest or L - ids
    rows = np.zeros((B, L), np.int64)
    x = np.zeros((B, L))
    for b in range(B):
        n = int(rng.integers(1, longest + 1))
        if b == 0:
            n = longest                       # one line fills the width
        rows[b, :ids + n] = rng.integers(1, rows_n, ids + n)
        x[b, :ids] = 1.0
        x[b, ids:ids + n] = round(1000 / n) / 1000
    return rows, x


def _table(rng, rows_n, value_range):
    t = rng.uniform(-value_range, value_range, (rows_n, K + 1))
    t[-1] = 0.0                               # the program's dead row
    return t


# ---- (a) the family file on its own -----------------------------------

def _score(P, inv, x, quant=None):
    return fm_order3.scores_and_row_grads(MODEL, P, inv, x, None, quant)


@pytest.mark.parametrize("seed,L,scale", [(0, 6, 0.3), (1, 24, 0.3),
                                          (2, 112, 0.05), (3, 9, 1.0)])
def test_backward_agrees_with_central_finite_differences(seed, L, scale):
    rng = np.random.default_rng(seed)
    U = 11
    P = rng.normal(size=(U, K + 1)) * scale
    inv = rng.integers(0, U, (5, L))
    x = rng.normal(size=(5, L))
    x[:, L - 2:] = 0.0                        # pad cells
    ds = rng.normal(size=5)
    score, backward = _score(P, inv, x)
    got = backward(ds)
    want = np.empty_like(P)
    h = 1e-6
    for u, c in itertools.product(range(U), range(K + 1)):
        up, dn = P.copy(), P.copy()
        up[u, c] += h
        dn[u, c] -= h
        want[u, c] = ((_score(up, inv, x)[0] - _score(dn, inv, x)[0])
                      * ds).sum() / (2 * h)
    # float64 central differences at h = 1e-6: relative 1e-7 and better
    assert np.abs(got - want).max() <= 1e-6 * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_score_is_the_brute_force_sum_over_pairs_and_triples(seed):
    """score = sum_l w x + sum_f (sum_{l1<l2} z z + sum_{l1<l2<l3} z z
    z), term by term in Python: no identity, no recurrence."""
    rng = np.random.default_rng(seed)
    U, B, L = 7, 3, 7
    P = rng.normal(size=(U, K + 1)) * 0.4
    inv = rng.integers(0, U, (B, L))
    x = rng.normal(size=(B, L))
    x[:, -1] = 0.0
    want = np.zeros(B)
    for b in range(B):
        z = P[inv[b], :-1] * x[b, :, None]
        want[b] = (P[inv[b], -1] * x[b]).sum()
        for l1, l2 in itertools.combinations(range(L), 2):
            want[b] += (z[l1] * z[l2]).sum()
        for l1, l2, l3 in itertools.combinations(range(L), 3):
            want[b] += (z[l1] * z[l2] * z[l3]).sum()
    np.testing.assert_allclose(_score(P, inv, x)[0], want, rtol=1e-12,
                               atol=1e-14)


def test_a_word_drawn_twice_is_two_cells():
    """A line (a, b, b): the repeated row is two cells of the ANOVA
    kernel, so the pair b-b and the triple a-b-b count; a line (a, b)
    with b's value doubled is another score."""
    rng = np.random.default_rng(5)
    P = rng.normal(size=(2, K + 1)) * 0.5
    (va, wa), (vb, wb) = ((P[i, :-1], P[i, -1]) for i in (0, 1))
    score, backward = _score(P, np.array([[0, 1, 1]]), np.ones((1, 3)))
    want = wa + 2 * wb + (2 * va * vb + vb * vb + va * vb * vb).sum()
    assert score[0] == pytest.approx(want, rel=1e-12)
    merged, _ = _score(P, np.array([[0, 1]]), np.array([[1.0, 2.0]]))
    assert abs(merged[0] - score[0]) > 1e-3
    # and the repeated row's gradient is the sum of its two cells'
    g = backward(np.ones(1))
    assert g[1, -1] == pytest.approx(2.0)
    np.testing.assert_allclose(g[1, :-1], 2 * (va + vb + va * vb),
                               rtol=1e-12)


def test_bf16_moves_the_score_and_the_gradient():
    rng = np.random.default_rng(6)
    rows, x = _lines(rng, 16, 24, 200)
    P = _table(rng, 200, 0.3)
    s, back = _score(P, rows, x)
    sq, backq = _score(P, rows, x, "bf16")
    assert 1e-4 < np.abs(sq - s).max() / np.abs(s).max() < 0.1
    ds = rng.normal(size=16)
    gap = reference.leaf_norm_gaps(backq(ds), back(ds))["worst"]
    assert 1e-4 < gap < 0.1


def test_the_family_is_order_3s_alone_and_shares_no_code():
    with pytest.raises(ValueError, match="order 3"):
        fm_order3.scores_and_row_grads(dict(MODEL, order=2), np.zeros((2, 9)),
                                       np.zeros((1, 2), int),
                                       np.ones((1, 2)), None)
    assert fm_order3.row_dim(MODEL) == K + 1
    assert reference.family_of(MODEL) is fm_order3
    src = inspect.getsource(fm_order3)
    imports = [l for l in src.splitlines()
               if l.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import numpy as np",
                       "from benchmarks.reference import quantize, "
                       "scatter_rows"]


# ---- (b) the program against it, through the path the cell drives ------

V = 4096                # vocabulary_size: row V is the dead row


def _spec(order):
    from fast_tffm_tpu.models.fm import ModelSpec
    return ModelSpec(model_type="fm", order=order, factor_num=K, field_num=0,
                     vocabulary_size=V, loss_type="logistic",
                     factor_lambda=MODEL["factor_lambda"],
                     bias_lambda=MODEL["bias_lambda"],
                     learning_rate=MODEL["learning_rate"], kernel="xla",
                     dedup="host")


def _feed(rows, x, y, U):
    """The host-unique feed of one batch: ``uniq_ids`` padded to U
    slots with the pad id, pad cells pointing at the last slot."""
    live = x != 0
    uniq = np.unique(rows[live])
    ids = np.full(U, V, np.int32)
    ids[:len(uniq)] = uniq
    idx = np.where(live, np.searchsorted(uniq, rows), U - 1)
    return dict(labels=y.astype(np.float32),
                weights=np.ones(len(y), np.float32), uniq_ids=ids,
                local_idx=idx.astype(np.int32), vals=x.astype(np.float32))


def _three_steps(order, value_range, seed=0, widths=(16, 24, 16), ids=4):
    """Three steps of the one-device ``TrainStep`` at two widths
    against ``ReferenceTrainer`` on the order-3 family: the numbers
    ``check.train_checks`` compares."""
    import jax.numpy as jnp
    from fast_tffm_tpu.models.fm import TrainStep
    rng = np.random.default_rng(seed)
    B = 64
    t0 = _table(rng, V + 1, value_range).astype(np.float32)
    batches = []
    for L in widths:
        rows, x = _lines(rng, B, L, V, ids)
        batches.append((rows, x, (rng.random(B) < 0.3).astype(np.float64)))
    step = TrainStep(_spec(order))
    table = jnp.asarray(t0)
    acc = jnp.full(t0.shape, MODEL["adagrad_init"], jnp.float32)
    losses, after_first = [], None
    for rows, x, y in batches:
        table, acc, loss, _ = step(table, acc, **_feed(rows, x, y, 8192))
        losses.append(float(loss))
        if after_first is None:
            after_first = np.asarray(table), np.asarray(acc)
    assert len(step._programs) == 2           # a program a width
    touched = np.unique(np.concatenate(
        [r[x != 0] for r, x, _ in batches] + [np.array([V])]))
    ref = reference.ReferenceTrainer(MODEL, touched, t0[touched])
    ref_losses, g1, rows1 = [], None, None
    for i, (rows, x, y) in enumerate(batches):
        r = np.where(x != 0, rows, V)         # pad cells: any row, value 0
        ref_losses.append(ref.step(r, x, y, np.ones(len(y)), None))
        if i == 0:
            g1, rows1 = ref.last_grad, touched[ref.last_touched]
    t1, a1 = after_first
    g_prog = ((t0[rows1].astype(np.float64) - t1[rows1])
              * np.sqrt(a1[rows1].astype(np.float64))
              / MODEL["learning_rate"])
    d_prog = np.asarray(table)[touched].astype(np.float64) - t0[touched]
    return {
        "loss_rel_gap_max": max(abs(p - r) / abs(r)
                                for p, r in zip(losses, ref_losses)),
        "grad_norm_gap_worst_leaf":
            reference.leaf_norm_gaps(g_prog, g1)["worst"],
        "update_norm_gap_worst_leaf":
            reference.leaf_norm_gaps(d_prog, ref.table - ref.table0)["worst"]}


@pytest.mark.parametrize("seed,widths,ids", [
    (0, (16, 24, 16), 4), (1, (16, 24, 16), 4), (2, (96, 112, 96), 12)])
def test_train_step_at_order_3_is_the_reference_on_two_widths(seed, widths,
                                                              ids):
    """Loss, first gradient and three steps' update, by worst leaf,
    under the configuration's own limits with room: a CPU's float32
    reads 1e-6 and less where the chip's limits are 1e-4 and 5e-5
    (what the TPU's float32 transcendentals and summation order need);
    a tenth of each limit here, so that the limits hold this path too."""
    conf = _config()
    limits = conf["check_limits"]["train"]
    got = _three_steps(3, conf["program"]["Train"]["init_value_range"], seed,
                       widths, ids)
    for name, limit in limits.items():
        assert got[name] <= 0.1 * limit, (name, got[name], limit)


def test_the_reference_in_bfloat16_fails_those_limits():
    """The same three batches' mathematics with rows, values and
    intermediate products rounded to bfloat16 is NOT inside the limits:
    they are tight enough that a lower precision fails one."""
    conf = _config()
    limits = conf["check_limits"]["train"]
    rng = np.random.default_rng(0)
    t0 = _table(rng, V + 1, conf["program"]["Train"]["init_value_range"])
    rows_all = np.arange(V + 1)
    runs = {}
    for quant in (None, "bf16"):
        rng_b = np.random.default_rng(1)
        tr = reference.ReferenceTrainer(MODEL, rows_all, t0, quant)
        for i, L in enumerate((16, 24, 16)):
            rows, x = _lines(rng_b, 64, L, V)
            tr.step(np.where(x != 0, rows, V), x,
                    (rng_b.random(64) < 0.3) * 1.0, np.ones(64), None)
            if i == 0:
                g = np.zeros_like(tr.table)
                g[tr.last_touched] = tr.last_grad
        runs[quant] = g, tr.table - tr.table0
    grad = reference.leaf_norm_gaps(runs["bf16"][0], runs[None][0])["worst"]
    upd = reference.leaf_norm_gaps(runs["bf16"][1], runs[None][1])["worst"]
    assert (grad > limits["grad_norm_gap_worst_leaf"]
            or upd > limits["update_norm_gap_worst_leaf"]), (grad, upd)


# ---- (c) the long loop -------------------------------------------------

@pytest.mark.parametrize("L", [96, 112])
def test_a_scan_of_the_cells_widths_equals_the_reference(L):
    """``fm_batch_scores`` at order 3 over 96 and 112 slots (the
    widths the cell ships), score and row gradient, against the power
    sums: float32 against float64, a sum of up to 112 terms a factor:
    2e-5 of the largest, where a wrong trip count or a dropped slot
    moves them by percents."""
    import jax
    import jax.numpy as jnp
    from fast_tffm_tpu.ops.interaction import fm_batch_scores
    rng = np.random.default_rng(L)
    B, U = 8, 300
    rows, x = _lines(rng, B, L, U, ids=12)
    P = _table(rng, U, 0.3)
    ds = rng.normal(size=B)
    score, backward = _score(P, rows, x)
    want_g = backward(ds)

    def f(p):
        s = fm_batch_scores(p, jnp.asarray(rows, jnp.int32),
                            jnp.asarray(x, jnp.float32), order=3)
        return (s * jnp.asarray(ds, jnp.float32)).sum(), s

    (_, got), got_g = jax.value_and_grad(f, has_aux=True)(
        jnp.asarray(P, jnp.float32))
    assert np.abs(np.asarray(got) - score).max() <= 2e-5 * np.abs(score).max()
    assert np.abs(np.asarray(got_g) - want_g).max() \
        <= 2e-5 * np.abs(want_g).max()
    # and the long lines matter: the degree-3 term is no rounding there
    two, _ = fm_order2.scores_and_row_grads(MODEL, P, rows, x, None)
    assert np.abs(two - score).max() > 1e-3 * np.abs(score).max()


# ---- (d) the wrong family ----------------------------------------------

def test_an_order_2_program_fails_the_order_3_check():
    """At the configuration's ``init_value_range`` a program that
    computes second-order FM is NOT inside the order-3 cell's limits
    (benchmarks/README.md: at 0.01 on lines of 11 cells it would be;
    on lines of 13 to 112 cells at the cell's two widths it is not, by
    ten times; PERF.md has the control's readings at the cell's size)."""
    conf = _config()
    limits = conf["check_limits"]["train"]
    got = _three_steps(2, conf["program"]["Train"]["init_value_range"],
                       widths=(96, 112, 96), ids=12)
    assert got["grad_norm_gap_worst_leaf"] \
        > 10 * limits["grad_norm_gap_worst_leaf"]
    assert got["update_norm_gap_worst_leaf"] \
        > 10 * limits["update_norm_gap_worst_leaf"]


def test_the_wrong_family_control_fails_and_rebinds_for_the_call_only(
        tmp_path, capsys, monkeypatch):
    """``control_family``: control.py's run "in bfloat16" is the other
    family's in float64 for the call. On tiny_tree's order-3 cell the
    second-order family fails the limits; the order-3 family against
    itself reads 0."""
    root = tiny_tree.make(str(tmp_path / "tree"))
    cell = harness.load_cell("tiny-fm3-train", root)
    # the tree's own family file is importable from its root only; the
    # repo's is the same mathematics
    cell.config["reference_family"] = "fm_order3"
    kept = reference.ReferenceTrainer
    with control_family.family_in_place_of_bf16("fm_order2"):
        wrong = control.control_numbers(cell, 3, str(tmp_path / "a"))
    with control_family.family_in_place_of_bf16("fm_order3"):
        same = control.control_numbers(cell, 3, str(tmp_path / "b"))
    assert reference.ReferenceTrainer is kept
    limits = cell.config["check_limits"]["train"]
    assert wrong["grad_norm_gap_worst_leaf"] \
        > 10 * limits["grad_norm_gap_worst_leaf"]
    assert same["grad_norm_gap_worst_leaf"] == 0.0
    assert same["update_norm_gap_worst_leaf"] == 0.0
    # the command line
    monkeypatch.setattr(harness, "load_cell", lambda name, root=root: cell)
    assert control_family.main(["--workload", "tiny-fm3-train", "--family",
                                "fm_order2", "--seeds", "4"]) == 0
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["correct"] is False and said["fails"]
    assert said["control"]["grad_norm_gap_worst_leaf"] \
        > 10 * limits["grad_norm_gap_worst_leaf"]


def test_the_configuration_is_the_bags_deployment_but_for_the_order():
    mine = _config()
    bags = harness.load_cell("fm8-train-bags").config
    general = dict(mine["program"]["General"])
    assert general.pop("order") == 3
    assert general == bags["program"]["General"]
    assert mine["program"]["Train"] == bags["program"]["Train"]
    assert mine["features"] == bags["features"]
    assert mine["reduced"] == ["corpus_lines"]
    assert mine["reference_family"] == "fm_order3"
    assert mine["check_limits"]["train"] == mine["check_limits"]["train_bags"]
    assert set(bags["assumed"]) < set(mine["assumed"])
    assert {"order", "one factor matrix", "init_value_range"} <= set(
        mine["assumed"])
    assert set(mine["check_limits"]["train"]) <= set(
        mine["check_limits_why"])


# ---- (e) the reader ------------------------------------------------------

@pytest.mark.parametrize("path,counts", [
    ("jit(fm_train_step)/jvp(interaction)/anova_scan/while/body/add:", True),
    ("jit(fm_train_step)/transpose(jvp(interaction))/anova_scan/while:",
     True),
    ("jit(fm_train_step)/transpose(jvp(anova_scan))/while/body/mul:", True),
    ("jit(fm_train_step)/jvp(anova_scan)/scan:", True),
    ("jit(fm_train_step)/jvp(interaction)/mul:", False),
    ("jit(fm_train_step)/transpose(jvp(interaction))/bl,bl->b/dot_general:",
     False),
    # the last component is the primitive; a function's name is no scope
    ("jit(fm_train_step)/interaction/anova_scan:", False),
    ("jit(fm_train_step)/jit(anova_scan)/add:", False),
    ("jit(fm_train_step)/anova_scan_later/add:", False),
    ("", False), (None, False),
])
def test_an_op_path_carries_the_component_or_not(path, counts):
    assert op_scope_device_ms.has_component(path, "anova_scan") is counts


def _synthetic(with_scan=True, widths=(96, 112, 96)):
    """Three executions of fm_train_step, each: an expand operation, an
    interaction operation outside the scan, the forward ``while`` with
    two body operations nested in it, the backward ``while``; the
    ``train/step`` span of each says its width."""
    Op = trace_reduce.Op
    scan = "anova_scan/" if with_scan else ""
    meta = {"/device:TPU:0": {
        "expand": {"tf_op": "jit(fm_train_step)/jvp(expand)/gather:"},
        "inter": {"tf_op": "jit(fm_train_step)/jvp(interaction)/mul:"},
        "fwd": {"tf_op": f"jit(fm_train_step)/jvp(interaction)/{scan}while:"},
        "body": {"tf_op": f"jit(fm_train_step)/jvp(interaction)/{scan}"
                          "while/body/add:"},
        "bwd": {"tf_op": "jit(fm_train_step)/transpose(jvp(interaction))/"
                         f"{scan}while:"},
        "copy": {}}}
    ops, mods, host = [], [], []
    for i, w in enumerate(widths):
        t = 0.020 * i
        unit = 1e-3 * w / 96
        mods.append(Op(f"jit_fm_train_step({i})", t, t + 0.012, {}))
        host.append(("main", Op("train/step", t - 0.001, t, {"width": w})))
        ops += [Op("expand", t, t + 0.004, {}),
                Op("inter", t + 0.004, t + 0.005, {}),
                Op("fwd", t + 0.005, t + 0.005 + unit, {}),
                Op("body", t + 0.005, t + 0.005 + unit / 4, {}),
                Op("body", t + 0.005 + unit / 2, t + 0.005 + unit, {}),
                Op("copy", t + 0.007, t + 0.008, {}),
                Op("bwd", t + 0.008, t + 0.008 + 2 * unit, {})]
    trace = trace_reduce.Trace(
        [trace_reduce.DeviceTrace("/device:TPU:0", ops, mods)], host, 0.0,
        0.020 * len(widths))
    return trace, meta


def _reader_ctx(trace, meta, monkeypatch):
    monkeypatch.setattr(op_scope_device_ms.xplane_meta, "read",
                        lambda path: meta)
    cell = harness.load_cell(CELL)
    run = harness.Run(cell=cell, seed=1, seconds=1.0, trace=True,
                      rehearse=False, t0=0.0)
    return {"trace": trace, "xplane_path": "synthetic", "run": run,
            "chips": 1, "device_kind": "TPU v5 lite"}


def _metric_args(name):
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           name + ".json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "op_scope_device_ms"
    return spec["args"]


def test_the_reader_counts_the_scan_forward_and_backward(monkeypatch,
                                                         capsys):
    """The two ``while`` operations and what is nested in them: 1 + 2
    ms at 96 wide (the median execution), each interval once; the
    interaction's operation outside the scan does not count."""
    trace, meta = _synthetic()
    ctx = _reader_ctx(trace, meta, monkeypatch)
    ms = op_scope_device_ms.read(ctx, **_metric_args("anova_scan_ms"))
    assert ms == pytest.approx(3.0)
    out = capsys.readouterr().out
    assert "anova_scan at width 96: 2 executions, 3.000 ms" in out
    assert "anova_scan at width 112: 1 executions, 3.500 ms" in out
    # the share of the roofline, by execution's own width: 92.2 us of
    # bytes over 3 ms at 96, 107.5 us over 3.5 ms at 112: the same share
    pct = op_scope_device_ms.read(ctx,
                                  **_metric_args("anova_scan_roofline"))
    least = op_scope_device_ms.scan_least_seconds(8192, 96, 8, 3,
                                                  "TPU v5 lite")
    assert least == pytest.approx(3 * 8192 * 96 * 8 * 4 / 819e9)
    assert least == pytest.approx(92.2e-6, rel=1e-3)
    assert pct == pytest.approx(100 * least / 3e-3)
    assert 0 < pct < 100
    assert capsys.readouterr().out == ""      # worked out once a run


def test_interaction_alone_reads_none(monkeypatch):
    """An order-2 step, or the parent's order-3 program from before
    the scope: no operation carries ``anova_scan``, both metrics are
    left out of the line, nothing raises."""
    trace, meta = _synthetic(with_scan=False)
    ctx = _reader_ctx(trace, meta, monkeypatch)
    for name in ("anova_scan_ms", "anova_scan_roofline"):
        assert op_scope_device_ms.read(ctx, **_metric_args(name)) is None


def test_a_recorded_order_2_trace_reads_none():
    """benchmarks/testdata's TPU trace of a second-order step (PR 25):
    real op paths, none with the component."""
    path = os.path.join(REPO, "benchmarks", "testdata",
                        "tiny_train_tpu_scoped.xplane.pb")
    ctx = {"trace": trace_reduce.reduce(path), "xplane_path": path}
    for name in ("anova_scan_ms", "anova_scan_roofline"):
        assert op_scope_device_ms.read(ctx, **_metric_args(name)) is None
    runs = op_scope_device_ms.per_execution_ms(
        ctx["trace"], op_scope_device_ms.xplane_meta.read(path),
        _metric_args("anova_scan_ms")["programs"], "interaction")
    # the same walk, asked for a scope, is scope_device_ms's number
    assert statistics.median(runs) == pytest.approx(scope_device_ms.read(
        ctx, _metric_args("anova_scan_ms")["programs"], "interaction"))
    assert statistics.median(runs) > 0


def test_spans_without_a_width_leave_the_roofline_out(monkeypatch):
    trace, meta = _synthetic()
    for _, e in trace.host:
        e.stats.clear()
    ctx = _reader_ctx(trace, meta, monkeypatch)
    assert op_scope_device_ms.read(
        ctx, **_metric_args("anova_scan_ms")) == pytest.approx(3.0)
    assert op_scope_device_ms.read(
        ctx, **_metric_args("anova_scan_roofline")) is None


def test_the_scopes_of_the_lowered_step_by_order():
    """``anova_scan`` rides the order-3 step's op paths inside
    ``interaction``, forward (``jvp``) and backward (``transpose``), so
    ``scope_device_ms`` still reads those operations as the
    interaction's; an order-2 and an FFM step carry no such name."""
    import jax
    import jax.numpy as jnp
    from fast_tffm_tpu.models import fm

    def paths(spec):
        B, L, U = 16, 8, 64
        args = [jnp.zeros((V + 1, spec.row_dim)),
                jnp.ones((V + 1, spec.row_dim)), jnp.zeros(B), jnp.ones(B),
                jnp.arange(U, dtype=jnp.int32), jnp.zeros((B, L), jnp.int32),
                jnp.ones((B, L))]
        if spec.model_type == "ffm":
            args.append(jnp.zeros((B, L), jnp.int32))
        txt = jax.jit(fm._bind(fm.train_step_body, spec, "fm_train_step")
                      ).lower(*args).as_text(debug_info=True)
        return set(re.findall(r'loc\("([^"]+)"', txt))

    three = {p for p in paths(_spec(3))
             if op_scope_device_ms.has_component(p + ":", "anova_scan")}
    assert any("jvp(interaction)/anova_scan/while" in p for p in three)
    assert any("transpose(jvp(interaction))/anova_scan/while" in p
               for p in three)
    assert {scope_device_ms.scope_of(p + ":") for p in three} == {
        "interaction"}
    ffm = dataclasses.replace(_spec(2), model_type="ffm", field_num=3,
                              factor_num=2)
    for spec in (_spec(2), ffm):
        assert not any("anova_scan" in p for p in paths(spec))
