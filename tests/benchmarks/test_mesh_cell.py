"""The four-chip cell ``fm16x4-train-zipf`` (ISSUE 27): its
configuration and metric files well-formed and tied to the program's
capacity plan, and ``readers/collective_device_ms`` over a recorded
four-device trace. All on the CPU; the recorded trace is a v5e host's
(four TPU v5 lite chips)."""

import json
import os

import pytest

from benchmarks import harness, trace_reduce
from benchmarks.readers import collective_device_ms as cdm

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TESTDATA = os.path.join(REPO, "benchmarks", "testdata")
X4 = os.path.join(TESTDATA, "tiny_train_tpu_x4.xplane.pb")
ONE_CHIP = os.path.join(TESTDATA, "tiny_train_tpu_scoped.xplane.pb")
CELL = "fm16x4-train-zipf"
MESH_STEP = ["fm_sharded_train_step"]
V5E_HBM = 16911433728            # bytes_limit of one v5e chip (PERF.md)


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def spec():
    return _json("BENCHMARK.json")


# ---- the configuration -------------------------------------------------

def test_the_x4_configuration_is_the_one_chip_share_times_four(spec):
    one = _json("benchmarks", "configs", "fm-k16-criteo1tb.json")
    x4 = _json("benchmarks", "configs", "fm-k16-criteo1tb-x4.json")
    changed = {(sec, k) for sec in x4["program"]
               for k in x4["program"][sec]
               if x4["program"][sec][k] != one["program"][sec].get(k)}
    assert changed == {("General", "vocabulary_size"),
                       ("Train", "batch_size")}
    assert x4["program"]["General"]["vocabulary_size"] == 2 ** 28
    assert x4["program"]["Train"]["batch_size"] == 4 * 8192
    for key in ("features", "precision"):
        assert x4[key] == one[key]
    assert set(x4["assumed"]) == set(one["assumed"])
    assert x4["mesh_chips"] == 4 and x4["deployment_chips"] == 64
    assert "synchronous" in x4["guarantees"]
    assert "reference.py" in x4["reference"]
    assert x4["reference_family"] == one["reference_family"] == "fm_order2"
    assert set(x4["reduced"]) == set(x4["reduced_why"]) == {
        "vocabulary_size", "corpus_lines", "mesh_chips"}
    entry = next(c for c in spec["configs"] if c["name"] == x4["name"])
    assert entry["reduced"] == x4["reduced"]
    assert entry["file"] == "benchmarks/configs/fm-k16-criteo1tb-x4.json"
    assert set(x4["check_limits"]) == {"train"}
    assert set(x4["check_limits"]["train"]) == set(
        one["check_limits"]["train"])


@pytest.mark.parametrize("shards,verdict", [(1, "EXCEEDS"), (4, "FITS")])
def test_the_x4_table_needs_the_four_chips(monkeypatch, tmp_path,
                                           shards, verdict):
    """The program's own planner on the cell's INI: refused on one
    v5e chip, 9.1 GB a chip (over the 25% floor twice) on four."""
    from fast_tffm_tpu.obs import memory as mem
    run = harness.Run(cell=harness.load_cell(CELL), seed=1, seconds=1.0,
                      trace=False, rehearse=True, t0=0.0)
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path / "work"))
    cfg = harness.program_cfg(run.cell.config, {}, run.work_dir)
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, str(V5E_HBM))
    p = mem.plan(cfg, "train", {"shards": shards})
    assert p["verdict"] == verdict
    if shards == 4:
        assert 0.5 * V5E_HBM < p["total_bytes"] < 0.6 * V5E_HBM
        assert p["owners"]["table"] == 2 ** 26 * 17 * 4 + 1024 * 17 * 4


def test_the_cell_asks_for_four_chips_and_reports_what_it_should(spec):
    cell = harness.load_cell(CELL)
    assert cell.chips == 4 and cell.kind == "train"
    assert cell.traffic == _json("benchmarks", "traffic",
                                 "train-zipf.json")
    # (how many cells may ask for four, and that this one keeps its
    # place in every list, is test_benchmark_json.py's)
    assert {m["name"] for m in cell.end_to_end} == {
        "train_examples_per_s_per_chip", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert {"collective_exposed_ms", "step_device_ms", "step_roofline",
            "input_wait_share", "h2d_bytes_per_example", "uniq_slot_fill",
            "table_gather_ms", "slot_expand_ms", "interaction_ms",
            "table_scatter_ms", "step_unscoped_ms", "setup_start_s",
            "setup_compile_s"} <= reported
    assert "dedup_sort_ms" not in reported   # no such scope in the step


# ---- the metric and its reader ----------------------------------------

def test_the_metric_file_matches_its_entry(spec):
    own = _json("benchmarks", "layer_metrics", "collective_exposed_ms.json")
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "collective_exposed_ms")
    assert entry["workloads"][0] == CELL    # a mesh's metric: x4 cells
    assert own["reader"] == "collective_device_ms"
    assert own["args"] == {"programs": MESH_STEP}
    assert own["source"] == "device_trace" and own["better"] == "lower"


@pytest.mark.parametrize("name,kind,nbytes", [
    ("%all-reduce.8 = f32[65536,17]{0,1:T(8,128)} all-reduce(%input), "
     "channel_id=38", "all-reduce", 65536 * 17 * 4),
    ("%all-reduce.10 = (f32[]{:T(128)}, f32[]{:T(128)}, f32[]{:T(128)}) "
     "all-reduce(%a, %b, %c)", "all-reduce", 12),
    ("%all-gather-start.3 = (s32[16384]{0}, s32[65536]{0}) "
     "all-gather-start(%x)", "all-gather-start", (16384 + 65536) * 4),
    ("all-gather-done.2", "all-gather-done", 0),
    ("%collective-permute.1 = bf16[8,128]{1,0} collective-permute(%x)",
     "collective-permute", 2048),
    ("%reduce-scatter = f32[4,17]{1,0} reduce-scatter(%x)",
     "reduce-scatter", 272),
    ("%all-to-all.2 = pred[16]{0} all-to-all(%x)", "all-to-all", 16),
    # compute, whatever it feeds or is named after
    ("%fusion.3 = f32[65536,17]{0,1} fusion(%all-reduce.8)", None, None),
    ("%all-reduce-feeder_fusion = f32[4]{0} fusion(%x)", None, None),
    ("%reduce.4 = f32[]{:T(128)} reduce(%x)", None, None),
])
def test_what_counts_as_a_collective(name, kind, nbytes):
    assert cdm.collective_of(name) == kind
    if kind is not None:
        assert cdm.result_bytes(name) == nbytes


@pytest.mark.parametrize("a,b,left", [
    ([(0, 10), (20, 30)], [(2, 3), (5, 22), (29, 40)], 11.0),
    ([(0, 10)], [], 10.0),
    ([(0, 10)], [(0, 10)], 0.0),
    ([], [(0, 10)], 0.0),
    ([(5, 6)], [(0, 5), (6, 9)], 1.0),
    ([(0, 4), (2, 10)], [(8, 9), (1, 3)], 7.0),     # neither merged
])
def test_exposed_time_is_the_collectives_less_everything_else(a, b, left):
    assert cdm.exposed_seconds(a, b) == pytest.approx(left)


def test_exposed_time_of_a_made_up_step():
    """One chip, one execution of 10 s: an all-reduce of 4 s of which 1
    s runs under a fusion, a fusion alone, an all-gather-done of 1 s
    alone. Exposed: 3 + 1."""
    Op = trace_reduce.Op
    ops = [Op("%fusion.1 = f32[8]{0} fusion()", 0.0, 2.0, {}),
           Op("%all-reduce.1 = f32[8]{0} all-reduce(%fusion.1)", 1.0, 5.0,
              {}),
           Op("%fusion.2 = f32[8]{0} fusion()", 6.0, 7.0, {}),
           Op("%all-gather-done.1 = f32[32]{0} all-gather-done(%s)", 8.0,
              9.0, {}),
           Op("%all-reduce.1 = f32[8]{0} all-reduce(%fusion.1)", 11.0,
              12.0, {})]                    # the next execution's
    mods = [Op("jit_fm_sharded_train_step(7)", 0.0, 10.0, {})]
    t = trace_reduce.Trace(
        [trace_reduce.DeviceTrace("/device:TPU:0", ops, mods)], [], 0.0,
        12.0)
    chips = cdm.by_chip(t, MESH_STEP)
    (exposed, each), = chips["/device:TPU:0"]
    assert exposed == pytest.approx(4.0)
    assert each == {"%all-reduce.1": (pytest.approx(4.0), 32),
                    "%all-gather-done.1": (pytest.approx(1.0), 128)}
    assert cdm.read({"trace": t}, MESH_STEP) == pytest.approx(4000.0)
    assert cdm.by_chip(t, ["fm_train_step"]) == {}


def test_collective_reader_on_a_recorded_four_chip_trace():
    """benchmarks/testdata/tiny_train_tpu_x4.xplane.pb: two steps of
    the tests' tiny FM (vocabulary 4,096, k=4, batch 64) on the four
    chips of a v5e host, mesh (4,1) (my chip run, PR 27; cut by
    ``xplane_meta.cut``). The numbers were read off the file's events
    by hand (a separate loop over ProfileData: the operations of each
    ``XLA Ops`` line do not overlap, so the exposed time of an
    execution is the sum of its collectives' durations) before the
    reader ran."""
    t = trace_reduce.reduce(X4)
    assert [d.name for d in t.devices] == [
        f"/device:TPU:{i}" for i in range(4)]
    chips = cdm.by_chip(t, MESH_STEP)
    assert {k: len(v) for k, v in chips.items()} == {
        f"/device:TPU:{i}": 2 for i in range(4)}
    by_hand_ns = {"/device:TPU:0": (30047.0, 30160.0),
                  "/device:TPU:1": (28049.0, 28433.0),
                  "/device:TPU:2": (28238.0, 28281.0),
                  "/device:TPU:3": (27898.0, 28441.0)}
    for chip, runs in chips.items():
        assert sorted(1e9 * r[0] for r in runs) == pytest.approx(
            by_hand_ns[chip], abs=0.01)
        assert set(runs[0][1]) == {
            "%all-gather.8", "%all-gather.9", "%all-gather.10",
            "%all-reduce", "%all-reduce.1", "%all-reduce.2",
            "%all-reduce.8", "%all-reduce.9"}
    # a gather of the data-sharded updates, f32[U,5]: the two traced
    # steps are two programs, fitted to U = 64 and U = 128 slots
    assert {r[1]["%all-gather.9"][1] for r in chips["/device:TPU:0"]} == {
        64 * 5 * 4, 128 * 5 * 4}
    # median of each chip, then the worst chip: 30.1035 us on chip 0
    ctx = {"trace": t, "device_kind": "TPU v5 lite"}
    assert cdm.read(ctx, MESH_STEP) == pytest.approx(0.0301035, abs=1e-7)
    assert cdm.read(ctx, MESH_STEP) == cdm.read({"trace": t}, MESH_STEP)
    # the step reads the same through the accepted readers: median of
    # the eight executions' device-busy time
    assert t.program_device_ms(MESH_STEP) == pytest.approx(0.041806,
                                                           rel=1e-4)
    assert t.program_device_ms(["fm_train_step"]) is None


def test_a_one_chip_trace_has_no_mesh_step_to_read():
    """The parent's side of a traced run and the one-chip cells: the
    reader finds nothing, returns None and does not raise."""
    ctx = {"trace": trace_reduce.reduce(ONE_CHIP)}
    assert cdm.read(ctx, MESH_STEP) is None
