"""Device-memory ledger + capacity planner (obs/memory.py, ISSUE 18):
one sizing formula, owner-tagged ledger gauges that never touch the
device, once-per-episode pressure events, OOM forensics at the
dispatch sites, the fmstat capacity planner cross-checked against the
LIVE ledger on real train/serve runs, and the serve reload spike /
capacity-degrade path."""

import json
import os

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.obs import memory as mem
from fast_tffm_tpu.obs.sink import read_events
from fast_tffm_tpu.obs.telemetry import RunTelemetry

pytestmark = pytest.mark.filterwarnings(
    "ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def _clean_ledger(monkeypatch):
    """The ledger and the fake-capacity env are process-global: every
    test starts from an empty book and a capacity-less backend."""
    monkeypatch.delenv(mem.FAKE_CAPACITY_ENV, raising=False)
    mem.LEDGER.reset()
    yield
    mem.LEDGER.reset()


def _corpus(path, n, vocab, seed=0):
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        y = int(rng.integers(0, 2))
        feats = sorted(rng.choice(vocab, size=4, replace=False))
        lines.append(f"{y} " + " ".join(f"{i}:1.0" for i in feats))
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")


def _train_cfg(tmp_path, **kw):
    _corpus(str(tmp_path / "train.txt"), 128, 4000)
    base = dict(vocabulary_size=4000, factor_num=8, batch_size=32,
                learning_rate=0.1, epoch_num=1, shuffle=False,
                max_features_per_example=16, bucket_ladder=(8, 16),
                train_files=(str(tmp_path / "train.txt"),),
                model_file=str(tmp_path / "m" / "fm"),
                metrics_file="auto", metrics_flush_steps=2,
                log_steps=0)
    base.update(kw)
    return FmConfig(**base)


# ------------------------------------------ table_bytes consolidation

def test_table_bytes_is_the_one_sizing_formula():
    cfg = FmConfig(vocabulary_size=1000, factor_num=4)
    assert mem.table_bytes(cfg) == cfg.num_rows * cfg.row_dim * 4
    ffm = FmConfig(vocabulary_size=1000, factor_num=4, field_num=3,
                   model_type="ffm")
    assert ffm.row_dim == 4 * 3 + 1
    assert mem.table_bytes(ffm) == ffm.num_rows * ffm.row_dim * 4
    # Explicit rows/dim for call sites with no config in scope, and
    # dtype_bytes for the planner's f16/int8 what-ifs.
    assert mem.table_bytes(rows=10, dim=5) == 200
    assert mem.table_bytes(cfg, dtype_bytes=2) \
        == cfg.num_rows * cfg.row_dim * 2


def test_lookup_memory_report_reads_through_the_seam(monkeypatch):
    """Satellite 3 (R018 migration): lookup.memory_report's device
    numbers come from obs/memory.device_memory_stats — unmeasured on
    the CPU container, the injected value under FM_FAKE_HBM_BYTES."""
    from fast_tffm_tpu.lookup import memory_report
    rep = memory_report()
    assert rep["device_in_use_mb"] is None  # unmeasured, never fake 0
    assert rep["device_limit_mb"] is None
    mem.LEDGER.register("table", 8 << 20)
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, str(64 << 20))
    rep = memory_report()
    assert rep["device_in_use_mb"] == 8
    assert rep["device_limit_mb"] == 64


# --------------------------------------------------- ledger semantics

def test_ledger_register_release_peak():
    L = mem.LEDGER
    L.register("table", 100)
    L.register("acc", 50)
    assert L.live_bytes() == 150
    assert L.peak_bytes() == 150
    L.register("table", 80)           # upsert, not accumulate
    assert L.live_bytes() == 130
    L.release("acc")
    assert L.live_bytes() == 80
    assert L.peak_bytes() == 150      # watermark survives releases
    L.release("never_registered")     # idempotent
    L.reset()
    assert L.live_bytes() == 0 and L.peak_bytes() == 0


def test_ledger_host_owners_excluded_from_device_total():
    L = mem.LEDGER
    L.register("table", 100)
    L.register("offload_table", 10_000, host=True)
    assert L.live_bytes() == 100
    assert L.host_owners() == {"offload_table": 10_000}
    # Re-registering on the other book moves the owner, not doubles it.
    L.register("offload_table", 10_000)
    assert L.live_bytes() == 10_100
    assert L.host_owners() == {}


def test_pressure_episode_fires_once_until_rearmed():
    L = mem.LEDGER
    assert L.begin_pressure_episode() is True
    assert L.begin_pressure_episode() is False
    L.end_pressure_episode()
    assert L.begin_pressure_episode() is True


# --------------------------------------------- the memory_stats seam

def test_seam_reports_none_on_cpu_and_env_injects_capacity(
        monkeypatch):
    assert mem.device_memory_stats() is None  # CPU container policy
    assert mem.device_capacity_bytes() is None
    mem.LEDGER.register("table", 300)
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, "1000")
    stats = mem.device_memory_stats()
    assert stats == {"bytes_limit": 1000, "bytes_in_use": 300}
    assert mem.device_capacity_bytes() == 1000


# ------------------------------------------------------ mem/* gauges

def test_ledger_gauges_empty_until_first_registration():
    assert mem.ledger_gauges() == {}


def test_ledger_gauges_rows(monkeypatch):
    mem.LEDGER.register("table", 100)
    mem.LEDGER.register("offload_acc", 40, host=True)
    rows = mem.ledger_gauges()
    assert rows["mem/table_bytes"] == 100.0
    assert rows["mem/offload_acc_bytes"] == 40.0
    assert rows["mem/live_bytes"] == 100.0
    assert rows["mem/host_live_bytes"] == 40.0
    assert rows["mem/peak_bytes"] == 100.0
    assert "mem/capacity_bytes" not in rows  # no capacity on CPU
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, "1000")
    rows = mem.ledger_gauges()
    assert rows["mem/capacity_bytes"] == 1000.0
    assert rows["mem/utilization_fraction"] == pytest.approx(0.1)


def test_mem_gauges_add_zero_device_fetches(tmp_path, monkeypatch):
    """THE acceptance pin: a flush that carries the full mem/* surface
    performs NO bulk_fetch — the ledger is host ints end to end,
    exactly the ``anatomy_gauges`` contract."""
    import fast_tffm_tpu.utils.fetch as fetch
    calls = []
    monkeypatch.setattr(fetch, "bulk_fetch",
                        lambda pairs, consume: calls.append(len(pairs))
                        or [])
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, "10000")
    mem.LEDGER.register("table", 800)
    mem.LEDGER.register("wire_buffers", 200)
    tel = RunTelemetry(str(tmp_path / "m.jsonl"), meta={},
                       flush_steps=1)
    tel.maybe_flush(1)
    tel.barrier_flush(2)
    tel.close()
    assert calls == []  # zero device fetches, ever
    evs = [e for e in read_events(str(tmp_path / "m.jsonl"))
           if e.get("event") == "metrics"]
    g = evs[-1]["gauges"]
    assert g["mem/table_bytes"] == 800.0
    assert g["mem/wire_buffers_bytes"] == 200.0
    assert g["mem/live_bytes"] == 1000.0
    assert g["mem/capacity_bytes"] == 10000.0


def test_empty_ledger_keeps_streams_byte_identical(tmp_path):
    """Pre-ledger consumers (and bare-registry tests) see no mem/*
    rows at all when nothing ever registered."""
    tel = RunTelemetry(str(tmp_path / "m.jsonl"), meta={},
                       flush_steps=1)
    tel.count("steps")
    tel.maybe_flush(1)
    tel.close()
    evs = [e for e in read_events(str(tmp_path / "m.jsonl"))
           if e.get("event") == "metrics"]
    assert not [k for e in evs for k in e["gauges"]
                if k.startswith("mem/")]


# --------------------------------------------------- pressure events

def test_hbm_pressure_emits_once_per_episode(tmp_path, monkeypatch):
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, "1000")
    path = str(tmp_path / "m.jsonl")
    tel = RunTelemetry(path, meta={}, flush_steps=1,
                       mem_pressure_fraction=0.5)
    mem.LEDGER.register("table", 600)     # 60% > 50% -> crossing
    tel.maybe_flush(1)
    tel.maybe_flush(2)                    # inside the episode: silent
    mem.LEDGER.register("table", 100)     # back below: re-arm
    tel.maybe_flush(3)
    mem.LEDGER.register("table", 900)     # second crossing
    tel.maybe_flush(4)
    tel.close()
    evs = list(read_events(path))
    pressure = [e for e in evs if e.get("event") == "health"
                and e.get("status") == "hbm_pressure"]
    assert len(pressure) == 2
    ev = pressure[0]
    assert ev["live_bytes"] == 600
    assert ev["capacity_bytes"] == 1000
    assert ev["threshold"] == 0.5
    assert ev["owners"] == {"table": 600}
    last = [e for e in evs if e.get("event") == "metrics"][-1]
    assert last["counters"]["mem/pressure_events"] == 2


def test_pressure_off_by_default_and_without_capacity(tmp_path,
                                                      monkeypatch):
    path = str(tmp_path / "m.jsonl")
    mem.LEDGER.register("table", 999)
    # Knob 0 -> no event even with capacity present.
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, "1000")
    tel = RunTelemetry(path, meta={}, flush_steps=1)
    tel.maybe_flush(1)
    tel.close()
    # Knob set but no capacity (CPU) -> no event either.
    monkeypatch.delenv(mem.FAKE_CAPACITY_ENV)
    tel = RunTelemetry(path + "2", meta={}, flush_steps=1,
                       mem_pressure_fraction=0.5)
    tel.maybe_flush(1)
    tel.close()
    for p in (path, path + "2"):
        assert not [e for e in read_events(p)
                    if e.get("event") == "health"]


def test_mem_pressure_fraction_knob_validates():
    cfg = FmConfig(mem_pressure_fraction=0.9)
    assert cfg.mem_pressure_fraction == 0.9
    with pytest.raises(ValueError, match="mem_pressure_fraction"):
        FmConfig(mem_pressure_fraction=1.5)


# ---------------------------------------------------- OOM forensics

def test_is_oom_matches_runtime_spellings():
    assert mem.is_oom(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert mem.is_oom(RuntimeError("Resource exhausted: hbm"))
    assert mem.is_oom(mem.HbmExhaustedError("wrapped"))
    assert not mem.is_oom(RuntimeError("INVALID_ARGUMENT"))


def test_oom_guard_wraps_with_ledger_and_hint():
    mem.LEDGER.register("table", 4 << 20)
    mem.LEDGER.register("adagrad_acc", 4 << 20)
    with pytest.raises(mem.HbmExhaustedError) as ei:
        with mem.oom_guard("train/step"):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
    msg = str(ei.value)
    assert "train/step" in msg
    assert "table" in msg and "adagrad_acc" in msg
    assert "fmstat capacity" in msg
    assert isinstance(ei.value.__cause__, RuntimeError)


def test_oom_guard_passes_other_errors_and_never_double_wraps():
    with pytest.raises(ValueError):
        with mem.oom_guard("x"):
            raise ValueError("not an oom")
    inner = mem.HbmExhaustedError("already attributed")
    with pytest.raises(mem.HbmExhaustedError) as ei:
        with mem.oom_guard("outer"):
            with mem.oom_guard("inner"):
                raise inner
    assert ei.value is inner


def test_injected_oom_at_train_dispatch_names_owners(tmp_path,
                                                     monkeypatch):
    """Acceptance: a RESOURCE_EXHAUSTED at the train dispatch site
    surfaces the per-owner breakdown in the wrapped error AND a crash
    event in the stream."""
    import fast_tffm_tpu.train as train_mod

    def exploding_maker(*maker_args, **maker_kw):
        def step(table, acc, **kw):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory while trying to "
                "allocate 1073741824 bytes.")
        return step

    # The test harness fakes several CPU devices, so the session may
    # build either the plain or the mesh step — explode both makers
    # (the sharded one is imported locally inside the session).
    import fast_tffm_tpu.parallel.sharded as sharded_mod
    monkeypatch.setattr(train_mod, "make_train_step", exploding_maker)
    monkeypatch.setattr(sharded_mod, "make_sharded_train_step",
                        exploding_maker)
    cfg = _train_cfg(tmp_path)
    with pytest.raises(mem.HbmExhaustedError) as ei:
        train_mod.train(cfg)
    msg = str(ei.value)
    assert "device out of memory at train/step" in msg
    assert "table" in msg and "adagrad_acc" in msg
    assert "fmstat capacity" in msg
    crash = [e for e in read_events(cfg.model_file + ".metrics.jsonl")
             if e.get("event") == "crash"]
    assert crash
    assert "RESOURCE_EXHAUSTED" in crash[0]["error"]


# ------------------------------------------------- capacity planner

def test_parse_what_if():
    assert mem.parse_what_if("") == {}
    assert mem.parse_what_if(
        "vocabulary_size=1000, dtype=f16,shards=4") \
        == {"vocabulary_size": 1000, "dtype": "f16", "shards": 4}
    with pytest.raises(ValueError, match="key=value"):
        mem.parse_what_if("vocab:1000")
    with pytest.raises(ValueError, match="dtype"):
        mem.parse_what_if("dtype=f13")


def test_plan_train_owners_and_overrides():
    cfg = FmConfig(vocabulary_size=1000, factor_num=4, batch_size=32,
                   max_features_per_example=16)
    p = mem.plan(cfg, "train")
    tbl = mem.table_bytes(cfg)
    assert p["owners"]["table"] == tbl
    assert p["owners"]["adagrad_acc"] == tbl
    assert p["owners"]["wire_buffers"] == 2 * (32 * 16 * 8 + 32 * 4)
    assert p["total_bytes"] == sum(p["owners"].values())
    assert p["verdict"].startswith("UNKNOWN")  # no capacity on CPU
    # Overrides: vocab scales rows; f16 halves the table but the
    # Adagrad accumulator stays f32; shards divide the per-device row.
    p2 = mem.plan(cfg, "train", {"vocabulary_size": 2000})
    assert p2["owners"]["table"] == 2001 * cfg.row_dim * 4
    p3 = mem.plan(cfg, "train", {"dtype": "f16"})
    assert p3["owners"]["table"] == tbl // 2
    assert p3["owners"]["adagrad_acc"] == tbl
    # More than one shard: the mesh's padded rows (ckpt_rows), a
    # device's share beside the whole.
    p4 = mem.plan(cfg, "train", {"shards": 4})
    whole = mem.table_bytes(cfg, rows=cfg.ckpt_rows)
    assert p4["owners"]["table"] == whole // 4
    assert p4["sharded_owners"] == {"table": whole, "adagrad_acc": whole}
    assert p4["shards"] == 4 and p["shards"] == 1
    assert p["sharded_owners"] == {}


def test_plan_serve_and_offload_host_owners():
    cfg = FmConfig(vocabulary_size=1000, factor_num=4)
    p = mem.plan(cfg, "serve")
    tbl = mem.table_bytes(cfg)
    assert p["owners"] == {"serve_table": tbl,
                           "serve_reload_transient": tbl}
    off = FmConfig(vocabulary_size=1000, factor_num=4, lookup="host",
                   dedup="host")
    po = mem.plan(off, "train")
    assert "table" not in po["owners"]
    # Same owner tags the train session registers (host book).
    assert po["host_owners"]["offload_table"] == tbl
    assert po["host_owners"]["offload_acc"] == tbl
    assert po["total_bytes"] == po["owners"]["wire_buffers"]


def test_plan_verdict_against_capacity(monkeypatch):
    cfg = FmConfig(vocabulary_size=1000, factor_num=4)
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, str(1 << 30))
    assert mem.plan(cfg, "serve")["verdict"] == "FITS"
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, "1024")
    p = mem.plan(cfg, "serve")
    assert p["verdict"] == "EXCEEDS"
    text = mem.render_plan(p)
    assert "serve_table" in text
    assert "predicted device total" in text
    assert "verdict: EXCEEDS" in text


def test_preflight_refuses_oversized_and_noop_without_capacity(
        monkeypatch):
    cfg = FmConfig(vocabulary_size=100_000, factor_num=8)
    mem.preflight_capacity(cfg, "train")  # CPU: no capacity, no-op
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, "65536")
    with pytest.raises(ValueError) as ei:
        mem.preflight_capacity(cfg, "train")
    msg = str(ei.value)
    assert "predicted device total" in msg
    assert "fmstat capacity" in msg and "--what-if" in msg
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, str(1 << 34))
    mem.preflight_capacity(cfg, "train")  # fits: silent


@pytest.mark.parametrize("capacity,refused", [
    (65536, True),       # under a device's share of the mesh: refused
    (2 << 20, False),    # under the whole 7.4 MB, over a 0.9 MB share
])
def test_train_preflight_fails_fast(tmp_path, monkeypatch, capacity,
                                    refused):
    """Satellite 2: the oversized config is refused BEFORE any device
    allocation, with the planner breakdown in the error. The session
    passes the mesh it built (the suite's eight devices): a table that
    exceeds one device and fits the mesh trains."""
    import jax
    from fast_tffm_tpu.train import train
    cfg = _train_cfg(tmp_path, vocabulary_size=100_000)
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, str(capacity))
    assert 2 * mem.table_bytes(cfg) > capacity
    if not refused:
        train(cfg)
        return
    with pytest.raises(ValueError, match="predicted device total") as ei:
        train(cfg)
    assert f"on each of the {jax.device_count()} devices" in str(ei.value)


@pytest.mark.parametrize("devices,capacity,refused", [
    (1, 3 << 20, True),      # 7.3 MB of state on one 3 MB device
    (4, 3 << 20, False),     # 1.8 MB a device on four
    (4, 1 << 20, True),      # exceeds four: refused by name
])
def test_preflight_is_per_device_of_the_mesh(monkeypatch, devices,
                                             capacity, refused):
    """ISSUE 27: the pre-flight holds ONE device's share of table and
    accumulator against one device's capacity. A table that exceeds
    one device and fits four starts on four and is refused on one."""
    import logging
    from fast_tffm_tpu.utils.logging import get_logger
    cfg = FmConfig(vocabulary_size=100_000, factor_num=8, batch_size=32,
                   max_features_per_example=16)
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, str(capacity))
    whole = mem.table_bytes(
        cfg, rows=cfg.ckpt_rows if devices > 1 else cfg.num_rows)
    if not refused:
        said = []
        handler = logging.Handler()
        handler.emit = lambda r: said.append(r.getMessage())
        get_logger().addHandler(handler)
        try:
            mem.preflight_capacity(cfg, "train", shards=devices)
        finally:
            get_logger().removeHandler(handler)
        assert f"per device, 1/4 of {2 * whole} bytes" in said[0]
        return
    with pytest.raises(ValueError) as ei:
        mem.preflight_capacity(cfg, "train", shards=devices)
    msg = str(ei.value)
    assert "adagrad_acc" in msg and "verdict: EXCEEDS" in msg
    assert "what-if {" not in msg  # the session's own mesh, no what-if
    assert "more devices" in msg and "lookup = host" in msg
    assert "ROADMAP" not in msg
    if devices > 1:
        assert "on each of the 4 devices" in msg
        assert f"(per device, 1/4 of {mem._mb(whole)})" in msg
        assert "per device" in msg.split("predicted device total")[1]
    else:
        assert "per device" not in msg


def test_offload_and_serve_plans_do_not_follow_the_mesh():
    """Offload keeps the table on the host (its session builds no
    mesh), a server holds a whole table per replica: neither plan is
    divided unless a what-if says so, and a plan is a function of the
    config alone, whatever devices the box that asks has."""
    cfg = FmConfig(vocabulary_size=1000, factor_num=4)
    tbl = mem.table_bytes(cfg)
    ps = mem.plan(cfg, "serve")
    assert ps["shards"] == 1 and ps["owners"]["serve_table"] == tbl
    off = FmConfig(vocabulary_size=1000, factor_num=4, lookup="host",
                   dedup="host")
    po = mem.plan(off, "train", shards=4)
    assert po["sharded_owners"] == {}
    assert "per device" not in mem.render_plan(po)
    assert mem.plan(off, "train")["host_owners"]["offload_table"] == tbl
    import jax
    assert jax.device_count() > 1  # the suite's eight CPU devices
    assert mem.plan(cfg, "train")["shards"] == 1
    assert mem.plan(cfg, "train")["owners"]["table"] == tbl


# ------------------------------- plan vs live ledger (the 10% check)

def test_plan_within_10pct_of_live_ledger_train(tmp_path):
    """Acceptance: the from-config prediction agrees with the ledger
    a REAL train run registered, within 10%, for the default train
    shape."""
    from fast_tffm_tpu.train import train
    # 40,000 rows: a device's share of table and accumulator, not the
    # whole batch's wire ceiling, is what the plan is made of.
    cfg = _train_cfg(tmp_path, vocabulary_size=40_000)
    train(cfg)
    live = 0.0
    for ev in read_events(cfg.model_file + ".metrics.jsonl"):
        if ev.get("event") == "metrics":
            live = max(live, ev["gauges"].get("mem/live_bytes", 0.0))
    assert live > 0
    # The session meshes over every device it sees (eight here) and
    # books one device's share of the mesh's padded rows.
    import jax
    n = jax.device_count()
    p = mem.plan(cfg, "train", shards=n)
    assert p["total_bytes"] == pytest.approx(live, rel=0.10)
    # The model state itself is predicted exactly.
    assert p["owners"]["table"] == mem.table_bytes(
        cfg, rows=cfg.ckpt_rows) // n


def _served(tmp_path, **overrides):
    """A published checkpoint + a live ScorerServer against it."""
    from fast_tffm_tpu.checkpoint import CheckpointState
    from fast_tffm_tpu.serve import ScorerServer
    cfg = FmConfig(vocabulary_size=4000, factor_num=4,
                   max_features_per_example=16, bucket_ladder=(8, 16),
                   serve_max_batch=8, serve_poll_seconds=60.0,
                   model_file=str(tmp_path / "m" / "fm"), **overrides)
    rng = np.random.default_rng(0)
    table = rng.standard_normal(
        (cfg.ckpt_rows, cfg.row_dim)).astype(np.float32) * 0.01
    ckpt = CheckpointState(cfg.model_file)
    for step in (1, 2):
        ckpt.save(step, table, np.full_like(table, 0.1),
                  vocabulary_size=cfg.vocabulary_size, wait=True)
    ckpt.publish_step(1)
    ckpt.close()
    return cfg, ScorerServer(cfg, watch=False)


def test_plan_within_10pct_of_live_ledger_serve(tmp_path):
    cfg, server = _served(tmp_path)
    try:
        live = mem.LEDGER.owners()
        p = mem.plan(cfg, "serve")
        assert live["serve_table"] == pytest.approx(
            p["owners"]["serve_table"], rel=0.10)
        # Steady-state serving holds ONE table; the transient is plan
        # headroom, not resident state.
        assert "serve_reload_table" not in live
    finally:
        server.close()
    assert mem.LEDGER.owners() == {}  # close releases its owners


# ------------------------------------------- serve reload spike path

def test_serve_reload_spike_gauges_old_plus_new(tmp_path):
    """Acceptance: a real hot reload's serve/reload_peak_bytes shows
    the old+new transient."""
    cfg, server = _served(tmp_path)
    try:
        old = mem.LEDGER.owners()["serve_table"]
        assert server.reload_step(2)
        g = server._reg.snapshot()["gauges"]
        assert g["serve/reload_peak_bytes"] == float(
            old + mem.LEDGER.owners()["serve_table"])
        assert g["serve/reload_peak_bytes"] == pytest.approx(
            2 * mem.table_bytes(cfg))
    finally:
        server.close()


def test_reload_exceeding_capacity_degrades_to_counted_failure(
        tmp_path, monkeypatch):
    """A reload whose old+new transient would not fit is REFUSED on
    the keep-serving path: reload_failures counts it, the old step
    keeps serving, and nothing was allocated."""
    cfg, server = _served(tmp_path)
    try:
        resident = mem.LEDGER.live_bytes()
        # Room for the old table plus half a new one: the swap's
        # old+new transient cannot fit.
        monkeypatch.setenv(mem.FAKE_CAPACITY_ENV,
                           str(resident + mem.table_bytes(cfg) // 2))
        assert not server.reload_step(2)
        snap = server._reg.snapshot()
        assert snap["counters"]["serve/reload_failures"] == 1
        assert snap["gauges"]["serve/served_step"] == 1.0
        assert "serve_reload_table" not in mem.LEDGER.owners()
        # With headroom restored the same reload succeeds.
        monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, str(1 << 30))
        assert server.reload_step(2)
        assert server._reg.snapshot()["gauges"]["serve/served_step"] \
            == 2.0
    finally:
        server.close()


def test_server_startup_preflight_fails_fast(tmp_path, monkeypatch):
    from fast_tffm_tpu.checkpoint import CheckpointState
    from fast_tffm_tpu.serve import ScorerServer
    cfg = FmConfig(vocabulary_size=4000, factor_num=4,
                   max_features_per_example=16, bucket_ladder=(8, 16),
                   serve_max_batch=8,
                   model_file=str(tmp_path / "m" / "fm"))
    table = np.zeros((cfg.ckpt_rows, cfg.row_dim), dtype=np.float32)
    ckpt = CheckpointState(cfg.model_file)
    ckpt.save(1, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    ckpt.publish_step(1)
    ckpt.close()
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, "4096")
    with pytest.raises(ValueError, match="predicted device total"):
        ScorerServer(cfg, watch=False)


# ------------------------------------------------ fmstat / fmtrace

def _write_cfg_file(tmp_path, vocab=1000):
    p = tmp_path / "t.cfg"
    p.write_text(f"""
[General]
vocabulary_size = {vocab}
factor_num = 4
model_file = {tmp_path}/model/fm

[Train]
train_files = {tmp_path}/train.txt
batch_size = 32
max_features_per_example = 16
""")
    return str(p)


def test_fmstat_capacity_cli(tmp_path, capsys):
    from tools.fmstat import main
    cfg_path = _write_cfg_file(tmp_path)
    assert main(["capacity", cfg_path]) == 0
    out = capsys.readouterr().out
    assert "capacity plan (train)" in out
    assert "predicted device total" in out
    assert "UNKNOWN" in out  # CPU: no capacity
    # --what-if + --capacity-bytes: verdict + exit code track EXCEEDS.
    assert main(["capacity", cfg_path, "--kind", "serve",
                 "--what-if", "vocabulary_size=1000000,dtype=f16",
                 "--capacity-bytes", str(1 << 30)]) == 0
    assert "FITS" in capsys.readouterr().out
    assert main(["capacity", cfg_path, "--capacity-bytes",
                 "1024"]) == 1
    assert "EXCEEDS" in capsys.readouterr().out


def test_fmstat_capacity_json(tmp_path, capsys):
    from tools.fmstat import main
    cfg_path = _write_cfg_file(tmp_path)
    assert main(["capacity", cfg_path, "--json", "--what-if",
                 "shards=2"]) == 0
    p = json.loads(capsys.readouterr().out)
    assert p["kind"] == "train"
    assert p["overrides"] == {"shards": 2}
    assert p["total_bytes"] == sum(p["owners"].values())


def test_fmtrace_fraction_counter_unit():
    from tools.fmtrace import counter_track
    assert counter_track("mem/utilization_fraction") \
        == "mem/utilization_fraction [ratio]"
    assert counter_track("mem/live_bytes") == "mem/live_bytes [B]"


# --------------------------------------------- fmstat MEMORY section

def test_memory_table_from_gauges():
    from fast_tffm_tpu.obs.attribution import memory_table
    assert memory_table({"gauges": {}}) is None
    t = memory_table({
        "gauges": {"mem/table_bytes": 80.0, "mem/live_bytes": 100.0,
                   "mem/peak_bytes": 200.0,
                   "mem/capacity_bytes": 1000.0,
                   "mem/utilization_fraction": 0.1,
                   "serve/reload_peak_bytes": 160.0},
        "counters": {"mem/pressure_events": 2.0}})
    assert t["owners"] == {"table": 80.0}
    assert t["live_bytes"] == 100.0
    assert t["peak_bytes"] == 200.0
    assert t["capacity_bytes"] == 1000.0
    assert t["pressure_events"] == 2.0
    assert t["reload_peak_bytes"] == 160.0


def test_render_memory_section_and_pressure_verdict(tmp_path,
                                                    monkeypatch):
    """End to end through the REAL stream: a pressured run renders a
    MEMORY section and an HBM-PRESSURE verdict (ranked below DEGRADED,
    above STALE PUBLISH)."""
    from fast_tffm_tpu.obs.attribution import (health_verdict, render,
                                               summarize)
    from fast_tffm_tpu.train import train
    cfg = _train_cfg(tmp_path, mem_pressure_fraction=0.5)
    import jax  # the session books one device's share of its mesh
    owners = mem.plan(cfg, "train", shards=jax.device_count())["owners"]
    resident = owners["table"] + owners["adagrad_acc"]
    monkeypatch.setenv(mem.FAKE_CAPACITY_ENV, str(int(resident / 0.6)))
    train(cfg)
    summary = summarize([cfg.model_file + ".metrics.jsonl"])
    v = health_verdict(summary)
    assert v["verdict"].startswith("HBM-PRESSURE")
    assert "fmstat capacity" in v["detail"]
    text = render(summary)
    assert "MEMORY" in text
    assert "live / peak" in text


def test_pressure_ranks_below_worker_loss():
    from fast_tffm_tpu.obs.attribution import health_verdict
    pressure = {"status": "hbm_pressure", "fraction": 0.95,
                "threshold": 0.9, "owners": {"table": 100}}
    lost = {"status": "worker_lost",
            "lost": [{"process_index": 1}]}
    v = health_verdict({"health_events": [pressure, lost],
                        "run_starts": 1, "run_ends": 1})
    assert v["verdict"].startswith("DEGRADED")
    v = health_verdict({"health_events": [pressure],
                        "run_starts": 1, "run_ends": 1})
    assert v["verdict"].startswith("HBM-PRESSURE")
