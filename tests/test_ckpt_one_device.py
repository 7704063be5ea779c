"""A one-device state's save and resume (ISSUE 52, ROADMAP R15): the
snapshot goes to the host and the restore comes from it in row blocks,
so the device holds no second table on either way; the checkpoint on
disk keeps the contract every topology shares; a job that saved, was
killed and came back goes on where ``resume_start_epoch`` says; the
save's phases count."""

import json
import logging

import jax
import numpy as np
import pytest

from fast_tffm_tpu import checkpoint
from fast_tffm_tpu.checkpoint import (CheckpointState, HostSnapshot,
                                      checkpoint_template, ckpt_state,
                                      device_rows, resume_start_epoch,
                                      saver_buffers, snapshot_buffers)
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.models.fm import init_accumulator, init_table
from fast_tffm_tpu.train import train

N_LINES, BATCH = 480, 32
STEPS_PER_EPOCH = N_LINES // BATCH


@pytest.fixture
def one_device(monkeypatch):
    """train() takes the one-chip path (the suite has eight CPU devices)."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 1,000 rows: a table of a few thousand rows goes in
    several, the last moved back to end on the last row."""
    monkeypatch.setattr(checkpoint, "STATE_BLOCK_ROWS", 1000)


def _cfg(tmp_path, **kw):
    base = dict(vocabulary_size=5000, factor_num=4,
                model_file=str(tmp_path / "m" / "fm"))
    base.update(kw)
    return FmConfig(**base)


def _state(cfg, seed=0):
    """A state no two rows of which are alike."""
    table = init_table(cfg, seed)
    acc = init_accumulator(cfg) + jax.numpy.abs(table)
    return table, acc


def _big(of):
    """Live device arrays at least as large as ``of``."""
    return [a for a in jax.live_arrays() if a.nbytes >= of.nbytes]


# ---- the snapshot and the placement ---------------------------------------

def test_the_snapshot_is_the_contract_and_makes_no_second_table(
        tmp_path, small_blocks, monkeypatch):
    cfg = _cfg(tmp_path)
    table, acc = _state(cfg)
    assert cfg.num_rows == 5001 and cfg.ckpt_rows == 8192
    known = {id(a) for a in _big(table)}
    seen = []
    real = checkpoint._take_rows

    def spy(arr, start, *, block):
        seen.append((start, block))
        assert {id(a) for a in _big(table)} <= known   # no array of its size
        return real(arr, start, block=block)
    monkeypatch.setattr(checkpoint, "_take_rows", spy)
    host_t, host_a = ckpt_state(cfg, table, acc)
    assert {id(a) for a in _big(table)} <= known
    assert [s for s, _ in seen[:6]] == [0, 1000, 2000, 3000, 4000, 4001]
    assert all(b == 1000 for _, b in seen) and len(seen) == 12
    for host, arr, tail in ((host_t, table, 0.0),
                            (host_a, acc, cfg.adagrad_init)):
        assert type(host) is HostSnapshot and host.dtype == np.float32
        assert host.shape == (cfg.ckpt_rows, cfg.row_dim)
        assert (host[:cfg.num_rows] == np.asarray(arr)).all()
        assert (host[cfg.num_rows:] == np.float32(tail)).all()


def test_a_table_smaller_than_a_block_goes_in_one(tmp_path):
    cfg = _cfg(tmp_path, vocabulary_size=50)
    table, acc = _state(cfg)
    host_t, host_a = ckpt_state(cfg, table, acc)
    assert (host_t[:51] == np.asarray(table)).all()
    assert (host_a[:51] == np.asarray(acc)).all()
    assert (host_t[51:] == 0).all() and host_t.shape[0] == 4096


def test_a_state_at_ckpt_rows_is_handed_on_as_it_is(tmp_path):
    """A mesh's state is the contract's shape already: orbax snapshots
    its shards itself."""
    cfg = _cfg(tmp_path, vocabulary_size=4095)
    assert cfg.num_rows == cfg.ckpt_rows == 4096
    table, acc = _state(cfg)
    out = ckpt_state(cfg, table, acc)
    assert out[0] is table and out[1] is acc


def test_a_job_s_buffers_are_reused_and_orbax_does_not_copy_them(
        tmp_path, small_blocks):
    import copy
    cfg = _cfg(tmp_path)
    into = snapshot_buffers(cfg)
    assert (into[0][cfg.num_rows:] == 0).all() and (
        into[1][cfg.num_rows:] == np.float32(cfg.adagrad_init)).all()
    assert copy.deepcopy(into[0]) is into[0]        # what orbax would do
    table, acc = _state(cfg)
    out = ckpt_state(cfg, table, acc, into=into)
    assert out[0] is into[0] and out[1] is into[1]
    assert (into[0][:cfg.num_rows] == np.asarray(table)).all()
    table2, acc2 = _state(cfg, seed=1)
    ckpt_state(cfg, table2, acc2, into=into)
    assert (into[0][:cfg.num_rows] == np.asarray(table2)).all()
    assert (into[1][:cfg.num_rows] == np.asarray(acc2)).all()
    assert (into[1][cfg.num_rows:] == np.float32(cfg.adagrad_init)).all()


def test_warming_makes_the_program_a_save_of_a_steps_result_runs(tmp_path):
    """The copy's program is compiled for a committed array (a train
    step's result) although the fresh table it is warmed on is not."""
    cfg = _cfg(tmp_path, vocabulary_size=777)
    fresh = jax.jit(lambda: jax.numpy.ones((cfg.num_rows, cfg.row_dim)))()
    assert not fresh._committed
    pair = saver_buffers(cfg, fresh, fresh)
    assert (pair[0][:cfg.num_rows] == 1).all() and (pair[0][cfg.num_rows:]
                                                    == 0).all()
    assert (pair[1][cfg.num_rows:] == np.float32(cfg.adagrad_init)).all()
    committed = jax.device_put(fresh, fresh.sharding)
    before = checkpoint._take_rows._cache_size()
    out = np.empty((cfg.num_rows, cfg.row_dim), np.float32)
    checkpoint._rows_to_host(committed, out)
    assert checkpoint._take_rows._cache_size() == before
    assert (out == 1).all()


def test_placing_a_restored_array_makes_no_array_of_ckpt_rows(
        tmp_path, small_blocks, monkeypatch):
    cfg = _cfg(tmp_path)
    host = np.arange(cfg.ckpt_rows * cfg.row_dim, dtype=np.float32).reshape(
        cfg.ckpt_rows, cfg.row_dim)
    real = checkpoint._put_rows
    starts = []

    def spy(dst, blk, start):
        starts.append(int(start))
        assert blk.shape == (1000, cfg.row_dim)
        return real(dst, blk, start)
    monkeypatch.setattr(checkpoint, "_put_rows", spy)
    before = {id(a) for a in jax.live_arrays()}
    placed = device_rows(host, cfg.num_rows)
    assert starts == [0, 1000, 2000, 3000, 4000, 4001]
    assert placed.shape == (cfg.num_rows, cfg.row_dim)
    assert (np.asarray(placed) == host[:cfg.num_rows]).all()
    new = [a for a in jax.live_arrays() if id(a) not in before
           and a.nbytes >= placed.nbytes]
    assert [id(a) for a in new] == [id(placed)]     # the table, once


# ---- the contract on disk, across topologies --------------------------------

def _mesh4():
    from fast_tffm_tpu.parallel.sharded import make_mesh
    return make_mesh(jax.devices()[:4])


def test_a_step_saved_on_one_device_restores_on_a_mesh_and_back(
        tmp_path, small_blocks):
    cfg = _cfg(tmp_path)
    table, acc = _state(cfg)
    ckpt = CheckpointState(cfg.model_file)
    ckpt.save(7, *ckpt_state(cfg, table, acc),
              vocabulary_size=cfg.vocabulary_size, wait=True, epoch=1)
    mesh = _mesh4()
    on_mesh = ckpt.restore(template=checkpoint_template(cfg, mesh))
    for name, arr, tail in (("table", table, 0.0),
                            ("acc", acc, cfg.adagrad_init)):
        got = on_mesh[name]
        assert got.shape == (cfg.ckpt_rows, cfg.row_dim)
        assert len(got.sharding.device_set) == 4
        assert (np.asarray(got)[:cfg.num_rows] == np.asarray(arr)).all()
        assert (np.asarray(got)[cfg.num_rows:] == np.float32(tail)).all()
    # ... and a step the mesh saves (its arrays are the contract's
    # shape: handed to orbax as they are) comes back to one device
    state = ckpt_state(cfg, on_mesh["table"], on_mesh["acc"])
    assert state[0] is on_mesh["table"]
    ckpt.save(9, *state, vocabulary_size=cfg.vocabulary_size, wait=True,
              epoch=2)
    back = ckpt.restore(step=9, template=checkpoint_template(cfg, host=True))
    ckpt.close()
    assert type(back["table"]) is np.ndarray and int(back["epoch"]) == 2
    t = device_rows(back["table"], cfg.num_rows)
    a = device_rows(back["acc"], cfg.num_rows)
    assert (np.asarray(t).view(np.uint32)
            == np.asarray(table).view(np.uint32)).all()
    assert (np.asarray(a).view(np.uint32)
            == np.asarray(acc).view(np.uint32)).all()


# ---- a job that saves, is killed and comes back -----------------------------

def _corpus(tmp_path):
    rng = np.random.default_rng(3)
    lines = [f"{int(rng.integers(0, 2))} {int(rng.integers(0, 50))}:1.0 "
             f"{int(rng.integers(0, 50))}:0.5" for _ in range(N_LINES)]
    data = tmp_path / "train.txt"
    data.write_text("\n".join(lines) + "\n")
    return str(data)


def _job(tmp_path, **kw):
    base = dict(vocabulary_size=50, factor_num=2, batch_size=BATCH,
                epoch_num=4, shuffle=False, log_steps=0,
                train_files=(_corpus(tmp_path),),
                metrics_file=str(tmp_path / "metrics.jsonl"),
                model_file=str(tmp_path / "model" / "fm"))
    base.update(kw)
    return FmConfig(**base)


def _counters(path):
    snaps = []
    with open(path) as fh:
        for line in fh:
            e = json.loads(line)
            if e.get("event") == "metrics" and "counters" in e:
                snaps.append(e["counters"])
    return snaps[-1]


class Killed(Exception):
    pass


def _kill_after(monkeypatch, n):
    """The process dies after its ``n``-th step: no exit save is made."""
    from fast_tffm_tpu.utils.timing import StepTimer
    real, state = StepTimer.tick, {"steps": 0}

    def tick(self, n_examples):
        real(self, n_examples)
        state["steps"] += 1
        if state["steps"] == n:
            raise Killed()
    monkeypatch.setattr(StepTimer, "tick", tick)
    return lambda: monkeypatch.setattr(StepTimer, "tick", real)


def test_a_periodic_save_a_kill_and_a_resume_go_on_with_the_schedule(
        tmp_path, one_device, monkeypatch):
    """Saves every 10 steps, epochs of 15; killed after step 23: the
    newest step on disk is 20, of epoch index 1, with one epoch
    complete. The job that comes back restores it, begins at epoch 1
    (``resume_start_epoch``) and runs the three epochs that are left."""
    cfg = _job(tmp_path, save_steps=10)
    revive = _kill_after(monkeypatch, 23)
    with pytest.raises(Killed):
        train(cfg)
    revive()
    first = _counters(cfg.metrics_file)
    assert first["checkpoint/saves"] == 2           # steps 10 and 20
    ckpt = CheckpointState(cfg.model_file)
    assert ckpt.latest_step() == 20
    at_kill = ckpt.restore(template=checkpoint_template(cfg, host=True))
    ckpt.close()
    assert (int(at_kill["step"]), int(at_kill["epoch"])) == (20, 1)
    assert resume_start_epoch(1, cfg.epoch_num) == 1
    cfg2 = _job(tmp_path, save_steps=10,
                metrics_file=str(tmp_path / "metrics2.jsonl"))
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())
    keep = Keep(level=logging.INFO)
    logging.getLogger("fast_tffm_tpu").addHandler(keep)
    try:
        table = train(cfg2)
    finally:
        logging.getLogger("fast_tffm_tpu").removeHandler(keep)
    said = "\n".join(lines)
    assert "restored checkpoint at step 20" in said
    assert "resuming interrupted epoch schedule at epoch 1/4" in said
    ckpt = CheckpointState(cfg.model_file)
    final = ckpt.restore(template=checkpoint_template(cfg, host=True))
    ckpt.close()
    assert int(final["step"]) == 20 + 3 * STEPS_PER_EPOCH
    assert int(final["epoch"]) == cfg.epoch_num
    assert table.shape == (cfg.num_rows, cfg.row_dim)
    assert (np.asarray(table) == final["table"][:cfg.num_rows]).all()
    # the second job's own saves: steps 30, 40, 50, 60 and the exit's 65
    second = _counters(cfg2.metrics_file)
    assert second["checkpoint/saves"] == 5
    assert second["checkpoint/restore_bytes"] == 2 * 4096 * 3 * 4
    assert second["checkpoint/restore_seconds"] > 0
    assert second["checkpoint/place_seconds"] > 0


def test_a_resumed_job_trains_as_the_unbroken_one(tmp_path, one_device,
                                                  monkeypatch):
    """Killed ON a saved step and brought back, the job ends with the
    bits of one that was never killed: what the save wrote was the
    state of its step and what the resume placed was what it wrote."""
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    whole = train(_job(tmp_path / "a", epoch_num=2, save_steps=15,
                       metrics_file=""))
    cfg = _job(tmp_path / "b", epoch_num=2, save_steps=15, metrics_file="")
    revive = _kill_after(monkeypatch, 15)
    with pytest.raises(Killed):
        train(cfg)
    revive()
    resumed = train(cfg)
    assert (np.asarray(resumed).view(np.uint32)
            == np.asarray(whole).view(np.uint32)).all()


# ---- the counters -----------------------------------------------------------

def test_the_saves_phases_count(tmp_path, one_device):
    cfg = _job(tmp_path, save_steps=10, epoch_num=2)
    train(cfg)
    c = _counters(cfg.metrics_file)
    # steps 10, 20, 30 and the exit save, which lands on step 30 again
    assert c["checkpoint/saves"] == 3
    state_bytes = 2 * 4096 * 3 * 4
    assert c["checkpoint/snapshot_bytes"] == 3 * state_bytes
    assert c["checkpoint/committed_bytes"] == 3 * state_bytes
    for name in ("checkpoint/save_seconds", "checkpoint/snapshot_seconds",
                 "checkpoint/commit_seconds",
                 "train/checkpoint_pause_seconds"):
        assert c[name] > 0, name
    assert c["checkpoint/settle_seconds"] >= 0
    # the phases nest: settle and snapshot inside the pause or the save
    assert c["checkpoint/snapshot_seconds"] <= (
        c["train/checkpoint_pause_seconds"] + c["checkpoint/save_seconds"])
    assert "checkpoint/restore_seconds" in c       # a fresh start: no bytes
    assert c.get("checkpoint/restore_bytes", 0) == 0


def test_the_plan_says_what_a_saver_keeps_on_the_host(tmp_path):
    from fast_tffm_tpu.obs import memory
    cfg = _cfg(tmp_path, save_steps=100)
    plan = memory.plan(cfg, "train")
    assert plan["host_owners"]["ckpt_snapshot"] == 2 * 8192 * 5 * 4
    assert "ckpt_snapshot" not in plan["owners"]
    assert plan["total_bytes"] == memory.plan(
        _cfg(tmp_path), "train")["total_bytes"]     # nothing on the device
    assert "ckpt_snapshot" not in memory.plan(_cfg(tmp_path), "train")[
        "host_owners"]
    assert "ckpt_snapshot" not in memory.plan(cfg, "train", shards=4)[
        "host_owners"]
