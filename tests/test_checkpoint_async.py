"""Async checkpointing: periodic saves must not stall the train loop for
the full serialization (round-2 review, item 7); final saves barrier."""

import time

import numpy as np

from fast_tffm_tpu.checkpoint import CheckpointState
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.models.fm import init_accumulator, init_table
from fast_tffm_tpu.checkpoint import checkpoint_template, ckpt_state
from fast_tffm_tpu.train import train


def test_async_save_returns_before_commit_and_restores(tmp_path):
    cfg = FmConfig(vocabulary_size=200_000, factor_num=8,
                   model_file=str(tmp_path / "m" / "fm"))
    table, acc = ckpt_state(cfg, init_table(cfg), init_accumulator(cfg))
    ckpt = CheckpointState(cfg.model_file)

    t0 = time.perf_counter()
    ckpt.save(1, table, acc, vocabulary_size=cfg.vocabulary_size)
    t_async = time.perf_counter() - t0
    ckpt.wait_until_finished()

    t0 = time.perf_counter()
    ckpt.save(2, table, acc, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    t_sync = time.perf_counter() - t0
    # The async call skips the serialization wait; it must be visibly
    # cheaper than the full committed write of the same ~13 MB state.
    assert t_async < t_sync, (t_async, t_sync)

    restored = ckpt.restore(template=checkpoint_template(cfg))
    assert int(restored["step"]) == 2
    np.testing.assert_array_equal(np.asarray(restored["table"]),
                                  np.asarray(table))
    ckpt.close()


def test_save_every_step_train_is_resumable(tmp_path, rng):
    """save_steps=1: every step issues an async save; the run must end
    with a committed, restorable checkpoint at the final step."""
    from tests.test_e2e import make_dataset
    make_dataset(tmp_path / "train.txt", 96, rng)
    cfg = FmConfig(vocabulary_size=200, factor_num=4, batch_size=32,
                   epoch_num=1, save_steps=1, shuffle=False,
                   train_files=(str(tmp_path / "train.txt"),),
                   model_file=str(tmp_path / "m" / "fm"))
    train(cfg)
    ckpt = CheckpointState(cfg.model_file)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    assert int(restored["step"]) == 3  # 96 examples / batch 32
    assert np.isfinite(np.asarray(restored["table"])).all()


def test_same_step_resave_updates_stale_epoch(tmp_path):
    """The final save landing on the last periodic save's step must not
    silently keep that save's MID-epoch metadata: a completed run would
    restore as 'interrupted' and retrain an epoch (review finding).
    Identical metadata stays a cheap no-op."""
    cfg = FmConfig(vocabulary_size=1000, factor_num=4,
                   model_file=str(tmp_path / "m" / "fm"))
    table, acc = ckpt_state(cfg, init_table(cfg), init_accumulator(cfg))
    ckpt = CheckpointState(cfg.model_file)
    # "Periodic" save mid-final-epoch: 7 completed of 8.
    ckpt.save(40, table, acc, vocabulary_size=cfg.vocabulary_size,
              wait=True, epoch=7)
    # "Final" save, same step, schedule now complete; the caller flags
    # the known-stale collision (train() derives this deterministically
    # from its own last periodic save).
    ckpt.save(40, table, acc, vocabulary_size=cfg.vocabulary_size,
              force=True, wait=True, epoch=8,
              rewrite_stale_metadata=True)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    assert int(restored["epoch"]) == 8
    assert int(restored["step"]) == 40
    ckpt.close()


def test_epoch_sidecar_pruned_and_not_leaked(tmp_path):
    """The stale-epoch correction is a sidecar file, not a delete+resave
    (advisor r4: a hard kill in that window lost the newest step). It
    must (a) survive restore, (b) be dropped by a FRESH save at the same
    step (cleared-and-reused dir), (c) not accumulate once its step is
    GC'd."""
    import os
    cfg = FmConfig(vocabulary_size=1000, factor_num=4,
                   model_file=str(tmp_path / "m" / "fm"))
    table, acc = ckpt_state(cfg, init_table(cfg), init_accumulator(cfg))
    ckpt = CheckpointState(cfg.model_file, max_to_keep=2)
    ckpt.save(10, table, acc, vocabulary_size=cfg.vocabulary_size,
              wait=True, epoch=1)
    ckpt.save(10, table, acc, vocabulary_size=cfg.vocabulary_size,
              force=True, wait=True, epoch=2,
              rewrite_stale_metadata=True)
    sc = ckpt._epoch_sidecar(10)
    assert os.path.exists(sc)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    assert int(restored["epoch"]) == 2
    # steps 20, 30 push step 10 out of max_to_keep=2 -> sidecar pruned
    ckpt.save(20, table, acc, vocabulary_size=cfg.vocabulary_size,
              wait=True, epoch=3)
    ckpt.save(30, table, acc, vocabulary_size=cfg.vocabulary_size,
              wait=True, epoch=4)
    assert not os.path.exists(sc)
    ckpt.close()
    # cleared-and-reused dir: a stray sidecar must not overlay a fresh
    # same-step save's metadata
    ckpt2 = CheckpointState(cfg.model_file)
    with open(ckpt2._epoch_sidecar(40), "w") as fh:
        fh.write("99")
    ckpt2.save(40, table, acc, vocabulary_size=cfg.vocabulary_size,
               wait=True, epoch=5)
    restored = ckpt2.restore(template=checkpoint_template(cfg))
    assert int(restored["epoch"]) == 5
    ckpt2.close()


def test_sigkill_mid_async_save_restores_latest_complete(tmp_path, rng):
    """Crash-inject the async save path: SIGKILL a training process
    while saves are in flight (save_steps=1, ~23 MB state widens the
    write window), then require (a) restore finds a complete step —
    orbax's tmp-dir + atomic-commit protocol must hide any partially
    written step the kill left behind — and (b) a resumed run finishes.
    The resume story assumed this atomicity held under kill -9; this
    pins it (round-4 review item 6)."""
    import os
    import signal
    import subprocess
    import sys
    import time

    from tests.test_e2e import make_dataset
    make_dataset(tmp_path / "train.txt", 2000, rng, vocab=500)
    model = tmp_path / "m" / "fm"
    cfg_path = tmp_path / "kill.cfg"
    cfg_path.write_text(f"""
[General]
vocabulary_size = 300000
factor_num = 8
model_file = {model}

[Train]
train_files = {tmp_path / 'train.txt'}
epoch_num = 50
batch_size = 32
learning_rate = 0.1
shuffle = False
save_steps = 1
log_steps = 0
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "run_tffm.py", "train", str(cfg_path)],
        cwd=repo, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    ckpt_dir = str(model) + ".ckpt"
    try:
        # Kill the instant a later step starts appearing: step N's async
        # write is then likely mid-flight. Generous deadline: the child
        # pays interpreter + jax + jit-compile startup (~25 s idle, a
        # multiple of that when the 1-core host is already loaded —
        # observed flaking at 120 s under a concurrent suite).
        deadline = time.time() + 300
        while time.time() < deadline:
            steps = [d for d in (os.listdir(ckpt_dir)
                                 if os.path.isdir(ckpt_dir) else [])
                     if d.isdigit()]
            if len(steps) >= 3:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("child never wrote 3 checkpoint steps")
        proc.send_signal(signal.SIGKILL)
    finally:
        if proc.poll() is None:  # assertion path: don't leak the child
            proc.kill()
        proc.wait(timeout=60)
    assert proc.returncode == -signal.SIGKILL

    from fast_tffm_tpu.config import load_config
    cfg = load_config(str(cfg_path))
    cfg = type(cfg)(**{**cfg.__dict__, "epoch_num": 1})
    ckpt = CheckpointState(cfg.model_file)
    s = ckpt.latest_step()
    assert s is not None, "no complete step visible after SIGKILL"
    restored = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    assert int(restored["step"]) == s
    table = np.asarray(restored["table"])
    assert np.isfinite(table).all() and np.abs(table).max() > 0
    # the resumed run restores and completes its (already-satisfied or
    # remaining) schedule without tripping on leftover tmp dirs
    from fast_tffm_tpu.train import train
    train(cfg)
    ckpt2 = CheckpointState(cfg.model_file)
    assert ckpt2.latest_step() >= s
    ckpt2.close()


def test_legacy_checkpoint_without_epoch_leaf_restores(tmp_path):
    """Checkpoints written before the 'epoch' leaf existed must still
    restore (default 0 = no interrupted schedule): an upgraded binary
    has to resume a preempted job's old checkpoint."""
    import jax
    import orbax.checkpoint as ocp
    cfg = FmConfig(vocabulary_size=1000, factor_num=4,
                   model_file=str(tmp_path / "m" / "fm"))
    table, acc = ckpt_state(cfg, init_table(cfg), init_accumulator(cfg))
    import os
    path = cfg.model_file + ".ckpt"
    os.makedirs(path, exist_ok=True)
    mngr = ocp.CheckpointManager(path)
    # Plain ints for the scalar leaves (ISSUE 3 triage): the installed
    # orbax's StandardSave rejects numpy scalars outright, and the
    # legacy property under test is the MISSING 'epoch' leaf, not the
    # scalar dtype the old writer happened to use.
    mngr.save(7, args=ocp.args.StandardSave(
        {"table": np.asarray(table), "acc": np.asarray(acc),
         "step": 7, "vocab": int(cfg.vocabulary_size)}))
    mngr.wait_until_finished()
    mngr.close()
    ckpt = CheckpointState(cfg.model_file)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    assert int(restored["step"]) == 7
    assert int(restored["epoch"]) == 0  # defaulted, not an error
