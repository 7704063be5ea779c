"""train.py's SIGTERM/SIGINT preemption path, exercised by REAL signal
delivery (testing/faults.preempt_after_steps raises the signal
in-process at a deterministic step): durable final save, epoch
metadata round-tripping through restore/resume_start_epoch, handler
teardown, and the PREEMPTED health verdict."""

import signal

import pytest

from fast_tffm_tpu.checkpoint import CheckpointState
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.testing.faults import preempt_after_steps
from fast_tffm_tpu.checkpoint import checkpoint_template, resume_start_epoch
from fast_tffm_tpu.train import train

N_LINES = 240
BATCH = 16
STEPS_PER_EPOCH = N_LINES // BATCH  # 15


def _cfg(tmp_path, **overrides):
    import numpy as np
    rng = np.random.default_rng(3)
    lines = []
    for _ in range(N_LINES):
        y = int(rng.integers(0, 2))
        lines.append(f"{y} {int(rng.integers(0, 50))}:1.0 "
                     f"{int(rng.integers(0, 50))}:0.5")
    data = tmp_path / "train.txt"
    data.write_text("\n".join(lines) + "\n")
    base = dict(vocabulary_size=50, factor_num=2, batch_size=BATCH,
                epoch_num=4, shuffle=False, log_steps=0,
                train_files=(str(data),),
                model_file=str(tmp_path / "model" / "fm"))
    base.update(overrides)
    return FmConfig(**base)


@pytest.mark.parametrize("sig", [signal.SIGTERM, signal.SIGINT])
def test_preemption_saves_durably_and_resumes(tmp_path, sig):
    cfg = _cfg(tmp_path)
    prev = signal.getsignal(sig)
    # Fire mid-epoch 1 (steps 16..30 belong to epoch index 1).
    with preempt_after_steps(STEPS_PER_EPOCH + 3, sig=sig) as state:
        train(cfg)
    assert state["fired"]
    # Handlers must be restored: a later real signal must not land in
    # train()'s dead flag list.
    assert signal.getsignal(sig) is prev

    ckpt = CheckpointState(cfg.model_file)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    assert restored is not None, "preemption save never landed"
    step = int(restored["step"])
    epoch = int(restored["epoch"])
    # The save is cut mid-schedule: exactly 1 completed epoch, and the
    # step counter reflects the interrupted position (the signal lands
    # at tick N; the loop drains it at the next step boundary).
    assert epoch == 1
    assert STEPS_PER_EPOCH < step <= STEPS_PER_EPOCH + 4
    # resume_start_epoch round-trip: the restart begins at the first
    # incomplete epoch, not zero and not done.
    assert resume_start_epoch(epoch, cfg.epoch_num) == 1

    # The restarted run completes the remaining schedule.
    train(cfg)
    ckpt = CheckpointState(cfg.model_file)
    final = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    assert int(final["epoch"]) == cfg.epoch_num
    assert int(final["step"]) >= 4 * STEPS_PER_EPOCH - 1


def test_preempted_health_event_and_fmstat_verdict(tmp_path, capsys):
    metrics = str(tmp_path / "m.jsonl")
    cfg = _cfg(tmp_path, metrics_file=metrics, metrics_flush_steps=5)
    with preempt_after_steps(STEPS_PER_EPOCH + 2):
        train(cfg)
    from fast_tffm_tpu.obs.attribution import health_verdict, summarize
    summary = summarize([metrics])
    hv = health_verdict(summary)
    assert hv["verdict"] == "PREEMPTED", hv
    assert "resume" in hv["detail"]
    # A clean preemption is not a crash: run_end was written.
    assert summary["run_ends"] == summary["run_starts"]

    # fmstat surfaces it in both text and --json modes.
    from tools.fmstat import main as fmstat_main
    assert fmstat_main([metrics]) == 0
    assert "health: PREEMPTED" in capsys.readouterr().out
    import json
    assert fmstat_main(["--json", metrics]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["health"]["verdict"] == "PREEMPTED"


@pytest.mark.slow
def test_multiworker_sigterm_coordinates_group_stop(tmp_path):
    """ISSUE 6 satellite: a SIGTERM delivered to ONE worker of a
    lockstep group must stop, save, and exit EVERY worker at the same
    boundary — the flag rides the per-step and per-window (validation)
    allgathers, so the un-signalled worker sees it in the same
    gathered result instead of desyncing when its peer bails."""
    import os
    import signal
    import socket
    import subprocess
    import sys
    import time

    import numpy as np

    from fast_tffm_tpu.testing.faults import committed_steps, wait_until

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rng = np.random.default_rng(5)
    lines = []
    for _ in range(1600):
        nnz = rng.integers(2, 8)
        ids = rng.choice(50, size=nnz, replace=False)
        lines.append(" ".join(["1" if rng.random() < 0.5 else "0"]
                              + [f"{i}:{rng.random():.3f}" for i in ids]))
    data = tmp_path / "train.txt"
    data.write_text("\n".join(lines) + "\n")
    model = tmp_path / "model" / "fm"
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = s.getsockname()[1]
    cfg = tmp_path / "dist.cfg"
    cfg.write_text(f"""
[General]
vocabulary_size = 50
factor_num = 2
model_file = {model}

[Train]
train_files = {data}
validation_files = {data}
epoch_num = 40
batch_size = 32
learning_rate = 0.1
shuffle = False
log_steps = 0
save_steps = 10
metrics_file = {tmp_path}/metrics.jsonl
metrics_flush_steps = 2

[Cluster]
worker_hosts = localhost:{coord - 1000},localhost:{coord - 999}
heartbeat_seconds = 1.0
collective_timeout_seconds = 60
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    outs = [open(tmp_path / f"w{i}.out", "w") for i in range(2)]
    procs = [subprocess.Popen(
        [sys.executable, "run_tffm.py", "train", str(cfg),
         "dist_train", "worker", str(i)],
        cwd=repo, env=env, stdout=outs[i], stderr=subprocess.STDOUT)
        for i in range(2)]
    try:
        # SIGTERM the NON-chief once the group is demonstrably
        # stepping in lockstep (a committed checkpoint step).
        wait_until(lambda: len(committed_steps(str(model))) >= 1,
                   timeout=240, message="first committed step")
        procs[1].send_signal(signal.SIGTERM)
        deadline = time.time() + 240
        while (any(p.poll() is None for p in procs)
               and time.time() < deadline):
            time.sleep(0.5)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=60)
        for fh in outs:
            fh.close()
    texts = [(tmp_path / f"w{i}.out").read_text() for i in range(2)]
    for i, text in enumerate(texts):
        assert procs[i].returncode == 0, f"worker {i}:\n{text[-2000:]}"
        # BOTH workers take the coordinated save-and-exit path, not
        # just the one that received the signal.
        assert "preemption signalled; saving and exiting" in text, (
            f"worker {i} missed the group stop:\n{text[-2000:]}")
        assert "training done" in text
    # the preemption save is durable and carries a mid-schedule epoch
    restored = CheckpointState(str(model)).restore(
        template=checkpoint_template(load_cfg_for(model, data)))
    assert restored is not None
    assert 0 <= int(restored["epoch"]) < 40
    # fmstat over both shards reads PREEMPTED (a clean exit), never
    # CRASHED/DEGRADED
    from fast_tffm_tpu.obs.attribution import health_verdict, summarize
    shards = [str(tmp_path / "metrics.jsonl")]
    p1 = str(tmp_path / "metrics.jsonl.p1")
    import os.path
    if os.path.exists(p1):
        shards.append(p1)
    assert health_verdict(summarize(shards))["verdict"] == "PREEMPTED"


def load_cfg_for(model, data):
    return FmConfig(vocabulary_size=50, factor_num=2, batch_size=32,
                    epoch_num=40, train_files=(str(data),),
                    model_file=str(model))


def test_second_signal_during_save_window_is_absorbed(tmp_path):
    """Handlers stay installed until the final save is on disk; a
    signal raised by the test right after train() returns must hit the
    ORIGINAL disposition (restored), while signals during the run are
    absorbed into the flag list."""
    cfg = _cfg(tmp_path, epoch_num=2)
    seen = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    try:
        with preempt_after_steps(3):
            train(cfg)
        assert seen == []  # train's handler owned the signal
        signal.raise_signal(signal.SIGTERM)
        assert seen == [signal.SIGTERM]  # ours is back
    finally:
        signal.signal(signal.SIGTERM, prev)
