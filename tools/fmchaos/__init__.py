"""fmchaos — end-to-end fault-injection soak scenarios for the data
plane (README "Fault tolerance").

    python -m tools.fmchaos               # run every scenario
    python -m tools.fmchaos skip preempt-resume
    python -m tools.fmchaos --list
    make chaos                            # the CI target (CPU)

Each scenario builds a tiny synthetic corpus, runs a REAL training job
through ``fast_tffm_tpu.train.train`` under one injected fault
(``fast_tffm_tpu/testing/faults.py`` — all deterministic/seeded), and
asserts the documented recovery behavior:

- ``skip``            0.5% corrupt lines + ``bad_line_policy = skip``
                      → trains to completion; the skip count equals
                      the injected corruption exactly.
- ``quarantine``      same corpus, 2 epochs → quarantine sidecar holds
                      each bad line ONCE (file/lineno/raw), while the
                      skip counter counts both epochs' passes.
- ``max-bad``         10% corruption trips the ``max_bad_fraction``
                      breaker → the run aborts naming the worst file.
- ``flaky-open``      the first 2 opens of the train file raise EIO →
                      the retry/backoff layer absorbs them; retry
                      counters land in the metrics stream.
- ``flaky-open-parallel`` the same transient-open fault soaked under
                      ``host_threads = 4`` (the parallel data plane):
                      retries absorb identically, the run's metrics
                      prove the worker pool actually ran, AND a
                      10%-corrupt quarantine run through the parallel
                      plane trips the ``max_bad_fraction`` breaker
                      exactly once naming the worst file — with no
                      ``fm-build`` worker threads leaked after the
                      abort.
- ``serve-soak``      the online serving subsystem under concurrency
                      and a hot reload: 4 client threads fire
                      variable-size requests at a live ScorerServer
                      while `fmckpt publish` repoints the pointer →
                      responses land on BOTH steps, every one
                      bit-identical to batch predict against the step
                      that scored it, fmstat's SERVING section shows
                      the p50/p99 latency histograms with served ==
                      published at close, and no fm-serve thread
                      survives close().
- ``kill-replica-midburst`` the serving FLEET under fire (README
                      "Serving fleet"): 3 supervised replica processes
                      behind the failover proxy take a 4-thread
                      request burst while one replica is SIGKILLed
                      mid-flush → ZERO client-visible failures (the
                      proxy retries on a different ready replica),
                      every response bit-identical to batch predict
                      per its step tag, a mid-incident fmstat
                      snapshot reads FLEET DEGRADED (2/3 ready), the
                      supervisor respawns the victim under backoff
                      back to OK, and client p99 holds the [SLO]
                      bound.
- ``staggered-reload`` a fleet-wide hot reload under load: `fmckpt
                      publish` repoints the pointer while clients
                      fire through the proxy → the supervisor
                      staggers the reload so a high-rate sampler on
                      the proxy's /healthz NEVER sees ready == 0,
                      responses land on both steps, and none is torn
                      (byte parity against batch predict per step).
- ``preempt-resume``  SIGTERM mid-epoch → the run saves and exits
                      cleanly, ``fmstat`` reports PREEMPTED (not
                      CRASHED); a restart resumes the interrupted
                      epoch schedule and finishes OK.
- ``stream-soak``     run_mode = stream against a LIVE writer
                      injecting torn writes, plus flaky opens and one
                      mid-stream SIGTERM+resume → every sealed line is
                      consumed exactly once (final table BIT-IDENTICAL
                      to a clean single-pass control run over the same
                      sealed corpus) and >= 2 ``published`` pointer
                      flips land on manifest-verified steps.
- ``slo-soak``        the FULL closed loop under SLOs (README "SLOs &
                      quality gate"): a live writer feeds the stream,
                      a gated trainer (``publish_min_auc``) publishes
                      on interval, and a ScorerServer serves a
                      concurrent request load against the moving
                      pointer; a label-flipped poison burst must be
                      caught by the publish gate (pointer pinned to
                      the last good step, ``health: gate_held``,
                      fmstat GATE-HELD, serving uninterrupted), clean
                      data heals the loop, and at the end every
                      declared SLO passes: publish staleness, serve
                      p99, exactly-once consumption, min AUC, and
                      per-step bit-parity of every response against
                      an offline predict control snapshot.
- ``stream-truncate`` an in-progress (unsealed) stream file SHRINKS
                      under the reader → the (inode, size) regression
                      is quarantined through the BadLineTracker, the
                      run survives and finishes the successor shard,
                      breaker accounting exact.
- ``vocab-churn``     unbounded-vocabulary admission under stream
                      churn (``vocab_mode = admit``): a heavy-tailed
                      hashed-id stream (distinct ids >= 10x the
                      physical table) through a mid-run SIGTERM and a
                      checkpoint walk-back → admission state
                      round-trips bit-exactly, the slot map stays
                      bounded at vocabulary_size rows, cold-gone hot
                      ids are EVICTED at barriers, and the published
                      step serves evicted ids from the shared cold
                      row (bit-identical to a never-seen id), never
                      their stale embeddings.
- ``truncate-latest`` the newest checkpoint step is torn (truncated
                      array file) → with ``ckpt_verify = size`` the
                      restart quarantines it (``corrupt-<step>``,
                      never deleted), resumes from the previous step
                      with the correct epoch, emits
                      ``health: ckpt_fallback``, ``fmstat`` reports
                      ``OK (ckpt fallback x1)`` — and trains to the
                      SAME final table as a clean resume from that
                      step.
- ``kill-async-save`` SIGKILL a real training child mid-async-save
                      burst → the restart restores a committed step
                      cleanly (verified restore; orbax's atomic commit
                      plus the manifest check hide/catch any torn
                      state) and completes OK.
- ``kill-worker-midwindow`` SIGKILL one of 2 lockstep workers mid-run.
                      With ``elastic = shrink`` the survivor raises
                      the worker_lost diagnosis naming the dead
                      process within the collective deadline, reforms
                      a 1-worker cluster, restores the last verified
                      checkpoint, re-shards the input so every shard
                      of the recovered pass is consumed exactly once
                      (pinned by final step arithmetic), finishes the
                      schedule, and ``fmstat`` reports
                      ``DEGRADED (1 worker lost)``. With
                      ``elastic = off`` the survivor fails FAST with
                      the same named diagnosis instead of hanging.
- ``hang-worker``     SIGSTOP one of 2 lockstep workers: the deadline
                      guard expires, the diagnosis names the stopped
                      process (it stopped heartbeating without dying),
                      and the survivor exits with WorkerLostError —
                      never an indefinite hang.
- ``kill-then-grow``  the full elastic heal (``elastic = grow``): a
                      2-worker stream job loses worker 1 to SIGKILL,
                      the survivor shrinks and keeps training, a
                      ``run_tffm.py train <cfg> --join`` replacement
                      is admitted at the next publish settle, and the
                      run finishes at FULL membership — exactly-once
                      consumption summed across the dead worker's and
                      the joiner's metrics shards, final table
                      BIT-IDENTICAL to an uninterrupted 2-worker
                      control, fmstat RECOVERED (gen 2, 2 workers),
                      lease dir swept to current-generation files.
- ``grow-joiner-dies`` a joiner SIGKILLed mid-rendezvous (announced,
                      not yet committed) never wedges the incumbents:
                      the settle window expires, the dead joiner's
                      stale lease drops it, the reform commits
                      without it, and training finishes cleanly.
- ``predict-flaky``   the cross-file streaming scorer under faults:
                      flaky opens on the first predict file plus one
                      corrupt file mid-sweep with ``bad_line_policy =
                      quarantine`` → the sweep completes, every OTHER
                      file's scores are BIT-IDENTICAL to a fault-free
                      sweep, the corrupt file's score file stays
                      line-aligned (bad lines score as zero-feature
                      examples), the quarantine sidecar names each
                      injected line, and no writer/fetcher/build
                      threads leak.

The scenario functions are plain callables (workdir in, asserts
inside) so tests/test_chaos.py runs the same soaks under tier-1; the
CLI adds CPU forcing and PASS/FAIL reporting.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable, Dict, List

import numpy as np


def _write_corpus(path: str, n: int, seed: int,
                  vocab: int = 200, informative: int = 6) -> None:
    """Separable synthetic libsvm corpus (the e2e smoke shape): label-1
    examples prefer ids [0, informative), label-0 prefer the next
    block; a few noise features with float values exercise value
    parsing."""
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        y = int(rng.integers(0, 2))
        base = 0 if y else informative
        feats = {int(base + rng.integers(0, informative)): 1.0,
                 int(base + rng.integers(0, informative)): 1.0}
        for _ in range(3):
            feats[int(rng.integers(2 * informative, vocab))] = round(
                float(rng.uniform(0.5, 1.5)), 3)
        toks = " ".join(f"{i}:{v}" for i, v in sorted(feats.items()))
        lines.append(f"{y} {toks}")
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")


def _cfg(workdir: str, train_file: str, **overrides):
    from fast_tffm_tpu.config import FmConfig
    base = dict(
        vocabulary_size=200, factor_num=4, batch_size=32, epoch_num=1,
        learning_rate=0.1, shuffle=True, seed=0, log_steps=0,
        train_files=(train_file,),
        model_file=os.path.join(workdir, "model", "fm"),
        log_file=os.path.join(workdir, "chaos.log"),
        metrics_file=os.path.join(workdir, "metrics.jsonl"),
        metrics_flush_steps=5, io_backoff_seconds=0.01)
    base.update(overrides)
    return FmConfig(**base)


def _summary(cfg):
    from fast_tffm_tpu.obs.attribution import summarize
    return summarize([cfg.metrics_file])


def _counters(cfg) -> dict:
    return _summary(cfg).get("counters", {})


def _verdict(cfg) -> str:
    from fast_tffm_tpu.obs.attribution import health_verdict
    return health_verdict(_summary(cfg))["verdict"]


# --- scenarios -----------------------------------------------------------


def scenario_skip(workdir: str, seed: int = 0) -> str:
    """0.5% corrupt lines, policy=skip: completes; counts pin exactly."""
    from fast_tffm_tpu.testing.faults import corrupt_corpus
    from fast_tffm_tpu.train import train
    clean = os.path.join(workdir, "clean.txt")
    dirty = os.path.join(workdir, "train_skip.txt")
    _write_corpus(clean, 400, seed)
    bad = corrupt_corpus(clean, dirty, fraction=0.005, seed=seed)
    cfg = _cfg(workdir, dirty, bad_line_policy="skip")
    train(cfg)
    c = _counters(cfg)
    assert c.get("pipeline/bad_lines") == len(bad), (
        f"skip count {c.get('pipeline/bad_lines')} != injected "
        f"{len(bad)}")
    assert c.get("train/examples") == 400 - len(bad), (
        f"trained examples {c.get('train/examples')} != "
        f"{400 - len(bad)}")
    assert _verdict(cfg) == "OK", _verdict(cfg)
    return (f"skipped {len(bad)}/400 injected bad lines, trained "
            f"{int(c['train/examples'])} examples, verdict OK")


def scenario_quarantine(workdir: str, seed: int = 0) -> str:
    """Quarantine sidecar holds each injected bad line once (dedup
    across 2 epochs) with exact file/lineno/raw provenance."""
    from fast_tffm_tpu.testing.faults import corrupt_corpus
    from fast_tffm_tpu.train import train
    clean = os.path.join(workdir, "clean.txt")
    dirty = os.path.join(workdir, "train_quar.txt")
    _write_corpus(clean, 400, seed)
    bad = corrupt_corpus(clean, dirty, fraction=0.005, seed=seed)
    cfg = _cfg(workdir, dirty, bad_line_policy="quarantine",
               epoch_num=2)
    train(cfg)
    qpath = cfg.metrics_file + ".quarantine"
    with open(qpath) as fh:
        recs = [json.loads(ln) for ln in fh if ln.strip()]
    dirty_lines = open(dirty).read().splitlines()
    assert sorted(r["lineno"] for r in recs) == [i + 1 for i in bad], (
        f"quarantined linenos {sorted(r['lineno'] for r in recs)} != "
        f"injected {[i + 1 for i in bad]}")
    for r in recs:
        assert r["file"] == dirty
        assert r["raw"] == dirty_lines[r["lineno"] - 1]
        assert r["error"]
    c = _counters(cfg)
    assert c.get("pipeline/bad_lines") == 2 * len(bad)  # both epochs
    return (f"quarantined {len(recs)} line(s) once across 2 epochs "
            f"({int(c['pipeline/bad_lines'])} skips counted) to "
            f"{os.path.basename(qpath)}")


def scenario_max_bad(workdir: str, seed: int = 0) -> str:
    """10% corruption trips the breaker; the error names the file."""
    from fast_tffm_tpu.data.badlines import BadInputError
    from fast_tffm_tpu.testing.faults import corrupt_corpus
    from fast_tffm_tpu.train import train
    clean = os.path.join(workdir, "clean.txt")
    dirty = os.path.join(workdir, "train_rotten.txt")
    _write_corpus(clean, 400, seed)
    corrupt_corpus(clean, dirty, fraction=0.10, seed=seed)
    cfg = _cfg(workdir, dirty, bad_line_policy="skip")
    try:
        train(cfg)
    except BadInputError as e:
        assert dirty in str(e), f"breaker error must name the file: {e}"
        assert "max_bad_fraction" in str(e)
        return f"breaker tripped naming {os.path.basename(dirty)}"
    raise AssertionError("max_bad_fraction breaker never tripped on a "
                         "10%-corrupt corpus")


def scenario_flaky_open(workdir: str, seed: int = 0) -> str:
    """2 transient open failures on the train file: the run completes
    and the retries are visible in the metrics stream."""
    from fast_tffm_tpu.testing.faults import flaky_open
    from fast_tffm_tpu.train import train
    data = os.path.join(workdir, "train_flaky.txt")
    _write_corpus(data, 400, seed)
    cfg = _cfg(workdir, data, io_retries=3)
    with flaky_open(2, match="train_flaky.txt") as state:
        train(cfg)
    assert state["failures"] == 2, state
    c = _counters(cfg)
    assert c.get("io/retries", 0) >= 2, c.get("io/retries")
    assert _verdict(cfg) == "OK", _verdict(cfg)
    return (f"absorbed {state['failures']} injected open failures "
            f"({int(c['io/retries'])} retries in the metrics stream)")


def scenario_flaky_open_parallel(workdir: str, seed: int = 0) -> str:
    """The parallel host data plane under faults (host_threads=4):
    IO retry/backoff and the max_bad_fraction breaker must behave
    exactly as they do serially — retries absorbed, breaker trips
    ONCE naming the worst file — and an aborted run must not leak
    build-worker threads."""
    import threading
    from fast_tffm_tpu.data.badlines import BadInputError
    from fast_tffm_tpu.testing.faults import corrupt_corpus, flaky_open
    from fast_tffm_tpu.train import train

    def leaked_workers():
        return [t.name for t in threading.enumerate()
                if t.name.startswith("fm-build") and t.is_alive()]

    # Part 1: transient opens on the train file, absorbed by the
    # retry layer while the 4-worker plane is driving the reads.
    data = os.path.join(workdir, "train_flaky_par.txt")
    _write_corpus(data, 2000, seed)
    cfg = _cfg(workdir, data, io_retries=3, host_threads=4)
    with flaky_open(2, match="train_flaky_par.txt") as state:
        train(cfg)
    assert state["failures"] == 2, state
    c = _counters(cfg)
    assert c.get("io/retries", 0) >= 2, c.get("io/retries")
    # The pool really ran: per-worker build seconds only exist when
    # groups were built on fm-build threads.
    assert c.get("pipeline/worker_build_seconds", 0) > 0, c
    assert _verdict(cfg) == "OK", _verdict(cfg)
    assert not leaked_workers(), leaked_workers()

    # Part 2: the breaker through the PARALLEL quarantine plane — own
    # metrics file so the counters aren't folded into part 1's run.
    subdir = os.path.join(workdir, "breaker")
    os.makedirs(subdir, exist_ok=True)
    clean = os.path.join(subdir, "clean.txt")
    dirty = os.path.join(subdir, "train_rotten_par.txt")
    _write_corpus(clean, 2000, seed)
    corrupt_corpus(clean, dirty, fraction=0.10, seed=seed)
    cfg2 = _cfg(subdir, dirty, bad_line_policy="quarantine",
                host_threads=4)
    try:
        train(cfg2)
    except BadInputError as e:
        assert dirty in str(e), (
            f"breaker error must name the worst file: {e}")
        assert "max_bad_fraction" in str(e)
        assert str(e).count("aborting:") == 1, str(e)
    else:
        raise AssertionError("max_bad_fraction breaker never tripped "
                             "under the parallel plane")
    assert not leaked_workers(), leaked_workers()
    return ("parallel plane absorbed 2 injected open failures "
            f"({int(c['io/retries'])} retries), breaker tripped once "
            "naming the corrupt file, no fm-build threads leaked")


def scenario_preempt_resume(workdir: str, seed: int = 0) -> str:
    """SIGTERM mid-epoch: clean save-and-exit, fmstat says PREEMPTED;
    a restart resumes the interrupted schedule and finishes OK."""
    from fast_tffm_tpu.checkpoint import CheckpointState
    from fast_tffm_tpu.testing.faults import preempt_after_steps
    from fast_tffm_tpu.checkpoint import (checkpoint_template,
                                          resume_start_epoch)
    from fast_tffm_tpu.train import train
    data = os.path.join(workdir, "train_preempt.txt")
    _write_corpus(data, 400, seed)
    cfg = _cfg(workdir, data, epoch_num=3)
    # 400/32 -> 13 steps per epoch; step 16 is mid-epoch 1.
    with preempt_after_steps(16) as state:
        train(cfg)
    assert state["fired"], "SIGTERM injector never fired"
    assert _verdict(cfg) == "PREEMPTED", _verdict(cfg)
    ckpt = CheckpointState(cfg.model_file)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    epoch = int(restored["epoch"])
    assert 0 < epoch < cfg.epoch_num, (
        f"preemption checkpoint records {epoch} completed epochs; "
        f"expected mid-schedule (0 < e < {cfg.epoch_num})")
    assert resume_start_epoch(epoch, cfg.epoch_num) == epoch
    # Restart without the fault: resumes and completes the schedule.
    train(cfg)
    log = open(cfg.log_file).read()
    assert "resuming interrupted epoch schedule" in log
    assert _verdict(cfg) == "OK", _verdict(cfg)  # latest run segment
    ckpt = CheckpointState(cfg.model_file)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    assert int(restored["epoch"]) == cfg.epoch_num
    return (f"preempted at step {state['steps']} (epoch {epoch} "
            f"recorded), PREEMPTED verdict, resumed to "
            f"{cfg.epoch_num}/{cfg.epoch_num} epochs")


def scenario_truncate_latest(workdir: str, seed: int = 0) -> str:
    """Torn newest checkpoint (the acceptance scenario): with
    ``ckpt_verify = size`` the restart quarantines the truncated step,
    resumes from the previous step with the correct epoch, reports the
    fallback in fmstat — and trains to the SAME final table as a
    control twin that cleanly resumed from that previous step (the
    old by-hand remedy), so the healed run lost nothing but the torn
    step."""
    import shutil
    from fast_tffm_tpu.checkpoint import (CheckpointState,
                                          QUARANTINE_PREFIX,
                                          list_step_dirs, manifest_path)
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    from fast_tffm_tpu.checkpoint import checkpoint_template
    from fast_tffm_tpu.train import train
    workdir = os.path.abspath(workdir)
    data = os.path.join(workdir, "train_trunc.txt")
    _write_corpus(data, 400, seed)
    # Run 1: 400/32 -> 13 steps; periodic saves at 5 and 10, final 13.
    cfg = _cfg(workdir, data, save_steps=5)
    train(cfg)
    ckpt_dir = cfg.model_file + ".ckpt"
    steps = list_step_dirs(ckpt_dir)
    assert steps[-2:] == [10, 13], steps
    # Control twin BEFORE the fault: same run-1 state, newest step
    # removed CLEANLY (the manual remedy this PR automates), so its
    # resume starts from the same step the fallback should pick.
    control = os.path.join(workdir, "control")
    os.makedirs(control, exist_ok=True)
    shutil.copytree(os.path.join(workdir, "model"),
                    os.path.join(control, "model"))
    control_cfg = _cfg(control, data, epoch_num=2)
    control_ckpt_dir = control_cfg.model_file + ".ckpt"
    # fmlint: disable=R005 -- chaos control twin simulates the old
    # BY-HAND remedy (operator deletes the bad step) outside any run
    shutil.rmtree(os.path.join(control_ckpt_dir, "13"))
    for sidecar in (manifest_path(control_ckpt_dir, 13),
                    os.path.join(control_ckpt_dir, "epoch_override-13")):
        if os.path.exists(sidecar):
            # fmlint: disable=R005 -- part of the same simulated
            # by-hand cleanup in the control twin
            os.remove(sidecar)
    # The fault: tear the newest step's largest array file.
    victim = truncate_checkpoint(cfg.model_file, seed=seed)
    assert victim and f"{os.sep}13{os.sep}" in victim, victim
    # Run 2: restart onto the torn state; must self-heal.
    cfg2 = _cfg(workdir, data, epoch_num=2)
    table_fb = np.asarray(train(cfg2))
    log = open(cfg2.log_file).read()
    assert "restored checkpoint at step 10" in log, (
        "fallback run did not resume from the previous intact step")
    quarantined = [n for n in os.listdir(ckpt_dir)
                   if n.startswith(QUARANTINE_PREFIX)]
    assert quarantined == [f"{QUARANTINE_PREFIX}13"], quarantined
    assert 13 not in list_step_dirs(ckpt_dir)
    victim_rel = os.path.relpath(victim, os.path.join(ckpt_dir, "13"))
    assert os.path.exists(os.path.join(ckpt_dir, quarantined[0],
                                       victim_rel)), (
        "quarantine must preserve (not delete) the torn bytes")
    c = _counters(cfg2)
    assert c.get("checkpoint/fallbacks") == 1, c
    assert c.get("checkpoint/quarantined_steps") == 1, c
    assert c.get("checkpoint/saves", 0) >= 4, c
    v = _verdict(cfg2)
    assert v.startswith("OK (ckpt fallback x1"), v
    # Control twin: clean resume from step 10 over the same corpus.
    table_ctl = np.asarray(train(control_cfg))
    assert np.array_equal(table_fb, table_ctl), (
        "fallback resume diverged from a clean resume off the same "
        "step: max |delta| = "
        f"{np.abs(table_fb - table_ctl).max()}")
    # Both twins completed the 2-epoch schedule from step 10.
    ckpt = CheckpointState(cfg2.model_file)
    restored = ckpt.restore(template=checkpoint_template(cfg2))
    ckpt.close()
    assert int(restored["step"]) == 10 + 2 * 13
    assert int(restored["epoch"]) == 2
    return (f"quarantined torn step 13 -> {quarantined[0]}, resumed "
            f"from step 10, verdict {v!r}, final table identical to "
            "the clean-resume control")


def scenario_kill_async_save(workdir: str, seed: int = 0) -> str:
    """SIGKILL a real training child while async saves are in flight
    (save_steps=1, ~22 MB state widens the write window): the restart's
    VERIFIED restore must come up cleanly on a committed step — orbax's
    atomic commit hides torn step dirs, the manifest check catches
    anything that slipped through — and complete its schedule."""
    import signal
    import subprocess
    import sys
    import time as _time
    from fast_tffm_tpu.checkpoint import list_step_dirs
    workdir = os.path.abspath(workdir)
    data = os.path.join(workdir, "train_kill.txt")
    _write_corpus(data, 2000, seed)
    model = os.path.join(workdir, "model", "fm")
    cfg_path = os.path.join(workdir, "kill.cfg")
    with open(cfg_path, "w") as fh:
        fh.write(f"""
[General]
vocabulary_size = 300000
factor_num = 8
model_file = {model}

[Train]
train_files = {data}
epoch_num = 50
batch_size = 32
learning_rate = 0.1
shuffle = False
save_steps = 1
log_steps = 0
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    proc = subprocess.Popen(
        [sys.executable, "run_tffm.py", "train", cfg_path],
        cwd=repo, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    ckpt_dir = model + ".ckpt"
    try:
        # Kill once a second step commits: the NEXT async write is then
        # likely mid-flight. Generous deadline — the child pays
        # interpreter + jax + jit startup on a possibly loaded host.
        deadline = _time.time() + 300
        while _time.time() < deadline:
            if len(list_step_dirs(ckpt_dir)) >= 2:
                break
            _time.sleep(0.02)
        else:
            raise AssertionError(
                "child never committed 2 checkpoint steps")
        killed_at = max(list_step_dirs(ckpt_dir))
        proc.send_signal(signal.SIGKILL)
    finally:
        if proc.poll() is None:  # assertion path: don't leak the child
            proc.kill()
        proc.wait(timeout=60)
    assert proc.returncode == -signal.SIGKILL
    # Restart in-process with verified restore: must come up on a
    # committed step and finish one epoch.
    cfg = _cfg(workdir, data, vocabulary_size=300000, factor_num=8,
               shuffle=False)
    from fast_tffm_tpu.train import train
    train(cfg)
    final_steps = list_step_dirs(ckpt_dir)
    assert final_steps and final_steps[-1] > killed_at, (
        killed_at, final_steps)
    v = _verdict(cfg)
    assert v.startswith("OK"), v
    return (f"SIGKILLed child at committed step {killed_at}; restart "
            f"restored cleanly and finished at step {final_steps[-1]} "
            f"(verdict {v!r})")


def scenario_predict_flaky(workdir: str, seed: int = 0) -> str:
    """ISSUE 10: the cross-file streaming scorer under faults. One
    continuous sweep means one file's damage could in principle smear
    into its neighbors' batches — this pins that it doesn't: flaky
    opens + a quarantined-corrupt file mid-sweep leave every other
    file's scores bit-identical and line-aligned, and the sweep's
    writer/fetcher/build threads all exit."""
    import threading
    from fast_tffm_tpu.predict import predict
    from fast_tffm_tpu.testing.faults import corrupt_corpus, flaky_open
    from fast_tffm_tpu.train import train

    data = os.path.join(workdir, "train.txt")
    _write_corpus(data, 400, seed)
    cfg = _cfg(workdir, data)
    train(cfg)

    preds = []
    for i in range(3):
        p = os.path.join(workdir, f"pred{i}.txt")
        _write_corpus(p, 120, seed + 10 + i)
        preds.append(p)
    dirty_mid = os.path.join(workdir, "pred1_rotten.txt")
    bad = corrupt_corpus(preds[1], dirty_mid, fraction=0.05, seed=seed)
    assert bad, "corruption injection produced no bad lines"

    # Fault-free reference sweep over the same outer files.
    ref_cfg = dataclasses.replace(
        cfg, predict_files=tuple(preds),
        score_path=os.path.join(workdir, "score_ref"),
        metrics_file=os.path.join(workdir, "ref_metrics.jsonl"))
    predict(ref_cfg)

    # Faulted sweep: transient opens on file 0, the corrupt file in
    # the middle, quarantine policy, parallel host plane.
    flt_cfg = dataclasses.replace(
        cfg, predict_files=(preds[0], dirty_mid, preds[2]),
        score_path=os.path.join(workdir, "score_flaky"),
        metrics_file=os.path.join(workdir, "flaky_metrics.jsonl"),
        bad_line_policy="quarantine", io_retries=3, host_threads=4)
    with flaky_open(2, match="pred0.txt") as state:
        predict(flt_cfg)
    assert state["failures"] == 2, state

    def _score_text(cfg_, name):
        with open(os.path.join(cfg_.score_path, name + ".score")) as fh:
            return fh.read()

    # The files beside the damage: bit-identical to the clean sweep.
    for name in ("pred0.txt", "pred2.txt"):
        assert _score_text(flt_cfg, name) == _score_text(ref_cfg, name), (
            f"{name} scores diverged beside a corrupt neighbor")
    # The corrupt file itself: still one score per input line.
    n_scores = len(_score_text(flt_cfg,
                               "pred1_rotten.txt").splitlines())
    with open(dirty_mid) as fh:
        n_lines = sum(1 for _ in fh)
    assert n_scores == n_lines, (n_scores, n_lines)
    # Quarantine sidecar names each injected line of the corrupt file.
    with open(flt_cfg.metrics_file + ".quarantine") as fh:
        recs = [json.loads(ln) for ln in fh if ln.strip()]
    assert sorted(r["lineno"] for r in recs) == [i + 1 for i in bad], (
        f"quarantined {sorted(r['lineno'] for r in recs)} != injected "
        f"{[i + 1 for i in bad]}")
    assert all(r["file"] == dirty_mid for r in recs)
    c = _counters(flt_cfg)
    assert c.get("io/retries", 0) >= 2, c.get("io/retries")
    leaked = [t.name for t in threading.enumerate()
              if t.is_alive() and (t.name.startswith("fm-build")
                                   or t.name in ("fm-score-writer",
                                                 "fetcher"))]
    assert not leaked, leaked
    return (f"streaming sweep absorbed {state['failures']} flaky opens "
            f"+ quarantined {len(recs)} corrupt line(s) mid-sweep; "
            "neighbor scores bit-identical, alignment kept, no thread "
            "leaks")


def scenario_serve_soak(workdir: str, seed: int = 0) -> str:
    """ISSUE 11 acceptance: a long-lived scorer process serving
    CONCURRENT requests across at least one hot reload. Every response
    must be bit-identical to batch predict against the checkpoint step
    that scored it (responses are step-tagged), the reload is driven
    through the real pointer-watch loop by the `fmckpt publish`
    operator path, fmstat's SERVING section shows the p50/p99 latency
    histograms with no STALE MODEL, and no server/reload thread
    survives close()."""
    import dataclasses as dc
    import threading
    import time as _time
    from fast_tffm_tpu.checkpoint import (CheckpointState,
                                          list_step_dirs)
    from fast_tffm_tpu.metrics import sigmoid
    from fast_tffm_tpu.predict import load_table, predict_scores
    from fast_tffm_tpu.serve import ScoreClient, ScorerServer
    from fast_tffm_tpu.train import train
    from tools.fmckpt import cmd_publish

    data = os.path.join(workdir, "train.txt")
    _write_corpus(data, 400, seed)
    cfg = _cfg(workdir, data, epoch_num=2, save_steps=5,
               bucket_ladder=(8, 16), max_features_per_example=16,
               serve_max_batch=8, serve_max_wait_ms=2.0,
               serve_poll_seconds=0.02,
               metrics_file=os.path.join(workdir,
                                         "serve_metrics.jsonl"))
    train(dc.replace(cfg, metrics_file=""))
    ckpt = CheckpointState(cfg.model_file)
    steps = list_step_dirs(ckpt.directory)
    ckpt.close()
    assert len(steps) >= 2, f"need >= 2 retained steps, got {steps}"
    s_old, s_new = steps[0], steps[-1]
    # First publish through the operator CLI — the same path the
    # mid-soak repoint uses, so both flips exercise fmckpt publish.
    assert cmd_publish(cfg.model_file + ".ckpt", s_old) == 0

    server = ScorerServer(cfg)
    client = ScoreClient(server)
    req_lines = _corpus_lines(60, seed + 99)
    results = []   # (request lines, scores, step) — appended under lock
    res_lock = threading.Lock()
    errors = []
    stop_firing = threading.Event()

    def fire(worker: int) -> None:
        rng = np.random.default_rng(seed + worker)
        while not stop_firing.is_set():
            k = int(rng.integers(1, 6))
            lo = int(rng.integers(0, len(req_lines) - k))
            lines = req_lines[lo:lo + k]
            try:
                res = client.score(lines, timeout=30)
            except Exception as e:  # noqa: BLE001 - assert at the end
                errors.append(e)
                return
            with res_lock:
                results.append((lines, res.scores, res.step))

    threads = [threading.Thread(target=fire, args=(i,),
                                name=f"soak-client-{i}")
               for i in range(4)]
    for t in threads:
        t.start()
    # Let requests land on the OLD step, flip the pointer through the
    # operator CLI mid-fire, then keep firing until requests are
    # provably landing on the NEW step.
    deadline = _time.monotonic() + 30
    while not any(r[2] == s_old for r in list(results)):
        assert _time.monotonic() < deadline, "no old-step responses"
        _time.sleep(0.01)
    assert cmd_publish(cfg.model_file + ".ckpt", s_new) == 0
    while not any(r[2] == s_new for r in list(results)):
        assert _time.monotonic() < deadline, (
            f"hot reload to step {s_new} never served a request "
            f"(errors: {errors[:1]})")
        _time.sleep(0.01)
    stop_firing.set()
    for t in threads:
        t.join()
    assert not errors, errors[:3]
    server.close()

    leaked = [t.name for t in threading.enumerate()
              if t.is_alive() and t.name.startswith("fm-serve")]
    assert not leaked, f"leaked server threads: {leaked}"

    by_step = {}
    for _lines, _scores, step in results:
        by_step.setdefault(step, []).append((_lines, _scores))
    assert set(by_step) == {s_old, s_new}, (
        f"responses span steps {sorted(by_step)}, wanted "
        f"{[s_old, s_new]}")
    # Bit-identical parity per step: batch predict over the SAME lines
    # against the same published checkpoint must reproduce every
    # response byte for byte (the step tag says which table scored it).
    pcfg = dc.replace(cfg, metrics_file="")
    for step, pairs in sorted(by_step.items()):
        table = load_table(pcfg, step=step)
        req_path = os.path.join(workdir, f"requests_{step}.txt")
        flat, sizes = [], []
        for lines, _scores in pairs:
            flat.extend(lines)
            sizes.append(len(lines))
        with open(req_path, "w") as fh:
            fh.write("\n".join(flat) + "\n")
        want = sigmoid(predict_scores(pcfg, table, [req_path]))
        pos = 0
        for (lines, scores), n in zip(pairs, sizes):
            ref = want[pos:pos + n]
            pos += n
            assert np.array_equal(ref, scores), (
                f"step {step}: served scores diverged from batch "
                f"predict on the same checkpoint ({scores[:3]} vs "
                f"{ref[:3]})")
    # fmstat SERVING section: latency histograms visible, reload
    # counted, and the final flush shows served == published (no
    # STALE MODEL).
    from fast_tffm_tpu.obs.attribution import attribution, render
    summ = _summary(cfg)
    att = attribution(summ)
    assert att["serve_requests"] == len(results), (
        att["serve_requests"], len(results))
    assert att["serve_latency_p50_ms"] is not None
    assert att["serve_latency_p99_ms"] is not None
    assert att["serve_reloads"] >= 1
    assert att["serve_served_step"] == s_new
    text = render(summ)
    assert "SERVING" in text and "request latency p50 / p99" in text
    assert _verdict(cfg) == "OK", _verdict(cfg)
    n_old, n_new = len(by_step[s_old]), len(by_step[s_new])
    return (f"{len(results)} concurrent requests ({n_old} on step "
            f"{s_old}, {n_new} on step {s_new} after the hot reload) "
            f"all bit-identical to batch predict; p50="
            f"{att['serve_latency_p50_ms']:.1f}ms p99="
            f"{att['serve_latency_p99_ms']:.1f}ms, no thread leaks")


# --- serving-fleet scenarios ---------------------------------------------


def _free_port_block(n: int) -> int:
    """Base of n consecutive bindable loopback ports — the fleet
    contract puts replica i on ``serve_port + i``, so the scenario
    needs a whole block, not n scattered ports."""
    import socket
    for _ in range(64):
        socks = []
        try:
            s0 = socket.socket()
            s0.bind(("127.0.0.1", 0))
            base = s0.getsockname()[1]
            socks.append(s0)
            if base + n >= 65535:
                continue
            ok = True
            for i in range(1, n):
                s = socket.socket()
                socks.append(s)
                try:
                    s.bind(("127.0.0.1", base + i))
                except OSError:
                    ok = False
                    break
            if ok:
                return base
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no block of consecutive free loopback ports")


def _fleet_cfg_file(workdir: str, data: str, replicas: int,
                    base_port: int, **serve) -> str:
    """The ONE config file both the in-process FleetSupervisor and its
    replica child processes load (children see per-replica FM_* env
    deltas on top — port, metrics shard, external reload mode)."""
    knobs = {
        "serve_port": base_port,
        "serve_replicas": replicas,
        "serve_proxy_port": 0,
        "serve_max_batch": 8,
        "serve_max_wait_ms": 2.0,
        "serve_poll_seconds": 0.05,
        "serve_health_poll_seconds": 0.1,
        "serve_restart_backoff_seconds": 0.2,
        "serve_retry_budget": 2,
    }
    knobs.update(serve)
    block = "\n".join(f"{k} = {v}" for k, v in knobs.items())
    path = os.path.join(workdir, "fleet.cfg")
    with open(path, "w") as fh:
        fh.write(f"""
[General]
vocabulary_size = 200
factor_num = 4
model_file = {os.path.join(workdir, 'model', 'fm')}
log_file = {os.path.join(workdir, 'fleet.log')}

[Train]
train_files = {data}
batch_size = 32
learning_rate = 0.1
epoch_num = 2
save_steps = 5
shuffle = true
seed = 0
log_steps = 0
bucket_ladder = 8
max_features_per_example = 8
metrics_file = {os.path.join(workdir, 'fleet_metrics.jsonl')}
metrics_flush_steps = 5
io_backoff_seconds = 0.01

[SLO]
slo_p99_ms = 10000

[Serve]
{block}
""")
    return path


def _replica_log_tails(cfg, tail: int = 2000) -> str:
    out = []
    for i in range(cfg.serve_replicas):
        p = f"{cfg.model_file}.replica{i}.log"
        try:
            with open(p) as fh:
                out.append(f"--- replica {i} ---\n{fh.read()[-tail:]}")
        except OSError:
            out.append(f"--- replica {i}: no log at {p} ---")
    return "\n".join(out)


def _fire_proxy(port: int, req_lines, seed: int, stop_firing,
                results, res_lock, failures):
    """One proxy client: variable-size bursts of libsvm lines POSTed
    through the fleet front door, collecting (lines, response text,
    step, latency ms) — or the failure, which the scenarios assert
    NEVER happens."""
    import http.client as _http
    import time as _time
    rng = np.random.default_rng(seed)
    while not stop_firing.is_set():
        k = int(rng.integers(1, 6))
        lo = int(rng.integers(0, len(req_lines) - k))
        lines = req_lines[lo:lo + k]
        body = ("\n".join(lines) + "\n").encode("utf-8")
        t0 = _time.monotonic()
        try:
            conn = _http.HTTPConnection("127.0.0.1", port, timeout=60)
            try:
                conn.request("POST", "/score", body=body,
                             headers={"Content-Type": "text/plain"})
                resp = conn.getresponse()
                out = resp.read().decode("utf-8")
                status = resp.status
                step = resp.getheader("X-FM-Step")
            finally:
                conn.close()
        except Exception as e:  # noqa: BLE001 - asserted empty later
            failures.append(repr(e))
            continue
        lat_ms = (_time.monotonic() - t0) * 1000.0
        if status != 200 or step is None:
            failures.append(f"HTTP {status}: {out[:200]}")
            continue
        with res_lock:
            results.append((lines, out, int(step), lat_ms))


def _assert_fleet_parity(cfg, workdir: str, results) -> dict:
    """Per-step byte parity: every proxied response's text must equal
    the ``%.6f`` rendering of batch predict over the same lines
    against the step that scored it (the X-FM-Step tag). Torn or
    truncated responses fail here by construction. Returns the
    responses grouped by step."""
    import dataclasses as dc
    from fast_tffm_tpu.metrics import sigmoid
    from fast_tffm_tpu.predict import load_table, predict_scores
    pcfg = dc.replace(cfg, metrics_file="")
    by_step = {}
    for lines, text, step, _lat in results:
        by_step.setdefault(step, []).append((lines, text))
    for step, pairs in sorted(by_step.items()):
        table = load_table(pcfg, step=step)
        req_path = os.path.join(workdir, f"fleet_requests_{step}.txt")
        flat = [ln for lines, _text in pairs for ln in lines]
        with open(req_path, "w") as fh:
            fh.write("\n".join(flat) + "\n")
        want = sigmoid(predict_scores(pcfg, table, [req_path]))
        pos = 0
        for lines, text in pairs:
            n = len(lines)
            ref = "".join(f"{v:.6f}\n" for v in want[pos:pos + n])
            pos += n
            assert text == ref, (
                f"step {step}: proxied response diverged from batch "
                f"predict on the same checkpoint ({text[:40]!r} vs "
                f"{ref[:40]!r})")
    return by_step


def scenario_kill_replica_midburst(workdir: str, seed: int = 0) -> str:
    """ISSUE 19 acceptance (tentpole): a 3-replica serving fleet
    behind the failover proxy survives SIGKILL of one replica in the
    middle of a concurrent request burst. Zero client-visible
    failures (the proxy fails refused/reset forwards over to a
    different ready replica), every response byte-identical to batch
    predict against the step that scored it, a MID-INCIDENT fmstat
    snapshot reads FLEET DEGRADED (2/3 ready) (the supervisor's eager
    flush on the ready edge), the dead replica auto-restarts under
    backoff back to 3/3 with the post-drain verdict OK, and the
    client-observed p99 honors the [SLO] bound."""
    import signal as _signal
    import threading
    import time as _time
    from fast_tffm_tpu.checkpoint import (CheckpointState,
                                          list_step_dirs)
    from fast_tffm_tpu.config import load_config
    from fast_tffm_tpu.obs.attribution import (health_verdict, render,
                                               summarize)
    from fast_tffm_tpu.serve.fleet import FleetSupervisor
    from fast_tffm_tpu.train import train
    from tools.fmckpt import cmd_publish
    import dataclasses as dc

    workdir = os.path.abspath(workdir)
    data = os.path.join(workdir, "train.txt")
    _write_corpus(data, 400, seed)
    cfg_path = _fleet_cfg_file(workdir, data, replicas=3,
                               base_port=_free_port_block(3))
    cfg = load_config(cfg_path)
    train(dc.replace(cfg, metrics_file=""))
    ckpt = CheckpointState(cfg.model_file)
    steps = list_step_dirs(ckpt.directory)
    ckpt.close()
    s_pub = steps[-1]
    assert cmd_publish(cfg.model_file + ".ckpt", s_pub) == 0

    sup = FleetSupervisor(cfg, cfg_path).start()
    req_lines = _corpus_lines(60, seed + 99)
    results, res_lock, failures = [], threading.Lock(), []
    stop_firing = threading.Event()
    clients = []
    try:
        assert sup.wait_ready(3, timeout=300), (
            "fleet never reached 3 ready replicas:\n"
            + _replica_log_tails(cfg))
        clients = [threading.Thread(
            target=_fire_proxy,
            args=(sup.proxy_port, req_lines, seed + i, stop_firing,
                  results, res_lock, failures),
            name=f"burst-client-{i}") for i in range(4)]
        for t in clients:
            t.start()
        deadline = _time.monotonic() + 60
        while len(results) < 10:
            assert _time.monotonic() < deadline, (
                f"burst never started (failures: {failures[:3]})")
            _time.sleep(0.01)

        # The incident: SIGKILL one replica mid-burst.
        victim = sup.replicas[1]
        old_pid = victim.pid()
        os.kill(old_pid, _signal.SIGKILL)
        # Mid-incident observability: the supervisor flushes eagerly
        # on the ready-count edge, so fmstat over the live stream must
        # show the degradation window NOW, not after the fact.
        deadline = _time.monotonic() + 60
        while True:
            v = health_verdict(summarize([cfg.metrics_file]))["verdict"]
            if v.startswith("FLEET DEGRADED"):
                break
            assert _time.monotonic() < deadline, (
                f"no FLEET DEGRADED snapshot mid-incident (verdict "
                f"stayed {v!r})")
            _time.sleep(0.05)
        mid_verdict = v
        # Self-healing: the supervisor respawns the victim (capped
        # backoff) and the fleet returns to full strength.
        assert sup.wait_ready(3, timeout=300), (
            "killed replica never came back ready:\n"
            + _replica_log_tails(cfg))
        assert victim.pid() != old_pid, "victim was never respawned"
        # Keep the burst going on the healed fleet before stopping.
        n_mark = len(results)
        deadline = _time.monotonic() + 60
        while len(results) < n_mark + 10:
            assert _time.monotonic() < deadline, (
                f"no responses after recovery (failures: "
                f"{failures[:3]})")
            _time.sleep(0.01)
        stop_firing.set()
        for t in clients:
            t.join()
    finally:
        stop_firing.set()
        for t in clients:
            t.join(timeout=30)
        sup.stop()

    assert not failures, (
        f"{len(failures)} client-visible failure(s) — the proxy must "
        f"absorb the kill: {failures[:3]}")
    by_step = _assert_fleet_parity(cfg, workdir, results)
    assert set(by_step) == {s_pub}, (
        f"responses span steps {sorted(by_step)}, wanted [{s_pub}]")
    lat = sorted(r[3] for r in results)
    p99 = float(np.percentile(lat, 99))
    assert p99 <= cfg.slo_p99_ms, (
        f"client p99 {p99:.1f}ms blew the [SLO] slo_p99_ms = "
        f"{cfg.slo_p99_ms} bound")
    summ = summarize([cfg.metrics_file])
    c = summ.get("counters", {})
    assert c.get("fleet/deaths", 0) >= 1, c
    assert c.get("fleet/restarts", 0) >= 1, c
    assert c.get("proxy/requests") == len(results), (
        c.get("proxy/requests"), len(results))
    v_end = health_verdict(summ)["verdict"]
    assert v_end == "OK", v_end
    text = render(summ)
    assert "FLEET (serve --replicas)" in text and "r2:" in text, text
    leaked = [t.name for t in threading.enumerate()
              if t.is_alive() and (t.name.startswith("fm-fleet")
                                   or t.name.startswith("fm-proxy"))]
    assert not leaked, f"leaked fleet threads: {leaked}"
    retries = int(c.get("proxy/retries", 0)
                  + c.get("proxy/transport_errors", 0))
    return (f"{len(results)} proxied requests, 0 failures across a "
            f"SIGKILL of replica 1 (pid {old_pid}) mid-burst "
            f"({retries} failover retries/transport errors absorbed); "
            f"mid-incident fmstat read '{mid_verdict}', the replica "
            f"respawned and the final verdict is OK; all responses "
            f"bit-identical to batch predict on step {s_pub}; "
            f"p99 {p99:.1f}ms within the {cfg.slo_p99_ms}ms SLO")


def scenario_staggered_reload(workdir: str, seed: int = 0) -> str:
    """ISSUE 19 acceptance: a fleet-wide hot reload under load never
    has a zero-ready instant. `fmckpt publish` repoints the pointer
    while clients fire through the proxy; the supervisor staggers the
    reload (each replica waits for another ready replica before
    taking the token); a high-rate sampler on the proxy's aggregated
    /healthz must never observe ready == 0; responses land on BOTH
    steps and every one is byte-identical to batch predict against
    its step — none torn."""
    import json as _json
    import http.client as _http
    import threading
    import time as _time
    from fast_tffm_tpu.checkpoint import (CheckpointState,
                                          list_step_dirs)
    from fast_tffm_tpu.config import load_config
    from fast_tffm_tpu.obs.attribution import health_verdict, summarize
    from fast_tffm_tpu.serve.fleet import FleetSupervisor
    from fast_tffm_tpu.train import train
    from tools.fmckpt import cmd_publish
    import dataclasses as dc

    workdir = os.path.abspath(workdir)
    data = os.path.join(workdir, "train.txt")
    _write_corpus(data, 400, seed)
    cfg_path = _fleet_cfg_file(workdir, data, replicas=2,
                               base_port=_free_port_block(2))
    cfg = load_config(cfg_path)
    train(dc.replace(cfg, metrics_file=""))
    ckpt = CheckpointState(cfg.model_file)
    steps = list_step_dirs(ckpt.directory)
    ckpt.close()
    assert len(steps) >= 2, f"need >= 2 retained steps, got {steps}"
    s_old, s_new = steps[0], steps[-1]
    assert cmd_publish(cfg.model_file + ".ckpt", s_old) == 0

    sup = FleetSupervisor(cfg, cfg_path).start()
    req_lines = _corpus_lines(60, seed + 99)
    results, res_lock, failures = [], threading.Lock(), []
    stop_firing = threading.Event()
    stop_sampling = threading.Event()
    ready_samples = []
    clients = []
    sampler = None

    def sample_healthz():
        while not stop_sampling.is_set():
            try:
                conn = _http.HTTPConnection("127.0.0.1",
                                            sup.proxy_port, timeout=5)
                try:
                    conn.request("GET", "/healthz")
                    resp = conn.getresponse()
                    payload = _json.loads(resp.read())
                    ready_samples.append(
                        (int(payload["ready"]), resp.status))
                finally:
                    conn.close()
            except OSError:
                pass  # proxy briefly unreachable = not a zero-ready
            _time.sleep(0.005)

    try:
        assert sup.wait_ready(2, timeout=300), (
            "fleet never reached 2 ready replicas:\n"
            + _replica_log_tails(cfg))
        sampler = threading.Thread(target=sample_healthz,
                                   name="stagger-healthz-sampler")
        sampler.start()
        clients = [threading.Thread(
            target=_fire_proxy,
            args=(sup.proxy_port, req_lines, seed + i, stop_firing,
                  results, res_lock, failures),
            name=f"stagger-client-{i}") for i in range(3)]
        for t in clients:
            t.start()
        deadline = _time.monotonic() + 60
        while len(results) < 5:
            assert _time.monotonic() < deadline, (
                f"no responses before the publish (failures: "
                f"{failures[:3]})")
            _time.sleep(0.01)

        # The reload, through the operator path, under load.
        assert cmd_publish(cfg.model_file + ".ckpt", s_new) == 0
        deadline = _time.monotonic() + 180
        while True:
            rows = [r.probe() for r in sup.replicas]
            if all(h and h.get("served_step") == s_new
                   and h.get("ready") for h in rows):
                break
            assert _time.monotonic() < deadline, (
                f"staggered reload to step {s_new} never completed "
                f"(rows: {rows})\n" + _replica_log_tails(cfg))
            _time.sleep(0.05)
        # A few responses must land on the NEW step before we stop.
        deadline = _time.monotonic() + 60
        while not any(r[2] == s_new for r in list(results)):
            assert _time.monotonic() < deadline, (
                "no responses on the reloaded step")
            _time.sleep(0.01)
        stop_firing.set()
        for t in clients:
            t.join()
        stop_sampling.set()
        sampler.join()
        # Let the supervisor's CACHED health view (the source of the
        # fleet/ready gauge) observe full strength again before the
        # drain, so the final flush carries the healed fleet, not the
        # mid-reload edge.
        assert sup.wait_ready(2, timeout=60), (
            "fleet health view never recovered to 2 ready after the "
            "reload:\n" + _replica_log_tails(cfg))
    finally:
        stop_firing.set()
        stop_sampling.set()
        for t in clients:
            t.join(timeout=30)
        if sampler is not None:
            sampler.join(timeout=10)
        sup.stop()

    assert not failures, (
        f"{len(failures)} client-visible failure(s) during the "
        f"staggered reload: {failures[:3]}")
    assert ready_samples, "healthz sampler never sampled"
    min_ready = min(s[0] for s in ready_samples)
    assert min_ready >= 1, (
        f"zero-ready window observed during the staggered reload "
        f"({len(ready_samples)} samples)")
    assert all(s[1] == 200 for s in ready_samples), (
        "proxy /healthz went 503 during the reload")
    by_step = _assert_fleet_parity(cfg, workdir, results)
    assert set(by_step) == {s_old, s_new}, (
        f"responses span steps {sorted(by_step)}, wanted "
        f"{[s_old, s_new]}")
    summ = summarize([cfg.metrics_file])
    c = summ.get("counters", {})
    assert c.get("fleet/reloads", 0) >= 2, c
    assert c.get("fleet/reload_failures", 0) == 0, c
    v_end = health_verdict(summ)["verdict"]
    assert v_end == "OK", v_end
    n_old = len(by_step[s_old])
    n_new = len(by_step[s_new])
    return (f"staggered reload {s_old} -> {s_new} under load: "
            f"{len(results)} responses ({n_old} on the old step, "
            f"{n_new} on the new), 0 failures, min ready across "
            f"{len(ready_samples)} healthz samples = {min_ready} "
            f"(never zero), {int(c['fleet/reloads'])} replica "
            f"reloads, all responses bit-identical to batch predict")


# --- streaming run-mode scenarios ----------------------------------------


def _corpus_lines(n: int, seed: int) -> list:
    """The synthetic corpus as a line list (the stream writer appends
    them progressively instead of writing a file at once)."""
    import tempfile
    with tempfile.NamedTemporaryFile("r", suffix=".txt",
                                     delete=False) as fh:
        tmp = fh.name
    try:
        _write_corpus(tmp, n, seed)
        with open(tmp) as fh:
            return fh.read().splitlines()
    finally:
        os.remove(tmp)


def _append_shard_torn(path: str, lines: list, pause: float) -> None:
    """Append one shard the hostile way: several flushes, each ending
    with a TORN half-line that the next write completes — the reader
    must hold the torn tail back or it trains garbage — then the
    ``.done`` seal marker."""
    import time as _time
    thirds = max(1, len(lines) // 3)
    pos = 0
    with open(path, "a") as fh:
        while pos < len(lines):
            seg = lines[pos:pos + thirds]
            pos += len(seg)
            blob = "\n".join(seg) + "\n"
            if pos < len(lines):
                nxt = lines[pos]
                cut = max(1, len(nxt) // 2)
                fh.write(blob + nxt[:cut])   # torn write: half a line
                fh.flush()
                _time.sleep(pause)
                fh.write(nxt[cut:] + "\n")   # completed next flush
                fh.flush()
                pos += 1
            else:
                fh.write(blob)
                fh.flush()
            _time.sleep(pause)
    open(path + ".done", "w").close()


def _stream_cfg(workdir: str, stream_dir: str, **overrides):
    base = dict(run_mode="stream", stream_dir=stream_dir,
                stream_poll_seconds=0.05, seal_policy="done",
                shuffle=False, epoch_num=1)
    base.update(overrides)
    return _cfg(workdir, "", train_files=(), **base)


def scenario_stream_soak(workdir: str, seed: int = 0) -> str:
    """The streaming acceptance soak: a writer thread appends 6 shards
    WITH injected torn writes while the trainer streams them; a
    SIGTERM lands mid-stream and the restart resumes from the
    checkpointed watermark; the tail of the corpus is consumed under
    injected flaky opens. The run must finish having consumed every
    sealed line exactly once — pinned the strong way: the final table
    is BIT-IDENTICAL to a clean single-pass control run over the same
    sealed corpus — and at least 2 ``published`` pointer flips must
    land on manifest-verified steps."""
    import threading
    from fast_tffm_tpu.checkpoint import read_published
    from fast_tffm_tpu.testing.faults import (flaky_open,
                                              preempt_after_steps)
    from fast_tffm_tpu.train import train
    from tools.fmckpt import cmd_verify
    workdir = os.path.abspath(workdir)
    sd = os.path.join(workdir, "stream")
    os.makedirs(sd, exist_ok=True)
    n_shards, lines_per = 6, 400
    shard_lines = [_corpus_lines(lines_per, seed * 100 + i)
                   for i in range(n_shards)]

    def writer():
        for i in range(n_shards):
            _append_shard_torn(os.path.join(sd, f"part-{i:03d}.txt"),
                               shard_lines[i], pause=0.03)
        open(os.path.join(sd, "STOP"), "w").close()

    cfg = _stream_cfg(workdir, sd, publish_interval_seconds=0.25,
                      io_retries=3)
    w = threading.Thread(target=writer, name="stream-writer",
                         daemon=True)
    w.start()
    # Run 1: stream against the LIVE writer (torn writes in flight);
    # SIGTERM after 8 steps — mid-stream by construction (8 * 32 = 256
    # of 2400 lines).
    with preempt_after_steps(8) as st:
        train(cfg)
    assert st["fired"], "SIGTERM injector never fired"
    assert _verdict(cfg) == "PREEMPTED", _verdict(cfg)
    w.join(timeout=120)
    assert not w.is_alive(), "stream writer never finished"
    # Run 2: resume from the watermark; the first opens of a
    # not-yet-consumed shard fail transiently (EIO) — the retry layer
    # must absorb them.
    with flaky_open(2, match="part-003.txt") as fstate:
        table_stream = np.asarray(train(cfg))
    assert fstate["failures"] == 2, fstate
    # Exactly-once: total stepped examples across both run segments
    # equals the corpus exactly (no line lost at the preemption cut,
    # none double-trained on resume) ...
    c = _counters(cfg)
    total = n_shards * lines_per
    assert c.get("train/examples") == total, (
        c.get("train/examples"), total)
    assert c.get("io/retries", 0) >= 2, c.get("io/retries")
    # >= rather than ==: files the first segment discovered AFTER its
    # last adopted watermark are legitimately re-discovered (and
    # re-sealed) by the resumed segment's tracker, so the folded
    # counters can exceed the shard count — the exactness claims live
    # in train/examples and the bit-identity check.
    assert c.get("stream/files_discovered", 0) >= n_shards, c
    assert c.get("stream/files_sealed", 0) >= n_shards, c
    # ... and the strong form: bit-identical to a clean single-pass
    # control run over the same sealed corpus.
    ctl_dir = os.path.join(workdir, "ctl")
    os.makedirs(ctl_dir, exist_ok=True)
    ctl = _cfg(ctl_dir, "", shuffle=False, epoch_num=1,
               train_files=(os.path.join(sd, "part-*.txt"),))
    table_ctl = np.asarray(train(ctl))
    assert np.array_equal(table_stream, table_ctl), (
        "stream run diverged from the clean single-pass control: "
        f"max |delta| = {np.abs(table_stream - table_ctl).max()}")
    # Publishing: >= 2 pointer flips across the two segments, and the
    # final published pointer names a step fmckpt verify passes FULL.
    publishes = int(c.get("stream/publishes", 0))
    assert publishes >= 2, c
    assert not c.get("stream/publish_failures"), c
    ckpt_dir = cfg.model_file + ".ckpt"
    pub = read_published(ckpt_dir)
    assert pub is not None
    assert cmd_verify(ckpt_dir, mode="full", step=pub) == 0, (
        f"published step {pub} failed full verification")
    return (f"consumed {total} sealed lines exactly once across "
            f"SIGTERM+resume (torn writes held back, 2 flaky opens "
            f"absorbed), table bit-identical to the control, "
            f"{publishes} verified publishes (pointer at step {pub})")


def scenario_stream_truncate(workdir: str, seed: int = 0) -> str:
    """An in-progress (unsealed) stream file SHRINKS under the reader:
    the (inode, size) regression is detected, the file is sealed at
    the consumed position and the event is quarantined through the
    BadLineTracker — the run survives, finishes the rest of the
    stream, and the breaker accounting is exact (1 bad record, no
    trip)."""
    import json as _json
    from fast_tffm_tpu.testing.faults import preempt_after_steps
    from fast_tffm_tpu.train import train
    workdir = os.path.abspath(workdir)
    sd = os.path.join(workdir, "stream")
    os.makedirs(sd, exist_ok=True)
    growing = os.path.join(sd, "part-000.txt")
    lines = _corpus_lines(100, seed)
    with open(growing, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    # Run 1: tail the growing (UNSEALED — no .done) file; preempt after
    # 3 steps = 96 lines consumed, watermark mid-file.
    cfg = _stream_cfg(workdir, sd, bad_line_policy="quarantine",
                      save_steps=0)
    with preempt_after_steps(3) as st:
        train(cfg)
    assert st["fired"]
    # The fault: the in-progress file shrinks BELOW the consumed
    # position (a rewriting producer), a sealed successor shard
    # arrives, and the stream ends.
    with open(growing, "r+") as fh:
        fh.truncate(len("\n".join(lines[:50])) + 1)
    _write_corpus(os.path.join(sd, "part-001.txt"), 320,
                  seed + 1)
    open(os.path.join(sd, "part-001.txt.done"), "w").close()
    open(os.path.join(sd, "STOP"), "w").close()
    # Run 2: must detect the regression, quarantine it, and survive.
    train(cfg)
    c = _counters(cfg)
    assert c.get("stream/truncated_files") == 1, c
    assert c.get("pipeline/bad_lines") == 1, c
    # 96 lines before the cut + the whole successor shard, never the
    # vanished tail: exactly-once accounting around the damage.
    assert c.get("train/examples") == 96 + 320, c
    assert _verdict(cfg) == "OK", _verdict(cfg)
    qpath = cfg.metrics_file + ".quarantine"
    with open(qpath) as fh:
        recs = [_json.loads(ln) for ln in fh if ln.strip()]
    assert len(recs) == 1 and recs[0]["file"] == growing, recs
    assert "truncated" in recs[0]["error"], recs
    log = open(cfg.log_file).read()
    assert "truncated mid-stream" in log
    return ("in-progress file shrank 100 -> 50 lines at consumed line "
            "96: sealed at the watermark, 1 quarantine record, no "
            "breaker trip, run finished the successor shard (416 "
            "examples exactly once)")


def scenario_vocab_churn(workdir: str, seed: int = 0) -> str:
    """Unbounded-vocabulary admission under stream churn (README
    "Unbounded vocabulary"): a streaming run over a heavy-tailed
    hashed-id distribution — an early hot "era A" that goes cold, a
    later "era B", and a long unique tail far exceeding
    ``vocabulary_size`` — takes a mid-run SIGTERM, then resumes
    through a checkpoint WALK-BACK (the newest step is torn, so
    restore quarantines it and loads the older step's vocab sidecar).
    Asserts: admission state round-trips the preemption bit-exactly
    (payload -> load -> payload identity, and the resumed run logs the
    walked-back step's own live-row count), the slot map never exceeds
    the physical table (every row in [1, vocabulary_size), live <=
    vocabulary_size - 1) while the distinct-id count is >= 10x it,
    era-A rows are EVICTED once their decayed frequency falls below
    the floor, and the final published step serves an evicted id from
    the shared cold row — bit-identical to a never-seen id's score,
    NOT its stale embedding."""
    from fast_tffm_tpu.checkpoint import (QUARANTINE_PREFIX,
                                          list_step_dirs,
                                          read_published,
                                          read_vocab_sidecar)
    from fast_tffm_tpu.data.hashing import murmur64
    from fast_tffm_tpu.testing.faults import (preempt_after_steps,
                                              truncate_checkpoint)
    from fast_tffm_tpu.train import train
    from fast_tffm_tpu.vocab.sketch import HASH_SPACE
    from fast_tffm_tpu.vocab.table import VocabRuntime, payload_crc_ok
    import base64
    workdir = os.path.abspath(workdir)
    sd = os.path.join(workdir, "stream")
    os.makedirs(sd, exist_ok=True)
    V = 16  # physical table rows (1 cold + 15 live)
    rng = np.random.default_rng(seed)
    era_a = [f"hotA{i}" for i in range(4)]
    era_b = [f"hotB{i}" for i in range(4)]
    distinct = set()

    def write_shard(i, hot):
        lines = []
        for k in range(400):
            y = k % 2
            h = hot[(k % 2) * 2 + (k % 4) // 2]
            tail = f"u{int(rng.integers(0, 20000))}"
            distinct.update((h, tail))
            lines.append(f"{y} {h}:1 {tail}:0.5")
        path = os.path.join(sd, f"part-{i:03d}.txt")
        with open(path, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        open(path + ".done", "w").close()

    # LIVE writer with arrival gaps: publish barriers fire on the
    # driver's idle ticks inside each gap, so admission/eviction
    # decisions land deterministically BETWEEN eras regardless of how
    # fast the machine steps a sealed shard.
    import threading
    import time as _time

    def writer():
        write_shard(0, era_a)       # era A: hot, then never again
        _time.sleep(0.5)
        write_shard(1, era_b)       # era B takes over
        _time.sleep(0.5)
        write_shard(2, era_b)
        open(os.path.join(sd, "STOP"), "w").close()

    w = threading.Thread(target=writer, name="vocab-churn-writer",
                         daemon=True)
    cfg = _stream_cfg(workdir, sd, hash_feature_id=True,
                      vocabulary_size=V, save_steps=5,
                      publish_interval_seconds=0.15,
                      vocab_mode="admit", vocab_admit_threshold=2.0,
                      vocab_decay=0.25, vocab_sketch_mb=0.25)
    ckpt_dir = cfg.model_file + ".ckpt"

    def slot_keys(payload):
        return set(np.frombuffer(
            base64.b64decode(payload["state"]["slot_keys"]),
            np.int64).tolist())

    def slot_rows(payload):
        return np.frombuffer(
            base64.b64decode(payload["state"]["slot_rows"]), np.int32)

    # Run 1: SIGTERM after 20 steps — mid-era-B (shard 0 is 13
    # batches, so the era-A admission barrier has run inside the
    # first arrival gap), leaving shard 2 for the resumed run.
    w.start()
    with preempt_after_steps(20) as st:
        train(cfg)
    assert st["fired"], "SIGTERM injector never fired"
    assert _verdict(cfg) == "PREEMPTED", _verdict(cfg)
    w.join(timeout=120)
    assert not w.is_alive(), "stream writer never finished"
    assert len(distinct) >= 10 * V, len(distinct)
    steps = list_step_dirs(ckpt_dir)
    assert len(steps) >= 2, steps
    newest = steps[-1]
    payload = read_vocab_sidecar(ckpt_dir, newest)
    assert payload is not None and payload_crc_ok(payload)
    # Era A was admitted at SOME barrier before the preemption — pinned
    # via the cumulative counter, NOT membership in the newest sidecar:
    # barriers ride the wall-clock publish cadence, so on a fast machine
    # several fire inside the first arrival gap and era A can be
    # admitted AND already decayed out again by the step-20 save (that
    # early eviction is correct behavior, not a miss).
    c1 = _counters(cfg)
    assert c1.get("vocab/admitted_rows", 0) >= len(era_a), (
        f"expected >= {len(era_a)} admissions before the preemption, "
        f"got {c1.get('vocab/admitted_rows', 0)}")
    # Bit-exact round trip of the admission state through the sidecar
    # machinery: payload -> runtime.load -> state_payload identity.
    rt = VocabRuntime.from_config(cfg)
    rt.load(cfg, payload)
    assert rt.state_payload() == payload, (
        "vocab admission payload does not round-trip bit-exactly")
    # The walk-back fault: tear the newest step's largest array file —
    # the resume must quarantine it and load the OLDER step's sidecar.
    victim = truncate_checkpoint(cfg.model_file, seed=seed)
    assert victim and f"{os.sep}{newest}{os.sep}" in victim, victim
    older = steps[-2]
    older_payload = read_vocab_sidecar(ckpt_dir, older)
    assert older_payload is not None
    older_live = len(slot_keys(older_payload))
    # Run 2: resume through the walk-back, consume the rest of the
    # stream (era B + tail), evicting era A as its estimate decays.
    train(cfg)
    log = open(cfg.log_file).read()
    assert f"restored checkpoint at step {older}" in log, (
        "resume did not walk back to the older step")
    assert (f"restored vocab admission state at step {older}: "
            f"{older_live} live rows") in log, (
        "resume did not load the walked-back step's OWN vocab sidecar")
    assert any(n.startswith(QUARANTINE_PREFIX)
               for n in os.listdir(ckpt_dir))
    c = _counters(cfg)
    assert c.get("checkpoint/fallbacks", 0) >= 1, c
    # Final published state: bounded table, era A evicted.
    pub = read_published(ckpt_dir)
    assert pub is not None
    final_payload = read_vocab_sidecar(ckpt_dir, pub)
    assert final_payload is not None and payload_crc_ok(final_payload)
    rows = slot_rows(final_payload)
    assert len(rows) <= V - 1, len(rows)
    assert rows.size == 0 or (rows.min() >= 1 and rows.max() < V), rows
    assert c.get("vocab/evicted_rows", 0) >= 1, c
    final_keys = slot_keys(final_payload)
    evicted_a = [s for s in era_a
                 if murmur64(s.encode()) % HASH_SPACE not in final_keys]
    assert evicted_a, (
        "era-A ids all survived to the published step; eviction never "
        "reclaimed their rows")
    # Cold-row semantics at the published step: an EVICTED id scores
    # bit-identically to a never-seen id (both route to the shared
    # cold row) — never through its stale pre-eviction embedding.
    import dataclasses
    from fast_tffm_tpu.predict import load_table, predict_scores
    from fast_tffm_tpu.vocab.table import VocabMap
    pcfg = dataclasses.replace(cfg, run_mode="epochs", stream_dir="",
                               train_files=())
    table = load_table(pcfg, step=pub)
    vmap = VocabMap.from_payload(pcfg, final_payload)
    probe = os.path.join(workdir, "probe.txt")
    with open(probe, "w") as fh:
        fh.write(f"0 {evicted_a[0]}:1\n0 never_seen_xyzzy:1\n")
    s = predict_scores(pcfg, table, (probe,), vocab=vmap)
    assert s.shape == (2,)
    assert s[0] == s[1], (
        f"evicted id scored {s[0]} but the cold row scores {s[1]}: "
        "the published step is serving a stale embedding")
    return (f"{len(distinct)} distinct hashed ids (>= 10x the {V}-row "
            f"table) streamed through SIGTERM+resume and a walk-back "
            f"to step {older}; admission state round-tripped "
            f"bit-exactly, {int(c.get('vocab/evicted_rows', 0))} rows "
            f"evicted, published step {pub} serves evicted era-A ids "
            "from the cold row")


def scenario_slo_soak(workdir: str, seed: int = 0) -> str:
    """ISSUE 13 acceptance: the FULL closed loop under SLOs. A live
    writer feeds the stream, a gated trainer (``publish_min_auc``)
    publishes on interval, and a ScorerServer serves a concurrent
    request load against the moving pointer. Mid-soak a POISONED burst
    (label-flipped shard) arrives: the per-publish quality sweep must
    catch the regression — the ``published`` pointer never advances to
    a held step, ``health: gate_held`` fires, fmstat's verdict reads
    GATE-HELD — while serving continues uninterrupted on the last
    good step. Clean data then heals the model, publishes resume, and
    at the end EVERY SLO must hold: publish staleness bound, serve
    p99 bound, exactly-once stream consumption, minimum quality AUC,
    and per-step response parity — every served score bit-identical
    to offline predict against a control snapshot of the step that
    scored it (snapshots taken at pointer-observation time, so
    retention GC can't erase the evidence)."""
    import dataclasses as dc
    import shutil
    import subprocess
    import sys
    import threading
    import time as _time
    from fast_tffm_tpu.checkpoint import read_published
    from fast_tffm_tpu.config import load_config
    from fast_tffm_tpu.metrics import sigmoid
    from fast_tffm_tpu.obs.attribution import render
    from fast_tffm_tpu.obs.slo import SloSpec, evaluate_slos, overall
    from fast_tffm_tpu.predict import load_table, predict_scores
    from fast_tffm_tpu.serve import ScoreClient, ScorerServer
    from tools.fmstat import main as fmstat_main

    workdir = os.path.abspath(workdir)
    sd = os.path.join(workdir, "stream")
    os.makedirs(sd, exist_ok=True)
    val = os.path.join(workdir, "val.txt")
    _write_corpus(val, 240, seed + 1)

    shard_i = [0]
    total = [0]

    def write_shard(lines) -> None:
        path = os.path.join(sd, f"part-{shard_i[0]:03d}.txt")
        shard_i[0] += 1
        with open(path, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        open(path + ".done", "w").close()
        total[0] += len(lines)

    def flip(line: str) -> str:
        y, rest = line.split(" ", 1)
        return f"{1 - int(y)} {rest}"

    write_shard(_corpus_lines(400, seed))
    write_shard(_corpus_lines(400, seed + 2))

    # The trainer runs as a REAL process driving run_tffm.py (the
    # production entry point): the harness orchestrates purely through
    # the filesystem — the stream dir, the published pointer, and the
    # metrics JSONL — exactly like an operator's deployment.
    MIN_AUC = 0.7
    cfg_path = os.path.join(workdir, "slo_soak.cfg")
    with open(cfg_path, "w") as fh:
        fh.write(f"""
[General]
vocabulary_size = 200
factor_num = 4
model_file = {os.path.join(workdir, 'model', 'fm')}
log_file = {os.path.join(workdir, 'trainer.log')}

[Train]
run_mode = stream
stream_dir = {sd}
stream_poll_seconds = 0.05
seal_policy = done
shuffle = false
epoch_num = 1
batch_size = 32
learning_rate = 0.1
log_steps = 0
metrics_file = {os.path.join(workdir, 'metrics.jsonl')}
metrics_flush_steps = 2
io_backoff_seconds = 0.01
publish_interval_seconds = 0.2
publish_min_auc = {MIN_AUC}
validation_files = {val}

[SLO]
slo_publish_staleness_seconds = 60
slo_p99_ms = 10000
slo_min_auc = {MIN_AUC}
slo_max_bad_fraction = 0.001
""")
    cfg = load_config(cfg_path)
    ckpt_dir = cfg.model_file + ".ckpt"
    serve_metrics = os.path.join(workdir, "serve_metrics.jsonl")

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    trainer_out_path = os.path.join(workdir, "trainer.out")
    trainer_out = open(trainer_out_path, "w")
    trainer = subprocess.Popen(
        [sys.executable, "run_tffm.py", "train", cfg_path],
        cwd=repo, env=env, stdout=trainer_out,
        stderr=subprocess.STDOUT)

    def _trainer_tail() -> str:
        try:
            with open(trainer_out_path) as fh:
                return fh.read()[-3000:]
        except OSError:
            return "<no trainer output>"

    def wait_for(fn, what, deadline_s: float = 180.0):
        deadline = _time.monotonic() + deadline_s
        while True:
            v = fn()
            if v not in (None, False) and v != []:
                return v
            assert trainer.poll() is None, (
                f"trainer exited (rc {trainer.returncode}) before "
                f"{what}:\n{_trainer_tail()}")
            assert _time.monotonic() < deadline, (
                f"timed out waiting for {what}")
            _time.sleep(0.02)

    # Best-effort teardown on ANY exit: a wait_for timeout or a
    # failed assertion must not leak a live training subprocess
    # (polling the stream forever) or server/client threads into
    # the rest of the suite.
    server = None
    clients = []
    poller = None
    stop_firing = threading.Event()
    stop_polling = threading.Event()
    try:
        # The first publish only lands once the gate passes — an untrained
        # model's validation AUC holds publish_min_auc, so a pointer here
        # already proves the gate's first-publish (min-AUC-only) path ran.
        wait_for(lambda: read_published(ckpt_dir) is not None,
                 "first gate-passing publish")

        # Pointer trajectory + per-step offline CONTROL snapshots: each
        # newly observed published step dir (and its manifest) is copied
        # out at observation time, so the end-of-run parity check can
        # score against steps max_to_keep retention GC'd long before the
        # soak ended.
        ctl_prefix = os.path.join(workdir, "control", "fm")
        ctl_dir = ctl_prefix + ".ckpt"
        os.makedirs(ctl_dir, exist_ok=True)
        # pub_seen records every pointer value OBSERVED (the held-step
        # and response-subset assertions key on observation, not on
        # snapshot success); ctl_ok records the steps whose control
        # snapshot actually landed — a copytree can lose a race with
        # retention GC, in which case that step's parity is checked
        # only if the server also never scored it.
        pub_seen = set()
        ctl_ok = set()

        def snapshot(step) -> bool:
            src = os.path.join(ckpt_dir, str(step))
            dst = os.path.join(ctl_dir, str(step))
            if os.path.isdir(dst):
                return True
            if not os.path.isdir(src):
                return False
            try:
                shutil.copytree(src, dst)
                man = os.path.join(ckpt_dir, f"manifest-{step}.json")
                if os.path.isfile(man):
                    shutil.copy(man, os.path.join(
                        ctl_dir, f"manifest-{step}.json"))
                return True
            except OSError:
                # racing retention GC mid-copy: drop the partial snapshot
                # and let the next poll retry (the pointer only names live
                # steps, so a re-observation re-snapshots it)
                shutil.rmtree(dst, ignore_errors=True)
                return False

        def poll_pointer():
            while not stop_polling.is_set():
                s = read_published(ckpt_dir)
                if s is not None:
                    pub_seen.add(s)
                    # Retry failed/pending snapshots while their step
                    # dirs are still live (GC may yet win — that only
                    # weakens parity for a step nothing served).
                    for p in pub_seen - ctl_ok:
                        if snapshot(p):
                            ctl_ok.add(p)
                _time.sleep(0.005)

        poller = threading.Thread(target=poll_pointer,
                                  name="slo-pointer-poll", daemon=True)
        poller.start()
        wait_for(lambda: bool(ctl_ok), "pointer snapshot")

        # The serving plane, live against the moving pointer.
        scfg = dc.replace(cfg, metrics_file=serve_metrics,
                          serve_poll_seconds=0.02, serve_max_batch=8,
                          serve_max_wait_ms=2.0)
        server = ScorerServer(scfg)
        client = ScoreClient(server)
        req_lines = _corpus_lines(60, seed + 99)
        results, res_lock, errors = [], threading.Lock(), []

        def fire(worker: int) -> None:
            rng = np.random.default_rng(seed + worker)
            while not stop_firing.is_set():
                k = int(rng.integers(1, 6))
                lo = int(rng.integers(0, len(req_lines) - k))
                lines = req_lines[lo:lo + k]
                try:
                    res = client.score(lines, timeout=30)
                except Exception as e:  # noqa: BLE001 - assert at the end
                    errors.append(e)
                    return
                with res_lock:
                    results.append((lines, res.scores, res.step))

        clients = [threading.Thread(target=fire, args=(i,),
                                    name=f"slo-client-{i}")
                   for i in range(3)]
        for t in clients:
            t.start()
        wait_for(lambda: len(results) >= 5, "first served responses")

        # The poisoned burst: the same feature distribution with every
        # label flipped — training through it inverts the model, and the
        # next publish tick's validation sweep must catch it.
        write_shard([flip(ln) for ln in _corpus_lines(1600, seed + 3)])

        def gate_events():
            return [h for h in _summary(cfg).get("health_events", [])
                    if h.get("status") == "gate_held"]

        held = wait_for(gate_events, "gate_held health event")
        held_steps = {int(h["step"]) for h in held}
        pub_at_hold = read_published(ckpt_dir)
        n_before_recovery = len(results)

        # Recovery: clean shards until a NEW step publishes past the hold
        # — the closed loop healing itself.
        write_shard(_corpus_lines(800, seed + 4))
        write_shard(_corpus_lines(800, seed + 5))
        wait_for(lambda: read_published(ckpt_dir) not in (None,
                                                          pub_at_hold),
                 "post-recovery publish")
        open(os.path.join(sd, "STOP"), "w").close()
        try:
            rc = trainer.wait(timeout=180)
        except subprocess.TimeoutExpired:
            trainer.kill()
            raise AssertionError(
                f"trainer never drained the stream:\n{_trainer_tail()}")
        finally:
            trainer_out.close()
        assert rc == 0, f"trainer failed (rc {rc}):\n{_trainer_tail()}"
        final_pub = read_published(ckpt_dir)
        assert final_pub is not None
        pub_seen.add(final_pub)
        if snapshot(final_pub):  # post-join: the final step is live
            ctl_ok.add(final_pub)
        # Let the server observe the exit publish so responses cover the
        # final step too, then stop traffic.
        deadline = _time.monotonic() + 30
        while (server.served_step != final_pub
               and _time.monotonic() < deadline):
            _time.sleep(0.01)
        assert server.served_step == final_pub, (
            f"server never reloaded the final published step {final_pub} "
            f"(serving {server.served_step})")
        _time.sleep(0.1)  # a few requests on the final step
        stop_firing.set()
        for t in clients:
            t.join()
        assert not errors, errors[:3]
        server.close()
        stop_polling.set()
        poller.join(timeout=5)

        # --- the five SLO assertions -------------------------------------
        c = _counters(cfg)
        # (1) exactly-once consumption: every written line (good AND
        # poisoned) trained exactly once.
        assert c.get("train/examples") == total[0], (
            c.get("train/examples"), total[0])
        # (2) the gate caught the burst: >= 1 hold, the held steps never
        # published and never served, and serving CONTINUED through the
        # hold (responses kept landing before the recovery publish).
        assert held_steps, "no gate_held step recorded"
        assert int(c.get("quality/gate_held", 0)) >= 1, c
        assert not held_steps & pub_seen, (
            f"held step(s) {held_steps & pub_seen} reached the pointer")
        resp_steps = {r[2] for r in results}
        assert not held_steps & resp_steps, (
            f"held step(s) {held_steps & resp_steps} served traffic")
        assert resp_steps <= pub_seen, (
            f"responses tagged unpublished steps: {resp_steps - pub_seen}")
        assert len(results) > n_before_recovery, (
            "serving stalled during the gate hold")
        assert len(pub_seen) >= 2, pub_seen
        # (3) per-step score parity with the offline predict control: every
        # response bit-identical against its step's snapshot.
        pcfg = dc.replace(cfg, metrics_file="", model_file=ctl_prefix,
                          run_mode="epochs", stream_dir="",
                          publish_interval_seconds=0.0,
                          publish_min_auc=0.0, validation_files=())
        by_step = {}
        for lines, scores, step in results:
            by_step.setdefault(step, []).append((lines, scores))
        # Every SERVED step must have its control snapshot: the server
        # loads a step strictly after publishing it, and the retry
        # loop re-snapshots while the dir is live, so only a step
        # nothing ever served may legitimately lose the GC race.
        assert set(by_step) <= ctl_ok, (
            f"served step(s) {set(by_step) - ctl_ok} have no control "
            f"snapshot (observed {sorted(pub_seen)}, "
            f"snapshotted {sorted(ctl_ok)})")
        assert final_pub in by_step, (
            f"no responses landed on the final step {final_pub}")
        for step, pairs in sorted(by_step.items()):
            table = load_table(pcfg, step=step)
            req_path = os.path.join(workdir, f"requests_{step}.txt")
            flat, sizes = [], []
            for lines, _scores in pairs:
                flat.extend(lines)
                sizes.append(len(lines))
            with open(req_path, "w") as fh:
                fh.write("\n".join(flat) + "\n")
            want = sigmoid(predict_scores(pcfg, table, [req_path]))
            pos = 0
            for (lines, scores), n in zip(pairs, sizes):
                ref = want[pos:pos + n]
                pos += n
                assert np.array_equal(ref, scores), (
                    f"step {step}: served scores diverged from the "
                    f"offline predict control ({scores[:3]} vs {ref[:3]})")
        # (4) + (5) the declared SLOs all PASS from the JSONL alone —
        # publish staleness, serve p99, min AUC (recovered past the
        # poison), bad fraction — via the library AND the fmstat slo CLI.
        from fast_tffm_tpu.obs.attribution import summarize
        summary = summarize([cfg.metrics_file, serve_metrics])
        spec = SloSpec.from_summary(summary)
        slo_rows = evaluate_slos(spec, summary)
        assert len(slo_rows) == 4, slo_rows
        assert overall(slo_rows) == "PASS", [
            (r.objective, r.status, r.measured) for r in slo_rows]
        assert fmstat_main(["slo", cfg.metrics_file, serve_metrics,
                            "--json"]) == 0
        # fmstat renders the verdict + QUALITY section.
        v = _verdict(cfg)
        assert v.startswith("GATE-HELD"), v
        text = render(_summary(cfg))
        assert "QUALITY (per-publish eval + gate)" in text, text
        auc_final = summary["gauges"].get("quality/auc")
        return (f"{total[0]} streamed lines trained exactly once; gate "
                f"held {len(held)}x at step(s) {sorted(held_steps)} on the "
                f"poisoned burst (pointer pinned, serving continued), "
                f"{len(pub_seen)} publishes landed, {len(results)} "
                f"concurrent responses across {len(by_step)} step(s) all "
                f"bit-identical to the offline control, final AUC "
                f"{auc_final:.3f}, all 4 SLOs PASS")
    finally:
        stop_firing.set()
        stop_polling.set()
        for t in clients:
            t.join(timeout=10)
        if poller is not None:
            poller.join(timeout=5)
        if server is not None:
            server.close()  # idempotent: a no-op on the orderly path
        if trainer.poll() is None:
            trainer.kill()
            trainer.wait(timeout=30)
        try:
            trainer_out.close()
        except OSError:
            pass


# --- multi-worker compute-plane scenarios --------------------------------


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _write_cluster_cfg(workdir: str, data: str, model: str,
                       metrics: str, epoch_num: int, elastic: str,
                       collective_timeout: float = 30.0,
                       save_steps: int = 0) -> str:
    """A 2-worker localhost cluster config with the compute-plane
    knobs the scenarios exercise: sub-second heartbeats so a dead
    worker goes visibly stale fast, and a small collective deadline so
    a hang is diagnosed in test time, not operator time."""
    coord = _free_port()
    cfg_path = os.path.join(workdir, f"cluster_{elastic}.cfg")
    with open(cfg_path, "w") as fh:
        fh.write(f"""
[General]
vocabulary_size = 200
factor_num = 4
model_file = {model}

[Train]
train_files = {data}
epoch_num = {epoch_num}
batch_size = 32
learning_rate = 0.1
shuffle = False
log_steps = 0
save_steps = {save_steps}
metrics_file = {metrics}
metrics_flush_steps = 2

[Cluster]
worker_hosts = localhost:{coord - 1000},localhost:{coord - 999}
cluster_connect_timeout_seconds = 120
collective_timeout_seconds = {collective_timeout}
heartbeat_seconds = 0.4
elastic = {elastic}
""")
    return cfg_path


def _spawn_workers(workdir: str, cfg_path: str, n: int = 2):
    """Launch n real worker processes (run_tffm.py train ... dist_train
    worker i), stdout+stderr into worker<i>.out files."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    procs = []
    for i in range(n):
        out = open(os.path.join(workdir, f"worker{i}.out"), "w")
        procs.append((subprocess.Popen(
            [sys.executable, "run_tffm.py", "train", cfg_path,
             "dist_train", "worker", str(i)],
            cwd=repo, env=env, stdout=out, stderr=subprocess.STDOUT),
            out))
    return procs


def _worker_out(workdir: str, i: int) -> str:
    with open(os.path.join(workdir, f"worker{i}.out")) as fh:
        return fh.read()


def _metrics_step(metrics_path: str) -> int:
    """Latest flushed train/steps counter in a (possibly mid-write)
    metrics stream — the milestone the scenarios key fault delivery
    on: steps flushing means every worker is past bring-up and
    stepping in lockstep."""
    best = 0
    try:
        with open(metrics_path, encoding="utf-8") as fh:
            for line in fh:
                if '"metrics"' not in line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail mid-write
                best = max(best, int((rec.get("counters") or {})
                                     .get("train/steps", 0)))
    except OSError:
        pass
    return best


def _reap(procs, sig=None) -> None:
    """Never leak a worker, assertions included. ``sig`` is delivered
    first to still-running workers (the hang scenario SIGCONTs its
    frozen worker so the SIGKILL can land)."""
    for p, out in procs:
        if p.poll() is None:
            if sig is not None:
                try:
                    p.send_signal(sig)
                except OSError:
                    pass
            try:
                p.kill()
            except OSError:
                pass
        try:
            p.wait(timeout=30)
        finally:
            out.close()


def scenario_kill_worker_midwindow(workdir: str, seed: int = 0) -> str:
    """SIGKILL one of 2 lockstep workers mid-run: with elastic=shrink
    the survivor diagnoses, reforms, restores the last verified
    checkpoint, and finishes the WHOLE schedule with every input shard
    of the recovered pass consumed exactly once; with elastic=off the
    survivor fails fast with the same named diagnosis."""
    import re
    import signal
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.checkpoint import CheckpointState
    from fast_tffm_tpu.testing.faults import committed_steps, wait_until
    from fast_tffm_tpu.checkpoint import checkpoint_template
    workdir = os.path.abspath(workdir)
    data = os.path.join(workdir, "train_elastic.txt")
    n_lines, batch = 4864, 32         # 152 exact steps per single pass
    steps_per_pass = n_lines // batch
    _write_corpus(data, n_lines, seed)

    # Phase A (elastic=shrink): a fresh 2-worker job with periodic
    # saves; SIGKILL worker 1 in the window between two saves — after
    # a committed step exists (the recovery's restore point) and well
    # clear of the next save's orbax commit barrier.
    model = os.path.join(workdir, "model", "fm")
    metrics = os.path.join(workdir, "metrics.jsonl")
    epochs, save_steps = 4, 60
    cfg_path = _write_cluster_cfg(workdir, data, model, metrics,
                                  epoch_num=epochs, elastic="shrink",
                                  save_steps=save_steps)
    procs = _spawn_workers(workdir, cfg_path)
    try:
        def mid_save_window() -> bool:
            committed = committed_steps(model)
            if not committed:
                return False
            s = _metrics_step(metrics)
            return (s >= committed[-1] + 3
                    and s % save_steps < save_steps - 15)

        wait_until(mid_save_window, timeout=240, interval=0.02,
                   message="2-worker job stepping past a committed "
                           "save, clear of the next")
        procs[1][0].send_signal(signal.SIGKILL)
        wait_until(lambda: procs[0][0].poll() is not None, timeout=300,
                   message="survivor finishing after the kill")
    finally:
        _reap(procs)
    out0 = _worker_out(workdir, 0)
    assert procs[0][0].returncode == 0, (
        f"survivor failed:\n{out0[-3000:]}")
    assert "worker lost" in out0 and "process 1" in out0, out0[-3000:]
    assert "elastic reform generation 1" in out0, out0[-3000:]
    assert "elastic recovery complete" in out0, out0[-3000:]
    assert "training done" in out0, out0[-3000:]
    # Exactly-once recovered pass: the survivor restored the last
    # verified checkpoint (step s0, epoch e0) and re-ran epochs
    # e0..epochs-1 ALONE, so each recovered epoch is one full
    # 152-step pass over every byte of the corpus — the dead worker's
    # shards redistributed by construction. Any dropped or
    # double-consumed shard changes the final step count.
    restores = re.findall(r"restored checkpoint at step (\d+)", out0)
    assert restores, "recovered session never restored a checkpoint"
    s0 = int(restores[-1])
    resumes = re.findall(
        r"resuming interrupted epoch schedule at epoch (\d+)/", out0)
    e0 = int(resumes[-1]) if resumes else 0
    cfg = FmConfig(vocabulary_size=200, factor_num=4, batch_size=batch,
                   epoch_num=epochs, train_files=(data,),
                   model_file=model)
    ckpt = CheckpointState(model)
    final = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    want_step = s0 + (epochs - e0) * steps_per_pass
    assert int(final["step"]) == want_step, (int(final["step"]),
                                             want_step, s0, e0)
    assert int(final["epoch"]) == epochs, int(final["epoch"])
    # fmstat over the chief stream + the dead worker's shard: the
    # worker_lost diagnosis and the elastic recovery land in ONE run
    # segment, and the verdict is DEGRADED (ranked below PREEMPTED).
    from fast_tffm_tpu.obs.attribution import health_verdict, summarize
    shards = [metrics] + ([metrics + ".p1"]
                          if os.path.exists(metrics + ".p1") else [])
    summary = summarize(shards)
    statuses = [h.get("status") for h in summary["health_events"]]
    assert "worker_lost" in statuses, statuses
    assert "elastic_recovered" in statuses, statuses
    v = health_verdict(summary)["verdict"]
    assert v == "DEGRADED (1 worker lost)", v

    # Phase B: same kill, elastic=off — fail FAST with the named
    # diagnosis (bounded by the collective deadline), never a hang.
    offdir = os.path.join(workdir, "off")
    os.makedirs(offdir, exist_ok=True)
    off_metrics = os.path.join(offdir, "metrics.jsonl")
    off_cfg = _write_cluster_cfg(
        offdir, data, os.path.join(offdir, "model", "fm"), off_metrics,
        epoch_num=20, elastic="off", collective_timeout=20.0)
    procs = _spawn_workers(offdir, off_cfg)
    try:
        wait_until(lambda: _metrics_step(off_metrics) >= 4, timeout=240,
                   message="elastic=off job stepping")
        procs[1][0].send_signal(signal.SIGKILL)
        # Fail-fast bound: deadline + staleness grace + teardown slack.
        wait_until(lambda: procs[0][0].poll() is not None, timeout=120,
                   message="elastic=off survivor failing fast")
    finally:
        _reap(procs)
    out0 = _worker_out(offdir, 0)
    assert procs[0][0].returncode != 0, "elastic=off must fail fast"
    assert "WorkerLostError" in out0 and "process 1" in out0, (
        out0[-3000:])
    return (f"shrink: survivor recovered to step {want_step}/"
            f"epoch {epochs} with verdict {v!r}; off: survivor failed "
            "fast naming process 1")


def scenario_hang_worker(workdir: str, seed: int = 0) -> str:
    """SIGSTOP one of 2 lockstep workers: the deadline guard expires
    and the survivor exits with a WorkerLostError naming the stopped
    process (its heartbeats went quiet without the process dying) —
    never an indefinite hang."""
    import signal
    from fast_tffm_tpu.testing.faults import wait_until
    workdir = os.path.abspath(workdir)
    data = os.path.join(workdir, "train_hang.txt")
    _write_corpus(data, 1216, seed)
    metrics = os.path.join(workdir, "metrics.jsonl")
    cfg_path = _write_cluster_cfg(
        workdir, data, os.path.join(workdir, "model", "fm"), metrics,
        epoch_num=20, elastic="off", collective_timeout=8.0)
    procs = _spawn_workers(workdir, cfg_path)
    try:
        wait_until(lambda: _metrics_step(metrics) >= 4, timeout=240,
                   message="2-worker job stepping")
        procs[1][0].send_signal(signal.SIGSTOP)
        # Never an indefinite hang: the guard's 8s deadline + the
        # staleness grace bound the diagnosis; 120s covers teardown.
        wait_until(lambda: procs[0][0].poll() is not None, timeout=120,
                   message="survivor diagnosing the stopped worker")
    finally:
        _reap(procs, sig=signal.SIGCONT)
    out0 = _worker_out(workdir, 0)
    assert procs[0][0].returncode != 0, (
        "survivor must fail fast, not complete, when a peer is "
        "stopped mid-schedule")
    assert "WorkerLostError" in out0, out0[-3000:]
    assert "process 1" in out0, out0[-3000:]
    from fast_tffm_tpu.obs.attribution import summarize
    summary = summarize([metrics])
    lost = [h for h in summary["health_events"]
            if h.get("status") == "worker_lost"]
    assert lost, summary["health_events"]
    named = {p.get("process_index")
             for h in lost for p in h.get("lost", [])}
    assert 1 in named, named
    return ("survivor diagnosed the SIGSTOPped worker 1 within the "
            "collective deadline and exited with WorkerLostError")


# --- elastic GROW scenarios ----------------------------------------------


def _write_grow_cfg(workdir: str, stream_dir: str, model: str,
                    metrics: str, join_settle: float = 2.5) -> str:
    """A 2-worker localhost STREAM cluster with elastic = grow: fast
    heartbeats/publishes so rendezvous runs in test time, an explicit
    uniq_bucket (no probe — bucket choice must not depend on which
    shards exist when a session starts), and per-step metrics flushes
    so a SIGKILLed worker's final counters are already durable (the
    exactly-once accounting below sums the dead worker's shard)."""
    coord = _free_port()
    cfg_path = os.path.join(workdir, "grow.cfg")
    with open(cfg_path, "w") as fh:
        fh.write(f"""
[General]
vocabulary_size = 200
factor_num = 4
model_file = {model}

[Train]
epoch_num = 1
batch_size = 32
learning_rate = 0.1
shuffle = False
log_steps = 0
save_steps = 0
metrics_file = {metrics}
metrics_flush_steps = 1
run_mode = stream
stream_dir = {stream_dir}
stream_poll_seconds = 0.05
seal_policy = done
publish_interval_seconds = 0.3
max_features_per_example = 16
uniq_bucket = 256

[Cluster]
worker_hosts = localhost:{coord - 1000},localhost:{coord - 999}
cluster_connect_timeout_seconds = 120
collective_timeout_seconds = 30
heartbeat_seconds = 0.4
elastic = grow
join_settle_seconds = {join_settle}
""")
    return cfg_path


def _stage_shard(stream_dir: str, index: int, lines: list) -> None:
    """Publish one COMPLETE sealed shard atomically: written as a
    dotfile (discovery skips hidden names), renamed into place in one
    operation, sealed immediately. The bit-parity contract of the grow
    scenarios depends on this — a shard must never be discovered
    half-written, or batch grouping (and the final table's bits) would
    depend on writer/reader timing instead of only on the corpus."""
    name = f"part-{index:03d}.txt"
    tmp = os.path.join(stream_dir, "." + name)
    with open(tmp, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    os.replace(tmp, os.path.join(stream_dir, name))
    open(os.path.join(stream_dir, name + ".done"), "w").close()


def _spawn_joiner(workdir: str, cfg_path: str):
    """Launch the replacement worker: run_tffm.py train <cfg> --join."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = open(os.path.join(workdir, "joiner.out"), "w")
    return (subprocess.Popen(
        [sys.executable, "run_tffm.py", "train", cfg_path, "--join"],
        cwd=repo, env=env, stdout=out, stderr=subprocess.STDOUT), out)


class _SignalDeath(Exception):
    """A spawned worker died on SIGSEGV/SIGABRT/SIGBUS — the KNOWN
    upstream jaxlib restore-then-step crash class
    (tests/test_multiprocess._rerun_on_worker_signal carries the same
    bounded guard for the slow suite; the silent-corruption variant is
    fixed by checkpoint._restore_host_staged, the process-death
    variant still fires intermittently). Distinct from an assertion
    or a nonzero exit, which must NEVER retry."""

    def __init__(self, sig: int, what: str):
        super().__init__(f"worker died on signal {sig} {what}")
        self.sig = sig


_RERUN_SIGNALS = (11, 6, 7)  # SIGSEGV / SIGABRT / SIGBUS


def _raise_if_signal_death(p, what: str) -> None:
    rc = p.returncode
    if rc is not None and rc < 0 and -rc in _RERUN_SIGNALS:
        raise _SignalDeath(-rc, what)


def _retry_known_jaxlib_flake(body, workdir: str, name: str,
                              attempts: int = 2):
    """Bounded rerun for the known upstream crash above: ONLY a
    _SignalDeath reruns, each attempt in a FRESH subdir so leftover
    checkpoints/leases can't contaminate the retry; assertion failures
    and nonzero worker exits propagate on the first attempt — a real
    regression must never hide behind the retry."""
    import sys
    for attempt in range(attempts + 1):
        sub = os.path.join(workdir,
                           name if attempt == 0
                           else f"{name}_retry{attempt}")
        os.makedirs(sub, exist_ok=True)
        try:
            return body(sub)
        except _SignalDeath as e:
            if attempt >= attempts:
                raise
            print(f"fmchaos: {name}: worker died on signal {e.sig} "
                  f"(known jaxlib restore-then-step flake); rerun "
                  f"{attempt + 1}/{attempts}", file=sys.stderr)


def _wait_published(ckpt_dir: str, step: int, timeout: float = 240,
                    procs=()) -> None:
    """Block until the published pointer reaches ``step`` — the
    consumption gate between staged shards. Fails EARLY if a process
    whose exit we are not expecting dies (a crashed chief would
    otherwise burn the whole timeout looking at a frozen pointer); a
    SIGNAL death raises _SignalDeath so the bounded flake guard can
    rerun it."""
    from fast_tffm_tpu.checkpoint import read_published
    from fast_tffm_tpu.testing.faults import wait_until

    def due() -> bool:
        for p, _out in procs:
            if p.poll() is not None:
                _raise_if_signal_death(
                    p, f"while waiting for published step {step}")
                raise AssertionError(
                    f"worker exited rc={p.returncode} while waiting "
                    f"for published step {step}")
        return (read_published(ckpt_dir) or -1) >= step
    wait_until(due, timeout=timeout, interval=0.05,
               message=f"published pointer reaching step {step}")


def scenario_kill_then_grow(workdir: str, seed: int = 0) -> str:
    """ISSUE 14 acceptance: a 2-worker stream job loses worker 1 to
    SIGKILL mid-window, the survivor shrinks and keeps training, a
    freshly launched ``--join`` replacement is admitted at the next
    publish settle, and the run finishes at FULL membership — with
    exactly-once consumption (train/examples == every line written,
    summed across the chief's stream, the dead worker's shard, and the
    joiner's shard) and the final table BIT-IDENTICAL to an
    uninterrupted 2-worker control run over the same phase-gated
    corpus. fmstat renders RECOVERED, not DEGRADED: the cluster
    healed."""
    import signal
    from fast_tffm_tpu.checkpoint import CheckpointState
    from fast_tffm_tpu.config import load_config
    from fast_tffm_tpu.testing.faults import wait_until
    from fast_tffm_tpu.checkpoint import checkpoint_template
    workdir = os.path.abspath(workdir)
    lines_per, batch = 416, 32      # 13 EXACT steps per shard: batch
    steps_per = lines_per // batch  # grouping never spans shards, so
    # membership changes between shards cannot move batch boundaries
    shard_lines = [_corpus_lines(lines_per, seed * 10 + i)
                   for i in range(4)]

    def run_cluster(subdir: str, heal: bool) -> dict:
        """One phase-gated stream job over the 4 shards; with ``heal``
        the kill-then-grow sequence runs between shards 1 and 2 (ledger
        owners alternate 0,1,0,1 — shard 3 is consumed by the
        REPLACEMENT, proving the re-balance)."""
        os.makedirs(subdir, exist_ok=True)
        sd = os.path.join(subdir, "stream")
        os.makedirs(sd, exist_ok=True)
        model = os.path.join(subdir, "model", "fm")
        metrics = os.path.join(subdir, "metrics.jsonl")
        cfg_path = _write_grow_cfg(subdir, sd, model, metrics)
        ckpt_dir = model + ".ckpt"
        procs = _spawn_workers(subdir, cfg_path)
        joiner = None
        try:
            for i in (0, 1):
                _stage_shard(sd, i, shard_lines[i])
                _wait_published(ckpt_dir, steps_per * (i + 1),
                                procs=procs)
            if heal:
                # Mid-window kill: worker 1 sits in the lockstep
                # flags window (the stream idles between phases).
                procs[1][0].send_signal(signal.SIGKILL)
                wait_until(lambda: "elastic recovery complete"
                           in _worker_out(subdir, 0),
                           timeout=120, message="survivor shrinking")
                joiner = _spawn_joiner(subdir, cfg_path)
                wait_until(lambda: "input shards re-balanced"
                           in _worker_out(subdir, 0),
                           timeout=120, message="joiner admitted at "
                           "the publish settle")
            for i in (2, 3):
                _stage_shard(sd, i, shard_lines[i])
                _wait_published(
                    ckpt_dir, steps_per * (i + 1),
                    procs=[procs[0]] + ([joiner] if joiner else
                                        [procs[1]]))
            open(os.path.join(sd, "STOP"), "w").close()
            wait_until(lambda: procs[0][0].poll() is not None,
                       timeout=240, message="chief finishing")
            _raise_if_signal_death(procs[0][0], "at chief exit")
            if joiner is not None:
                wait_until(lambda: joiner[0].poll() is not None,
                           timeout=120, message="joiner finishing")
                _raise_if_signal_death(joiner[0], "at joiner exit")
        finally:
            _reap(procs)
            if joiner is not None:
                _reap([joiner])
        return {"cfg_path": cfg_path, "model": model,
                "metrics": metrics, "subdir": subdir,
                "joiner_rc": joiner[0].returncode if joiner else None,
                "chief_rc": procs[0][0].returncode}

    total = 4 * lines_per
    el = _retry_known_jaxlib_flake(
        lambda sub: run_cluster(sub, heal=True), workdir, "elastic")
    out0 = _worker_out(el["subdir"], 0)
    assert el["chief_rc"] == 0, f"chief failed:\n{out0[-3000:]}"
    assert el["joiner_rc"] == 0, (
        "joiner failed:\n"
        + open(os.path.join(el["subdir"], "joiner.out")).read()[-3000:])
    assert "worker lost" in out0 and "process 1" in out0, out0[-3000:]
    assert "elastic reform generation 1" in out0, out0[-3000:]
    assert "elastic grow generation 2" in out0, out0[-3000:]
    assert "training done" in out0, out0[-3000:]
    # Exactly-once across the membership changes: chief stream + the
    # DEAD worker's shard + the joiner's shard (two run segments in
    # the same .p1 file — the sink appends) sum to every line written.
    from fast_tffm_tpu.obs.attribution import health_verdict, summarize
    shards = [el["metrics"], el["metrics"] + ".p1"]
    assert os.path.exists(shards[1]), "worker-1 metrics shard missing"
    summary = summarize(shards)
    got = summary["counters"].get("train/examples")
    assert got == total, (got, total)
    statuses = [h.get("status") for h in summary["health_events"]]
    assert "worker_lost" in statuses, statuses
    kinds = [(h.get("kind"), h.get("status")) for h in
             summary["health_events"]
             if h.get("status") == "elastic_recovered"]
    assert ("shrink", "elastic_recovered") in kinds, kinds
    assert ("grow", "elastic_recovered") in kinds, kinds
    v = health_verdict(summary)["verdict"]
    assert v == "RECOVERED (gen 2, 2 workers)", v
    # Rendezvous litter: after 2 reforms only current-generation files
    # (and the live membership's leases) remain in the lease dir.
    hb_dir = os.path.abspath(el["model"]) + ".hb"
    litter = sorted(n for n in os.listdir(hb_dir)
                    if n.startswith(("reform-", "grow-", "commit-",
                                     "join-")))
    assert all(("-2-" in n or n.endswith("2.json")) for n in litter
               if n.startswith(("reform-", "grow-", "commit-"))), litter
    assert not [n for n in litter if n.startswith("join-")], litter
    # The control twin: an UNINTERRUPTED 2-worker run over the same
    # phase-gated corpus. Bit-identical final state pins that the
    # shrink+grow detour replayed nothing and skipped nothing.
    ct = _retry_known_jaxlib_flake(
        lambda sub: run_cluster(sub, heal=False), workdir, "control")
    assert ct["chief_rc"] == 0, _worker_out(ct["subdir"], 0)[-3000:]

    def final_state(run):
        cfg = load_config(run["cfg_path"])
        ckpt = CheckpointState(run["model"])
        restored = ckpt.restore(template=checkpoint_template(cfg))
        ckpt.close()
        return restored
    fe, fc = final_state(el), final_state(ct)
    assert int(fe["step"]) == int(fc["step"]) == 4 * steps_per, (
        int(fe["step"]), int(fc["step"]))
    for k in ("table", "acc"):
        a, b = np.asarray(fe[k]), np.asarray(fc[k])
        assert np.array_equal(a, b), (
            f"healed run's final {k} diverged from the uninterrupted "
            f"control: max |delta| = {np.abs(a - b).max()}")
    return (f"{total} lines consumed exactly once across SIGKILL -> "
            f"shrink (gen 1) -> --join grow (gen 2): final table "
            f"bit-identical to the uninterrupted 2-worker control at "
            f"step {int(fe['step'])}, verdict {v!r}, lease dir swept "
            "to current-generation files")


def scenario_grow_joiner_dies(workdir: str, seed: int = 0) -> str:
    """ISSUE 14 acceptance: a joiner SIGKILLed MID-RENDEZVOUS (after
    its announce, before the commit) never wedges the incumbents — the
    settle window expires, the dead joiner's lease is visibly stale,
    the reform COMMITS without it, and training continues to a clean
    finish. The stale ticket is never re-planned, and fmstat stays
    DEGRADED (the cluster never healed)."""
    import signal
    from fast_tffm_tpu.testing.faults import wait_until
    workdir = os.path.abspath(workdir)
    lines_per, batch = 416, 32
    steps_per = lines_per // batch

    def attempt(sub: str):
        sd = os.path.join(sub, "stream")
        os.makedirs(sd, exist_ok=True)
        model = os.path.join(sub, "model", "fm")
        metrics = os.path.join(sub, "metrics.jsonl")
        cfg_path = _write_grow_cfg(sub, sd, model, metrics,
                                   join_settle=2.5)
        ckpt_dir = model + ".ckpt"
        hb_dir = os.path.abspath(model) + ".hb"
        procs = _spawn_workers(sub, cfg_path)
        joiner = None
        try:
            _stage_shard(sd, 0, _corpus_lines(lines_per, seed))
            _wait_published(ckpt_dir, steps_per, procs=procs)
            procs[1][0].send_signal(signal.SIGKILL)
            wait_until(lambda: "elastic recovery complete"
                       in _worker_out(sub, 0),
                       timeout=120, message="survivor shrinking")
            joiner = _spawn_joiner(sub, cfg_path)

            def announced() -> bool:
                try:
                    return any(n.startswith("reform-2-")
                               and not n.startswith("reform-2-0")
                               for n in os.listdir(hb_dir))
                except OSError:
                    return False
            wait_until(announced, timeout=120, interval=0.005,
                       message="joiner announcing generation 2")
            # MID-RENDEZVOUS: announced, not yet committed (the settle
            # window always runs its full course — that is the
            # designed death-detection window). Kill it here.
            joiner[0].send_signal(signal.SIGKILL)
            wait_until(lambda: "never rendezvoused inside the settle "
                       "window" in _worker_out(sub, 0),
                       timeout=120, message="incumbent dropping the "
                       "dead joiner at the settle window")
            wait_until(lambda: "elastic recovery complete"
                       in _worker_out(sub, 0).split(
                           "never rendezvoused")[-1],
                       timeout=120, message="reform completing "
                       "without the dead joiner")
            # Training continues: the next shard is consumed and the
            # run finishes cleanly — the incumbents were never wedged.
            _stage_shard(sd, 1, _corpus_lines(lines_per, seed + 1))
            _wait_published(ckpt_dir, 2 * steps_per, procs=[procs[0]])
            open(os.path.join(sd, "STOP"), "w").close()
            wait_until(lambda: procs[0][0].poll() is not None,
                       timeout=240, message="survivor finishing")
            _raise_if_signal_death(procs[0][0], "at survivor exit")
        finally:
            _reap(procs)
            if joiner is not None:
                _reap([joiner])
        return sub, metrics, procs[0][0].returncode

    sub, metrics, rc0 = _retry_known_jaxlib_flake(attempt, workdir,
                                                  "run")
    out0 = _worker_out(sub, 0)
    assert rc0 == 0, out0[-3000:]
    assert "elastic grow generation 2: members [0]" in out0, (
        out0[-3000:])
    assert "training done" in out0, out0[-3000:]
    from fast_tffm_tpu.obs.attribution import health_verdict, summarize
    shards = [metrics] + ([metrics + ".p1"]
                          if os.path.exists(metrics + ".p1") else [])
    summary = summarize(shards)
    got = summary["counters"].get("train/examples")
    assert got == 2 * lines_per, (got, 2 * lines_per)
    grows = [h for h in summary["health_events"]
             if h.get("status") == "elastic_recovered"
             and h.get("kind") == "grow"]
    assert grows and grows[-1].get("members") == [0], grows
    v = health_verdict(summary)["verdict"]
    assert v == "DEGRADED (1 worker lost)", v
    return (f"joiner SIGKILLed mid-rendezvous: settle window dropped "
            f"it, reform committed [0] alone, survivor consumed all "
            f"{2 * lines_per} lines and finished (verdict {v!r}) — "
            "never wedged")


def scenario_oom_pressure(workdir: str, seed: int = 0) -> str:
    """Capacity wall under an injected HBM size (obs/memory.py): an
    oversized config is REFUSED by the pre-flight with the planner's
    per-owner breakdown (and the exact what-if invocation); a
    borderline config trains to completion while emitting
    ``health: hbm_pressure`` exactly once per episode, and fmstat
    renders the HBM-PRESSURE verdict."""
    from fast_tffm_tpu.obs.memory import FAKE_CAPACITY_ENV, LEDGER, plan
    from fast_tffm_tpu.train import train
    corpus = os.path.join(workdir, "train_oom.txt")
    _write_corpus(corpus, 400, seed)
    prev = os.environ.get(FAKE_CAPACITY_ENV)
    LEDGER.reset()
    try:
        # Leg 1: predicted resident bytes (a ~2 MB table) vs a 64 KB
        # injected capacity — refused at startup, never dispatched.
        big = _cfg(workdir, corpus, vocabulary_size=100000,
                   metrics_file=os.path.join(workdir,
                                             "metrics_big.jsonl"))
        os.environ[FAKE_CAPACITY_ENV] = str(64 * 1024)
        refused = False
        try:
            train(big)
        except ValueError as e:
            refused = True
            msg = str(e)
            assert "fmstat capacity" in msg, (
                f"pre-flight refusal must name the planner CLI: {msg}")
            assert "predicted device total" in msg, (
                f"pre-flight refusal must carry the breakdown: {msg}")
        assert refused, ("oversized config started under a 64 KB "
                         "injected capacity — pre-flight did not fire")
        LEDGER.reset()
        # Leg 2: borderline. The table+accumulator resident set is
        # ~60% of the injected capacity — above the 0.5 pressure
        # threshold at every flush (ONE episode, never re-armed), but
        # the full predicted set still FITS, so pre-flight lets it
        # run.
        cfg = _cfg(workdir, corpus, vocabulary_size=200000,
                   factor_num=8, mem_pressure_fraction=0.5)
        # (one device's share: the session row-shards both over the
        # mesh of every device here, and the ledger books the share)
        import jax
        p = plan(cfg, "train", shards=jax.device_count())
        resident = p["owners"]["table"] + p["owners"]["adagrad_acc"]
        cap = int(resident / 0.6)
        assert p["total_bytes"] <= cap, (
            "scenario shape drifted: the borderline config no longer "
            "fits its own injected capacity")
        os.environ[FAKE_CAPACITY_ENV] = str(cap)
        train(cfg)
        h = [e for e in (_summary(cfg).get("health_events") or [])
             if e.get("status") == "hbm_pressure"]
        assert len(h) == 1, (
            f"expected exactly 1 hbm_pressure episode event, got "
            f"{len(h)}")
        assert h[0].get("owners"), "pressure event lost its owner map"
        v = _verdict(cfg)
        assert v.startswith("HBM-PRESSURE"), v
    finally:
        if prev is None:
            os.environ.pop(FAKE_CAPACITY_ENV, None)
        else:
            os.environ[FAKE_CAPACITY_ENV] = prev
        LEDGER.reset()
    return ("pre-flight refused the oversized config with the planner "
            "breakdown; the borderline run trained under pressure with "
            "exactly one hbm_pressure episode and fmstat reads "
            "HBM-PRESSURE")


SCENARIOS: Dict[str, Callable[..., str]] = {
    "skip": scenario_skip,
    "quarantine": scenario_quarantine,
    "max-bad": scenario_max_bad,
    "flaky-open": scenario_flaky_open,
    "flaky-open-parallel": scenario_flaky_open_parallel,
    "predict-flaky": scenario_predict_flaky,
    "serve-soak": scenario_serve_soak,
    "kill-replica-midburst": scenario_kill_replica_midburst,
    "staggered-reload": scenario_staggered_reload,
    "preempt-resume": scenario_preempt_resume,
    "stream-soak": scenario_stream_soak,
    "slo-soak": scenario_slo_soak,
    "stream-truncate": scenario_stream_truncate,
    "vocab-churn": scenario_vocab_churn,
    "truncate-latest": scenario_truncate_latest,
    "kill-async-save": scenario_kill_async_save,
    "kill-worker-midwindow": scenario_kill_worker_midwindow,
    "hang-worker": scenario_hang_worker,
    "kill-then-grow": scenario_kill_then_grow,
    "grow-joiner-dies": scenario_grow_joiner_dies,
    "oom-pressure": scenario_oom_pressure,
}


def main(argv: List[str] = None) -> int:
    import argparse
    import sys
    import tempfile
    ap = argparse.ArgumentParser(
        prog="fmchaos", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("scenarios", nargs="*",
                    help="scenario names (default: all)")
    ap.add_argument("--list", action="store_true",
                    help="list scenarios and exit")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default=None,
                    help="keep artifacts here instead of a tempdir")
    args = ap.parse_args(argv)
    if args.list:
        for name in SCENARIOS:
            print(name)
        return 0
    # The chaos soaks run on CPU by contract (`make chaos` in CI): the
    # fault paths under test are host-side, and the scenarios must run
    # on machines with no accelerator.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    names = args.scenarios or list(SCENARIOS)
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        print(f"fmchaos: unknown scenario(s) {unknown}; "
              f"known: {list(SCENARIOS)}", file=sys.stderr)
        return 2
    failures = 0
    for name in names:
        if args.workdir:
            wd = os.path.join(args.workdir, name.replace("-", "_"))
            os.makedirs(wd, exist_ok=True)
            ctx = None
        else:
            ctx = tempfile.TemporaryDirectory(prefix=f"fmchaos_{name}_")
            wd = ctx.name
        try:
            detail = SCENARIOS[name](wd, seed=args.seed)
            print(f"PASS {name}: {detail}")
        except Exception as e:  # noqa: BLE001 - report, don't die
            failures += 1
            print(f"FAIL {name}: {type(e).__name__}: {e}",
                  file=sys.stderr)
        finally:
            if ctx is not None:
                ctx.cleanup()
    print(f"fmchaos: {len(names) - failures}/{len(names)} scenarios "
          "passed")
    return 1 if failures else 0
