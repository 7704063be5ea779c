"""Self-healing checkpoints (README "Checkpoint integrity & fallback"):
save-side integrity manifests, verified restore with quarantine +
last-good fallback, and the satellite coverage ISSUE 5 calls out
(export_npz pad-row slicing, the legacy-epoch both-attempts-fail path,
the fallback health verdict)."""

import json
import os

import numpy as np
import pytest

from fast_tffm_tpu.checkpoint import (CheckpointState, QUARANTINE_PREFIX,
                                      _restore_tolerating_legacy_epoch,
                                      compute_manifest, export_npz,
                                      list_step_dirs, manifest_path,
                                      read_manifest, verify_step_dir,
                                      write_manifest)
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.models.fm import init_accumulator, init_table
from fast_tffm_tpu.checkpoint import checkpoint_template, ckpt_state
from tests.orbax_caps import orbax_supports_partial_restore


def _mk_state(tmp_path, vocab=1000, **kw):
    cfg = FmConfig(vocabulary_size=vocab, factor_num=4,
                   model_file=str(tmp_path / "m" / "fm"))
    table, acc = ckpt_state(cfg, init_table(cfg), init_accumulator(cfg))
    ckpt = CheckpointState(cfg.model_file, **kw)
    return cfg, table, acc, ckpt


def _save(ckpt, cfg, table, acc, step, epoch=0, **kw):
    ckpt.save(step, table, acc, vocabulary_size=cfg.vocabulary_size,
              epoch=epoch, **kw)


# --- save-side: manifests --------------------------------------------------


def test_committed_save_writes_manifest_with_payload_echo(tmp_path):
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 5, epoch=2, wait=True)
    man = read_manifest(ckpt.directory, 5)
    assert man is not None
    assert man["step"] == 5 and man["epoch"] == 2
    assert man["vocab"] == cfg.vocabulary_size
    # every manifest entry matches the bytes on disk exactly
    step_dir = os.path.join(ckpt.directory, "5")
    assert man["files"], "manifest must list the step's files"
    for rel, info in man["files"].items():
        p = os.path.join(step_dir, rel)
        assert os.path.getsize(p) == info["size"]
    ckpt.close()


def test_async_save_manifest_flushes_on_close_and_next_save(tmp_path):
    """The manifest can only describe a FINALIZED step dir, so an async
    save owes its manifest until the commit is certain: the next save
    dispatches it (on a background thread — the hash is a full re-read
    that must not stall the train loop), and the synchronous settle
    points (wait_until_finished, close) guarantee it is on disk."""
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 1)           # async: manifest owed
    _save(ckpt, cfg, table, acc, 2)           # dispatches step 1's
    ckpt.wait_until_finished()                # joins 1's, settles 2's
    assert read_manifest(ckpt.directory, 1) is not None
    assert read_manifest(ckpt.directory, 2) is not None
    _save(ckpt, cfg, table, acc, 3)           # async again
    ckpt.close()                              # close settles step 3's
    assert read_manifest(ckpt.directory, 3) is not None


def test_manifests_pruned_with_gc_and_fresh_same_step_save(tmp_path):
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    ckpt2 = None
    try:
        for s in (10, 20, 30, 40):            # max_to_keep=3 drops 10
            _save(ckpt, cfg, table, acc, s, wait=True)
        assert not os.path.exists(manifest_path(ckpt.directory, 10))
        assert os.path.exists(manifest_path(ckpt.directory, 40))
    finally:
        ckpt.close()
    # cleared-and-reused dir: a stale same-step manifest describes the
    # OLD bytes and would brand the fresh save corrupt — it must go
    # before the fresh save's own manifest lands.
    stale = {"format": 1, "step": 50, "files": {"bogus": {
        "size": 1, "crc32": 0}}}
    write_manifest(ckpt.directory, 50, stale)
    ckpt2 = CheckpointState(cfg.model_file)
    try:
        _save(ckpt2, cfg, table, acc, 50, wait=True)
        man = read_manifest(ckpt2.directory, 50)
        assert "bogus" not in man["files"]
        assert ckpt2.verify_step(50) is None
    finally:
        ckpt2.close()


# --- verify ---------------------------------------------------------------


def test_verify_modes_size_and_full(tmp_path):
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 1, wait=True)
    assert ckpt.verify_step(1) is None
    assert verify_step_dir(ckpt.directory, 1, "full") is None
    # same-size bit flip: invisible to the size pass, caught by full
    man = read_manifest(ckpt.directory, 1)
    rel = max(man["files"], key=lambda r: man["files"][r]["size"])
    p = os.path.join(ckpt.directory, "1", rel)
    with open(p, "r+b") as fh:
        fh.seek(os.path.getsize(p) - 1)
        b = fh.read(1)
        fh.seek(-1, os.SEEK_CUR)
        fh.write(bytes([b[0] ^ 0xFF]))
    assert verify_step_dir(ckpt.directory, 1, "size") is None
    reason = verify_step_dir(ckpt.directory, 1, "full")
    assert reason and "crc32 mismatch" in reason
    assert verify_step_dir(ckpt.directory, 1, "off") is None
    # truncation: caught by the cheap size pass
    truncate_checkpoint(cfg.model_file, step=1)
    reason = ckpt.verify_step(1)
    assert reason and "size mismatch" in reason
    ckpt.close()


def test_verify_without_manifest_is_unverifiable_not_fail(tmp_path):
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 1, wait=True)
    os.remove(manifest_path(ckpt.directory, 1))
    assert ckpt.verify_step(1) is None  # pre-manifest steps restore
    ckpt.close()


def test_garbled_manifest_reads_as_corrupt(tmp_path):
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 1, wait=True)
    with open(manifest_path(ckpt.directory, 1), "w") as fh:
        fh.write("{not json")
    reason = ckpt.verify_step(1)
    assert reason and "manifest" in reason
    ckpt.close()


# --- restore: fallback + quarantine ---------------------------------------


def test_restore_falls_back_and_quarantines_torn_step(tmp_path):
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 1, epoch=0, wait=True)
    _save(ckpt, cfg, table, acc, 2, epoch=1, wait=True)
    victim = truncate_checkpoint(cfg.model_file)
    assert victim
    restored = ckpt.restore(template=checkpoint_template(cfg))
    assert int(restored["step"]) == 1
    assert int(restored["epoch"]) == 0
    # the bad step is renamed, never deleted — bytes survive for
    # forensics, and the torn file itself travels with the dir
    qdir = os.path.join(ckpt.directory, f"{QUARANTINE_PREFIX}2")
    assert os.path.isdir(qdir)
    rel = os.path.relpath(victim, os.path.join(ckpt.directory, "2"))
    assert os.path.exists(os.path.join(qdir, rel))
    assert os.path.exists(os.path.join(qdir, "QUARANTINE"))
    assert os.path.exists(os.path.join(qdir, "manifest-2.json"))
    assert list_step_dirs(ckpt.directory) == [1]
    # the manager's view follows: latest_step no longer offers step 2
    assert ckpt.latest_step() == 1
    ckpt.close()


def test_restore_exception_walks_back_without_manifest(tmp_path):
    """Steps too old to carry a manifest: verification can't see the
    tear, so the orbax restore error itself triggers quarantine +
    walk-back."""
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 1, wait=True)
    _save(ckpt, cfg, table, acc, 2, wait=True)
    for s in (1, 2):
        os.remove(manifest_path(ckpt.directory, s))
    truncate_checkpoint(cfg.model_file)  # tears step 2
    restored = ckpt.restore(template=checkpoint_template(cfg))
    assert int(restored["step"]) == 1
    assert os.path.isdir(os.path.join(ckpt.directory,
                                      f"{QUARANTINE_PREFIX}2"))
    ckpt.close()


def test_restore_last_candidate_error_raises_without_quarantine(tmp_path):
    """A restore failure on the LAST remaining step must stay a loud,
    actionable error (on a config mismatch it is the diagnosis for
    every step) — not a quarantine followed by a silent fresh start."""
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 1, wait=True)
    os.remove(manifest_path(ckpt.directory, 1))  # hide it from verify
    truncate_checkpoint(cfg.model_file, step=1)
    with pytest.raises(ValueError, match="could not be restored"):
        ckpt.restore(template=checkpoint_template(cfg))
    # still there, still named as a step — nothing was quarantined
    assert list_step_dirs(ckpt.directory) == [1]
    ckpt.close()


def test_restore_all_steps_failing_verification_raises(tmp_path):
    """Every step failing INTEGRITY must not silently turn into a
    fresh start: quarantine them, then raise naming fmckpt."""
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 1, wait=True)
    _save(ckpt, cfg, table, acc, 2, wait=True)
    truncate_checkpoint(cfg.model_file, step=1)
    truncate_checkpoint(cfg.model_file, step=2)
    with pytest.raises(ValueError, match="failed integrity"):
        ckpt.restore(template=checkpoint_template(cfg))
    assert list_step_dirs(ckpt.directory) == []
    names = sorted(os.listdir(ckpt.directory))
    assert f"{QUARANTINE_PREFIX}1" in names
    assert f"{QUARANTINE_PREFIX}2" in names
    ckpt.close()


def test_restore_empty_directory_still_fresh_start(tmp_path):
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    assert ckpt.restore(template=checkpoint_template(cfg)) is None
    ckpt.close()


def test_restore_explicit_step_verify_failure_raises_no_quarantine(
        tmp_path):
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 1, wait=True)
    _save(ckpt, cfg, table, acc, 2, wait=True)
    truncate_checkpoint(cfg.model_file, step=2)
    with pytest.raises(ValueError, match="never quarantined"):
        ckpt.restore(step=2, template=checkpoint_template(cfg))
    assert list_step_dirs(ckpt.directory) == [1, 2]
    ckpt.close()


def test_verify_off_restores_historical_behavior(tmp_path):
    """ckpt_verify=off: the torn newest step raises on restore (there
    is an older step, so the restore-exception walk-back still heals —
    off only disables the MANIFEST pass, not the exception fallback)."""
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    cfg, table, acc, ckpt = _mk_state(tmp_path, verify="off")
    _save(ckpt, cfg, table, acc, 1, wait=True)
    _save(ckpt, cfg, table, acc, 2, wait=True)
    truncate_checkpoint(cfg.model_file)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    assert int(restored["step"]) == 1
    ckpt.close()


def test_quarantine_suffix_on_repeat(tmp_path):
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 1, wait=True)
    ckpt.quarantine_step(1, "test A")
    _save(ckpt, cfg, table, acc, 1, wait=True, force=True)
    ckpt.quarantine_step(1, "test B")
    names = sorted(os.listdir(ckpt.directory))
    assert f"{QUARANTINE_PREFIX}1" in names
    assert f"{QUARANTINE_PREFIX}1.1" in names
    ckpt.close()


@pytest.mark.skipif(
    not orbax_supports_partial_restore(),
    reason="installed orbax lacks PyTreeRestore(partial_restore=)")
def test_restore_partial_skips_bad_latest(tmp_path):
    """The offload read path (restore_partial) goes through the same
    verified step decision: a torn latest step is quarantined and the
    previous one serves the partial read."""
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    _save(ckpt, cfg, table, acc, 1, wait=True)
    _save(ckpt, cfg, table, acc, 2, wait=True)
    truncate_checkpoint(cfg.model_file)
    template = checkpoint_template(cfg, host=True)
    template.pop("acc")
    restored = ckpt.restore_partial(template)
    assert int(restored["step"]) == 1
    assert "acc" not in restored
    ckpt.close()


# --- telemetry: the ckpt_fallback health event + counters -----------------


def test_fallback_emits_health_event_and_counters(tmp_path):
    from fast_tffm_tpu.obs.sink import read_events
    from fast_tffm_tpu.obs.telemetry import RunTelemetry, activate
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    stream = str(tmp_path / "metrics.jsonl")
    tel = RunTelemetry(stream, meta={"kind": "test"})
    with activate(tel):
        _save(ckpt, cfg, table, acc, 1, wait=True)
        _save(ckpt, cfg, table, acc, 2, wait=True)
        truncate_checkpoint(cfg.model_file)
        restored = ckpt.restore(template=checkpoint_template(cfg))
    assert int(restored["step"]) == 1
    tel.close(step=2)
    ckpt.close()
    events = list(read_events(stream))
    health = [e for e in events if e.get("event") == "health"]
    assert [h["status"] for h in health] == ["ckpt_fallback"]
    assert health[0]["step"] == 2
    assert "size mismatch" in health[0]["reason"]
    assert QUARANTINE_PREFIX + "2" in health[0]["quarantined"]
    last = [e for e in events if e.get("event") == "metrics"][-1]
    c = last["counters"]
    assert c["checkpoint/saves"] == 2
    assert c["checkpoint/fallbacks"] == 1
    assert c["checkpoint/quarantined_steps"] == 1


def test_same_step_collision_not_counted_as_save(tmp_path):
    """fmstat's "checkpoint saves" row means saves that WROTE state:
    the final save colliding with the last periodic save (orbax
    no-op) must not inflate it."""
    from fast_tffm_tpu.obs.telemetry import RunTelemetry, activate
    cfg, table, acc, ckpt = _mk_state(tmp_path)
    tel = RunTelemetry(str(tmp_path / "m.jsonl"), meta={"kind": "test"})
    with activate(tel):
        _save(ckpt, cfg, table, acc, 7, epoch=0, wait=True)
        _save(ckpt, cfg, table, acc, 7, epoch=1, wait=True, force=True,
              rewrite_stale_metadata=True)
    c = tel.registry.snapshot()["counters"]
    assert c["checkpoint/saves"] == 1
    tel.close(step=7)
    ckpt.close()


def test_health_verdict_ok_with_fallback_annotation():
    """ISSUE 5 satellite: a run that healed itself must not read as
    silently green — OK, but annotated — while real failures keep
    their severity."""
    from fast_tffm_tpu.obs.attribution import health_verdict
    summary = {
        "health_events": [{"status": "ckpt_fallback", "step": 13,
                           "quarantined": "/m/fm.ckpt/corrupt-13"}],
        "run_starts": 1, "run_ends": 1,
    }
    hv = health_verdict(summary)
    assert hv["verdict"] == "OK (ckpt fallback x1)"
    assert "13" in hv["detail"] and "fmckpt" in hv["detail"]
    crashed = dict(summary, crash_events=[{"error": "boom"}])
    assert health_verdict(crashed)["verdict"] == "CRASHED"
    preempted = dict(summary)
    preempted["health_events"] = summary["health_events"] + [
        {"status": "preempted", "step": 20, "epoch": 1}]
    assert health_verdict(preempted)["verdict"] == "PREEMPTED"


def test_fmstat_render_shows_checkpoint_rows():
    from fast_tffm_tpu.obs.attribution import attribution, render
    summary = {
        "counters": {"checkpoint/saves": 7, "checkpoint/fallbacks": 1,
                     "checkpoint/quarantined_steps": 2},
        "gauges": {}, "hists": {}, "health_events": [], "meta": {},
        "run_starts": 1, "run_ends": 1,
    }
    att = attribution(summary)
    assert att["checkpoint_saves"] == 7
    assert att["checkpoint_fallbacks"] == 1
    assert att["checkpoint_quarantined"] == 2
    text = render(summary)
    assert "checkpoint saves" in text
    assert "ckpt fallbacks / quarantined steps" in text


# --- ISSUE 5 satellite coverage -------------------------------------------


def test_export_npz_slices_mesh_divisibility_pad_rows(tmp_path):
    """vocabulary_size slicing must drop BOTH the sentinel pad row and
    the 4096-alignment pad rows a mesh-sharded table carries
    (documented in export_npz; previously untested)."""
    cfg = FmConfig(vocabulary_size=5000, factor_num=4,
                   model_file=str(tmp_path / "m" / "fm"))
    assert cfg.ckpt_rows == 8192  # 5001 rounded up — real pad tail
    D = cfg.row_dim
    table = np.arange(cfg.ckpt_rows * D,
                      dtype=np.float32).reshape(cfg.ckpt_rows, D)
    path = str(tmp_path / "sharded.npz")
    export_npz(table, path, vocabulary_size=cfg.vocabulary_size)
    arr = np.load(path)["table"]
    assert arr.shape == (cfg.vocabulary_size, D)
    np.testing.assert_array_equal(arr, table[:cfg.vocabulary_size])
    # without vocabulary_size only the single trailing pad row drops —
    # valid for unsharded [num_rows, D] tables only
    path2 = str(tmp_path / "unsharded.npz")
    export_npz(table[:cfg.num_rows], path2)
    arr2 = np.load(path2)["table"]
    assert arr2.shape == (cfg.vocabulary_size, D)
    np.testing.assert_array_equal(arr2, table[:cfg.vocabulary_size])


def test_restore_tolerating_legacy_epoch_both_attempts_fail():
    """Both the full-template attempt AND the epoch-less legacy retry
    fail: the caller gets the ORIGINAL error (the legacy retry's error
    would misdiagnose a genuine config mismatch), and exactly two
    attempts are made."""
    calls = []

    def do_restore(t):
        calls.append(frozenset(t))
        raise ValueError(f"attempt {len(calls)}")

    template = {"table": 1, "acc": 2, "epoch": 0}
    restored, err = _restore_tolerating_legacy_epoch(template, do_restore)
    assert restored is None
    assert str(err) == "attempt 1"
    assert calls == [frozenset({"table", "acc", "epoch"}),
                     frozenset({"table", "acc"})]
    # no epoch leaf -> no legacy retry to try: one attempt, same error
    calls.clear()
    restored, err = _restore_tolerating_legacy_epoch({"table": 1},
                                                     do_restore)
    assert restored is None and str(err) == "attempt 1"
    assert len(calls) == 1


def test_compute_manifest_matches_disk(tmp_path):
    d = tmp_path / "c.ckpt" / "7" / "sub"
    d.mkdir(parents=True)
    (d / "a.bin").write_bytes(b"x" * 1000)
    (d.parent / "b.bin").write_bytes(b"y" * 10)
    man = compute_manifest(str(tmp_path / "c.ckpt"), 7,
                           payload={"epoch": 3, "vocab": 9})
    assert man["epoch"] == 3 and man["vocab"] == 9
    assert man["files"]["sub/a.bin"]["size"] == 1000
    assert man["files"]["b.bin"]["size"] == 10
    assert json.dumps(man)  # JSON-serializable as written
