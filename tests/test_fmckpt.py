"""tools/fmckpt — the offline checkpoint-integrity CLI (ls / verify /
gc) against real CheckpointState-written directories."""

import json
import os

import numpy as np
import pytest

from fast_tffm_tpu.checkpoint import (CheckpointState, QUARANTINE_PREFIX,
                                      list_step_dirs, manifest_path)
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.models.fm import init_accumulator, init_table
from fast_tffm_tpu.checkpoint import ckpt_state
from tools.fmckpt import main, resolve_ckpt_dir, scan


def _mk_ckpt(tmp_path, steps=(1, 2)):
    cfg = FmConfig(vocabulary_size=500, factor_num=4,
                   model_file=str(tmp_path / "m" / "fm"))
    table, acc = ckpt_state(cfg, init_table(cfg), init_accumulator(cfg))
    ckpt = CheckpointState(cfg.model_file)
    for i, s in enumerate(steps):
        ckpt.save(s, table, acc, vocabulary_size=cfg.vocabulary_size,
                  wait=True, epoch=i)
    return cfg, ckpt


def test_resolve_accepts_model_file_and_dir(tmp_path):
    cfg, ckpt = _mk_ckpt(tmp_path)
    ckpt.close()
    d = resolve_ckpt_dir(cfg.model_file)
    assert d.endswith(".ckpt")
    assert resolve_ckpt_dir(d) == d
    with pytest.raises(FileNotFoundError):
        resolve_ckpt_dir(str(tmp_path / "nope"))


def test_missing_path_exits_2(tmp_path, capsys):
    assert main(["ls", str(tmp_path / "nope")]) == 2
    assert "no checkpoint directory" in capsys.readouterr().err


def test_ls_lists_steps_with_manifest_echo(tmp_path, capsys):
    cfg, ckpt = _mk_ckpt(tmp_path)
    ckpt.close()
    assert main(["ls", cfg.model_file]) == 0
    out = capsys.readouterr().out
    assert "step 1" in out and "step 2" in out
    assert "epoch=1 vocab=500" in out
    assert "NO MANIFEST" not in out


def test_ls_json_and_scan_flag_quarantine_and_orphans(tmp_path, capsys):
    cfg, ckpt = _mk_ckpt(tmp_path)
    ckpt.quarantine_step(2, "test")
    # orphan: a sidecar whose step no longer exists
    with open(manifest_path(ckpt.directory, 99), "w") as fh:
        fh.write("{}")
    ckpt.close()
    state = scan(ckpt.directory)
    assert [s["step"] for s in state["steps"]] == [1]
    assert [q["name"] for q in state["quarantined"]] == [
        f"{QUARANTINE_PREFIX}2"]
    assert state["orphans"] == ["manifest-99.json"]
    assert main(["ls", "--json", cfg.model_file]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["steps"][0]["step"] == 1
    assert rec["quarantined"][0]["name"] == f"{QUARANTINE_PREFIX}2"


def test_verify_pass_fail_and_exit_code(tmp_path, capsys):
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    cfg, ckpt = _mk_ckpt(tmp_path)
    ckpt.close()
    assert main(["verify", cfg.model_file]) == 0
    out = capsys.readouterr().out
    assert "step 1: OK" in out and "step 2: OK" in out
    truncate_checkpoint(cfg.model_file)  # tears step 2
    assert main(["verify", cfg.model_file]) == 1
    out = capsys.readouterr().out
    assert "step 1: OK" in out
    assert "step 2: FAIL" in out and "size mismatch" in out
    # single-step selection still passes for the intact one
    assert main(["verify", cfg.model_file, "--step", "1"]) == 0
    capsys.readouterr()


def test_verify_explicit_missing_step_fails(tmp_path, capsys):
    """A typo'd (or already-quarantined) --step must not read as
    'UNVERIFIABLE, restore accepts it' — restore would fail on it."""
    cfg, ckpt = _mk_ckpt(tmp_path, steps=(1,))
    ckpt.close()
    assert main(["verify", cfg.model_file, "--step", "14"]) == 1
    out = capsys.readouterr().out
    assert "step 14: MISSING" in out


def test_verify_reports_unmanifested_as_unverifiable(tmp_path, capsys):
    cfg, ckpt = _mk_ckpt(tmp_path, steps=(7,))
    os.remove(manifest_path(ckpt.directory, 7))
    ckpt.close()
    assert main(["verify", cfg.model_file]) == 0  # not a failure
    out = capsys.readouterr().out
    assert "UNVERIFIABLE" in out


def test_gc_dry_run_then_delete(tmp_path, capsys):
    cfg, ckpt = _mk_ckpt(tmp_path)
    qdir = ckpt.quarantine_step(2, "test")
    with open(manifest_path(ckpt.directory, 99), "w") as fh:
        fh.write("{}")
    # a killed manifest writer's litter: .tmp for a step that is gone
    tmp_litter = manifest_path(ckpt.directory, 98) + ".tmp"
    with open(tmp_litter, "w") as fh:
        fh.write("{")
    ckpt.close()
    assert main(["gc", cfg.model_file, "--dry-run"]) == 0
    out = capsys.readouterr().out
    assert "would delete" in out
    assert os.path.isdir(qdir)  # dry run touched nothing
    assert main(["gc", cfg.model_file]) == 0
    out = capsys.readouterr().out
    assert "deleted" in out
    assert not os.path.exists(qdir)
    assert not os.path.exists(manifest_path(ckpt.directory, 99))
    assert not os.path.exists(tmp_litter)
    # committed steps and their manifests are never gc'd
    assert list_step_dirs(ckpt.directory) == [1]
    assert os.path.exists(manifest_path(ckpt.directory, 1))


def test_ls_and_verify_cover_vocab_sidecar(tmp_path, capsys):
    """ISSUE 12 satellite: steps carrying a vocab admission sidecar get
    a +VOCAB mark in ls, verify re-checks the sidecar's embedded crc32
    (OK note on the good step), and a garbled sidecar is a verify FAIL
    — an admit-mode restore would silently fall back to fresh
    admission state, so the operator must see it before pointing a
    scorer at the step."""
    import re

    import numpy as np

    from fast_tffm_tpu.checkpoint import vocab_sidecar_path
    from fast_tffm_tpu.vocab.sketch import CountMinSketch
    from fast_tffm_tpu.vocab.table import VocabRuntime

    cfg = FmConfig(vocabulary_size=500, factor_num=4,
                   model_file=str(tmp_path / "m" / "fm"))
    table, acc = ckpt_state(cfg, init_table(cfg), init_accumulator(cfg))
    ckpt = CheckpointState(cfg.model_file)
    from types import SimpleNamespace
    rt = VocabRuntime(cfg.vocabulary_size, cfg.pad_id, 2.0, 0.5,
                      CountMinSketch(width=256))
    for _ in range(4):
        rt.note_trained(SimpleNamespace(
            vocab_obs=np.array([11, 22], np.int64)))
    rt.barrier(None)
    assert rt.live_rows == 2  # the sidecar under test is non-trivial
    ckpt.save(1, table, acc, vocabulary_size=cfg.vocabulary_size,
              wait=True, epoch=0)
    ckpt.save(2, table, acc, vocabulary_size=cfg.vocabulary_size,
              wait=True, epoch=0, vocab_state=rt.state_payload())
    ckpt.close()
    assert main(["ls", cfg.model_file]) == 0
    out = capsys.readouterr().out
    lines = {int(m.group(1)): line for line in out.splitlines()
             if (m := re.search(r"step (\d+)", line))}
    assert "+VOCAB" not in lines[1]
    assert "+VOCAB" in lines[2]
    assert main(["verify", cfg.model_file]) == 0
    out = capsys.readouterr().out
    assert "step 2: OK" in out and "+vocab crc OK" in out
    # Garble the sidecar: verify must FAIL the step and exit 1 — and
    # publish must refuse to point a scorer fleet at it (every
    # admit-mode reload of the step would raise).
    with open(vocab_sidecar_path(ckpt.directory, 2), "wb") as fh:
        fh.write(b"not gzip at all")
    assert main(["verify", cfg.model_file]) == 1
    out = capsys.readouterr().out
    assert "step 2: FAIL" in out and "vocab sidecar" in out
    from fast_tffm_tpu.checkpoint import read_published
    assert main(["publish", cfg.model_file, "2"]) == 1
    out = capsys.readouterr().out
    assert "vocab sidecar" in out and "pointer untouched" in out
    assert read_published(ckpt.directory) is None
