"""Streaming run mode (data/stream.py; README "Streaming / online
learning"): tracker hostile-filesystem behavior (torn-tail holdback,
seal policies, truncation/rotation/deletion), exactly-once watermark
checkpointing — including through a quarantine walk-back to an older
step — serial-vs-parallel stream parity, publishing, and the fmstat
STREAMING surface. The end-to-end soaks (live writer, SIGTERM+resume,
flaky opens) live in tools/fmchaos (`stream-soak` / `stream-truncate`)
and run under tier-1 via tests/test_chaos.py."""

import json
import os
import time

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data import stream as sl
from fast_tffm_tpu.data.badlines import BadLineTracker


def _write_lines(path, lines, append=False, newline_end=True):
    with open(path, "a" if append else "w") as fh:
        fh.write("\n".join(lines) + ("\n" if newline_end else ""))


def _numbered(lo, hi):
    """Distinct one-feature lines: line j carries exactly feature j,
    so a batch's uniq_ids names exactly the lines it holds."""
    return [f"{j % 2} {j}:1" for j in range(lo, hi)]


def _cfg(stream_dir, **kw):
    base = dict(vocabulary_size=4096, factor_num=2, batch_size=8,
                run_mode="stream", stream_dir=stream_dir,
                stream_poll_seconds=0.01, seal_policy="done",
                shuffle=False, seed=0)
    base.update(kw)
    return FmConfig(**base)


def _drain(src, limit=10000):
    out = []
    while len(out) < limit:
        b = src.next_batch(block=True)
        if b is sl.DONE:
            return out
        out.append(b)
    raise AssertionError("stream never drained")


def _batch_ids(batch, pad_id):
    if batch.uniq_ids is None:
        ids = np.asarray(batch.local_idx).ravel()
    else:
        ids = np.asarray(batch.uniq_ids)
    return sorted(int(i) for i in ids[ids != pad_id])


# --- config surface -------------------------------------------------------


def test_stream_config_validation():
    with pytest.raises(ValueError, match="requires stream_dir"):
        FmConfig(run_mode="stream")
    with pytest.raises(ValueError, match="run_mode is 'epochs'"):
        FmConfig(stream_dir="/tmp/x")
    with pytest.raises(ValueError, match="seal_policy"):
        FmConfig(run_mode="stream", stream_dir="/tmp/x",
                 seal_policy="nope")
    with pytest.raises(ValueError, match="weight_files"):
        FmConfig(run_mode="stream", stream_dir="/tmp/x",
                 weight_files=("w",))
    with pytest.raises(ValueError, match="train_files"):
        FmConfig(run_mode="stream", stream_dir="/tmp/x",
                 train_files=("a",))
    with pytest.raises(ValueError, match="stream_poll_seconds"):
        FmConfig(run_mode="stream", stream_dir="/tmp/x",
                 stream_poll_seconds=0)


def test_stream_knobs_load_from_ini(tmp_path):
    from fast_tffm_tpu.config import load_config
    p = tmp_path / "s.cfg"
    p.write_text("""
[Train]
run_mode = stream
stream_dir = /data/arriving
stream_poll_seconds = 7.5
seal_policy = quiet
publish_interval_seconds = 120
""")
    cfg = load_config(str(p))
    assert cfg.run_mode == "stream"
    assert cfg.stream_dir == "/data/arriving"
    assert cfg.stream_poll_seconds == 7.5
    assert cfg.seal_policy == "quiet"
    assert cfg.publish_interval_seconds == 120.0


# --- tracker: hostile filesystem ------------------------------------------


def test_torn_trailing_line_held_back(tmp_path):
    sd = tmp_path / "s"
    sd.mkdir()
    p = sd / "a.txt"
    p.write_text("1 1:1\n0 2:1\n1 3:")  # torn third line
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    chunks = tr.poll()
    released = b"".join(c for _, c in chunks)
    assert released == b"1 1:1\n0 2:1\n"  # torn tail held back
    time.sleep(0.02)
    assert tr.poll() == []  # still torn: nothing new
    with open(p, "a") as fh:
        fh.write("1\n0 4:1\n")  # complete the line + one more
    time.sleep(0.02)
    chunks = tr.poll()
    assert b"".join(c for _, c in chunks) == b"1 3:1\n0 4:1\n"


def test_seal_done_marker_flushes_newlineless_tail(tmp_path):
    sd = tmp_path / "s"
    sd.mkdir()
    p = sd / "a.txt"
    p.write_text("1 1:1\n0 2:1")  # final line has no newline
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    assert b"".join(c for _, c in tr.poll()) == b"1 1:1\n"
    (sd / "a.txt.done").touch()
    time.sleep(0.02)
    # Sealed: the newline-less final line is released with a
    # synthesized terminator, and the file reaches EOF state.
    assert b"".join(c for _, c in tr.poll()) == b"0 2:1\n"
    assert tr.files[0].sealed and tr.files[0].eof
    assert tr.files[0].end == p.stat().st_size


def test_seal_quiet_mtime(tmp_path):
    sd = tmp_path / "s"
    sd.mkdir()
    p = sd / "a.txt"
    p.write_text("1 1:1\n")
    tr = sl.StreamTracker(str(sd), 0.01, "quiet")
    tr.poll()
    assert not tr.files[0].sealed  # mtime is fresh
    old = time.time() - 10  # far beyond 3 x poll_seconds
    os.utime(p, (old, old))
    time.sleep(0.02)
    tr.poll()
    assert tr.files[0].sealed


def test_truncation_detected_and_quarantined(tmp_path):
    sd = tmp_path / "s"
    sd.mkdir()
    p = sd / "a.txt"
    p.write_text("\n".join(f"1 {i}:1" for i in range(20)) + "\n")
    bad = BadLineTracker("quarantine", 0.9,
                         quarantine_file=str(tmp_path / "q.jsonl"))
    tr = sl.StreamTracker(str(sd), 0.01, "done", bad_lines=bad)
    released = b"".join(c for _, c in tr.poll())
    assert released.count(b"\n") == 20
    with open(p, "r+") as fh:
        fh.truncate(10)  # shrink WAY below what was read
    time.sleep(0.02)
    assert tr.poll() == []
    fs = tr.files[0]
    assert fs.dead and fs.eof
    assert bad.bad == 1
    recs = [json.loads(ln)
            for ln in open(tmp_path / "q.jsonl") if ln.strip()]
    assert recs[0]["file"] == str(p)
    assert "truncated" in recs[0]["error"]
    bad.close()


def test_restored_sealed_file_shrunk_below_end_goes_dead(tmp_path):
    """A SEALED file that shrank below its recorded size while the run
    was down must go dead (quarantine-grade), not wedge the
    strict-order stream in silent IDLE forever waiting for bytes that
    will never exist."""
    sd = tmp_path / "s"
    sd.mkdir()
    p = sd / "a.txt"
    _write_lines(p, _numbered(0, 20))
    size = p.stat().st_size
    wm = {"format": 1, "files": [
        {"path": str(p), "bytes": 40, "lines": 8, "sealed": True,
         "dead": False, "end": size}]}
    with open(p, "r+") as fh:
        fh.truncate(60)  # below end, above the resume offset
    tr = sl.StreamTracker(str(sd), 0.01, "done", watermark=wm)
    assert tr.poll() == []
    assert tr.files[0].dead and tr.files[0].eof
    (sd / "STOP").touch()
    time.sleep(0.02)
    tr.poll()
    assert tr.finished  # the stream can still end


def test_poll_budget_streams_backlog_in_bounded_rounds(tmp_path,
                                                      monkeypatch):
    """A large sealed backlog is read across polls under
    MAX_POLL_BYTES, never materialized whole — and the reassembled
    bytes are exact."""
    monkeypatch.setattr(sl, "MAX_POLL_BYTES", 64)
    sd = tmp_path / "s"
    sd.mkdir()
    p = sd / "a.txt"
    _write_lines(p, _numbered(0, 30))  # ~200 bytes >> 64
    (sd / "a.txt.done").touch()
    tr = sl.StreamTracker(str(sd), 0.001, "done")
    got = b""
    rounds = 0
    while not tr.files or not tr.files[0].eof:
        time.sleep(0.002)
        chunks = tr.poll()
        for _, c in chunks:
            assert len(c) <= 64 + 80  # budget + one held-back line
            got += c
        rounds += 1
        assert rounds < 100
    assert rounds > 2  # genuinely split across polls
    assert got == p.read_bytes()
    assert tr.files[0].end == p.stat().st_size  # seal size = full size


def test_deleted_file_skipped_not_crashed(tmp_path):
    sd = tmp_path / "s"
    sd.mkdir()
    p = sd / "a.txt"
    p.write_text("1 1:1\n")
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    tr.poll()
    p.unlink()
    time.sleep(0.02)
    assert tr.poll() == []
    assert tr.files[0].dead  # logged + frozen, never raised


def test_strict_ledger_order_blocks_behind_open_head(tmp_path):
    """A sealed later shard must NOT be consumed past an open head —
    the stream is a log (and the bit-identity-with-control contract
    depends on it)."""
    sd = tmp_path / "s"
    sd.mkdir()
    (sd / "a.txt").write_text("1 1:1\n")  # open (unsealed) head
    (sd / "b.txt").write_text("1 2:1\n")
    (sd / "b.txt.done").touch()
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    chunks = tr.poll()
    paths = [tr.path(i) for i, _ in chunks]
    assert paths == [str(sd / "a.txt")]  # b waits behind the open head


def test_stop_marker_force_seals_and_finishes(tmp_path):
    sd = tmp_path / "s"
    sd.mkdir()
    (sd / "a.txt").write_text("1 1:1\n0 2:1\n")
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    tr.poll()
    assert not tr.finished
    (sd / "STOP").touch()
    time.sleep(0.02)
    tr.poll()
    assert tr.files[0].sealed
    assert tr.finished


# --- source: exactly-once watermarks --------------------------------------


def test_batches_carry_exact_positions(tmp_path):
    sd = tmp_path / "s"
    sd.mkdir()
    _write_lines(sd / "a.txt", _numbered(0, 20))
    (sd / "a.txt.done").touch()
    (sd / "STOP").touch()
    cfg = _cfg(str(sd))
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    src = sl.StreamSource(cfg, tr)
    batches = _drain(src)
    assert [b.num_real for b in batches] == [8, 8, 4]
    for k, b in enumerate(batches):
        rec = b.stream_pos["files"][0]
        want_lines = min((k + 1) * 8, 20)
        assert rec["lines"] == want_lines
        assert rec["bytes"] == sum(
            len(ln) + 1 for ln in _numbered(0, want_lines))
        assert _batch_ids(b, cfg.pad_id) == list(
            range(k * 8, want_lines))
    src.close()


def test_resume_from_mid_file_watermark_exact_next_batch(tmp_path):
    """The satellite contract: restore at an arbitrary mid-file offset
    and the next emitted batch starts at EXACTLY the right line."""
    sd = tmp_path / "s"
    sd.mkdir()
    _write_lines(sd / "a.txt", _numbered(0, 30))
    (sd / "a.txt.done").touch()
    (sd / "STOP").touch()
    cfg = _cfg(str(sd))
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    src = sl.StreamSource(cfg, tr)
    b1 = src.next_batch(block=True)
    wm = b1.stream_pos  # mid-file: 8 of 30 lines
    src.close()
    tr2 = sl.StreamTracker(str(sd), 0.01, "done", watermark=wm)
    src2 = sl.StreamSource(cfg, tr2)
    b2 = src2.next_batch(block=True)
    assert _batch_ids(b2, cfg.pad_id) == list(range(8, 16))
    src2.close()


def test_watermark_checkpoint_roundtrip_and_walkback(tmp_path):
    """Watermarks ride checkpoints: save at a mid-file offset, restore,
    and the stream resumes at exactly the right line — INCLUDING
    through the PR 4 quarantine walk-back to an older step, whose
    older watermark re-reads (never skips)."""
    from fast_tffm_tpu.checkpoint import (CheckpointState,
                                          read_watermark)
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    from fast_tffm_tpu.checkpoint import checkpoint_template
    sd = tmp_path / "s"
    sd.mkdir()
    _write_lines(sd / "a.txt", _numbered(0, 40))
    (sd / "a.txt.done").touch()
    (sd / "STOP").touch()
    cfg = _cfg(str(sd), model_file=str(tmp_path / "m" / "fm"))
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    src = sl.StreamSource(cfg, tr)
    batches = _drain(src)
    src.close()
    wm5 = batches[0].stream_pos   # after line 8
    wm10 = batches[2].stream_pos  # after line 24
    table = np.zeros((cfg.ckpt_rows, cfg.row_dim), np.float32)
    acc = np.full((cfg.ckpt_rows, cfg.row_dim), 0.1, np.float32)
    ckpt = CheckpointState(cfg.model_file)
    ckpt.save(5, table, acc, vocabulary_size=cfg.vocabulary_size,
              wait=True, stream_state=wm5)
    ckpt.save(10, table, acc, vocabulary_size=cfg.vocabulary_size,
              wait=True, stream_state=wm10)
    ckpt.close()
    ckpt_dir = cfg.model_file + ".ckpt"
    assert read_watermark(ckpt_dir, 5) == wm5
    assert read_watermark(ckpt_dir, 10) == wm10
    # Clean restore: newest step's watermark.
    ckpt = CheckpointState(cfg.model_file)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    assert int(restored["step"]) == 10
    assert restored["stream"] == wm10
    # Tear step 10; the verified restore must quarantine it, fall back
    # to step 5, and hand back the OLDER watermark (re-reads, never
    # skips) — its sidecar travels into the quarantine dir.
    truncate_checkpoint(cfg.model_file, step=10)
    ckpt = CheckpointState(cfg.model_file)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    assert int(restored["step"]) == 5
    assert restored["stream"] == wm5
    assert read_watermark(ckpt_dir, 10) is None
    assert os.path.exists(os.path.join(ckpt_dir, "corrupt-10",
                                       "watermark-10.json"))
    # And the resumed source starts at exactly wm5's next line.
    tr2 = sl.StreamTracker(str(sd), 0.01, "done",
                           watermark=restored["stream"])
    src2 = sl.StreamSource(cfg, tr2)
    nxt = src2.next_batch(block=True)
    assert _batch_ids(nxt, cfg.pad_id) == list(range(8, 16))
    src2.close()


def test_epoch_mode_checkpoints_carry_no_watermark(tmp_path):
    from fast_tffm_tpu.checkpoint import CheckpointState
    from fast_tffm_tpu.checkpoint import checkpoint_template
    cfg = FmConfig(vocabulary_size=256, factor_num=2,
                   model_file=str(tmp_path / "m" / "fm"))
    table = np.zeros((cfg.ckpt_rows, cfg.row_dim), np.float32)
    ckpt = CheckpointState(cfg.model_file)
    ckpt.save(3, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    assert restored["stream"] is None


# --- serial vs parallel stream parity -------------------------------------


def test_host_threads_parity_bit_identical(tmp_path):
    """host_threads > 1 in stream mode (sealed groups through the PR 7
    ring) must emit the BIT-IDENTICAL batch stream — arrays and
    watermark tags — as the serial stream path."""
    from fast_tffm_tpu.data import cparser
    if not cparser.available():
        pytest.skip("C++ extension unavailable")
    sd = tmp_path / "s"
    sd.mkdir()
    rng = np.random.default_rng(3)
    for i in range(3):
        lines = []
        for j in range(60):
            nnz = int(rng.integers(1, 6))
            ids = rng.choice(500, size=nnz, replace=False)
            lines.append(" ".join([str(j % 2)]
                                  + [f"{k}:{rng.random():.3f}"
                                     for k in ids]))
        _write_lines(sd / f"p{i}.txt", lines)
        (sd / f"p{i}.txt.done").touch()
    (sd / "STOP").touch()
    cfg = _cfg(str(sd), vocabulary_size=512, batch_size=16)

    def run(workers):
        tr = sl.StreamTracker(str(sd), 0.01, "done")
        src = sl.StreamSource(cfg, tr, workers=workers)
        out = _drain(src)
        src.close()
        return out

    serial, parallel = run(1), run(4)
    assert len(serial) == len(parallel)
    for a, b in zip(serial, parallel):
        assert a.num_real == b.num_real
        np.testing.assert_array_equal(a.labels, b.labels)
        np.testing.assert_array_equal(a.local_idx, b.local_idx)
        np.testing.assert_array_equal(a.vals, b.vals)
        np.testing.assert_array_equal(a.uniq_ids, b.uniq_ids)
        assert a.stream_pos == b.stream_pos


def test_stream_workers_routing():
    cfg = _cfg("/tmp/x", host_threads=4)
    from fast_tffm_tpu.data import cparser
    want = 4 if cparser.available() else 1
    assert sl.stream_workers(cfg) == want
    # fixed-U lockstep and tolerant policies stay serial-feed
    assert sl.stream_workers(cfg, fixed_shape=True) == 1
    assert sl.stream_workers(
        _cfg("/tmp/x", host_threads=4,
             bad_line_policy="skip")) == 1


def test_unlimited_features_routes_generic(tmp_path):
    """max_features_per_example = 0 ("unlimited") must ride the
    generic path in stream mode exactly as it does under epochs: the
    C++ builder writes fixed-stride rows and would silently truncate
    long examples at the ladder cap — the same corpus must train the
    same model regardless of run_mode."""
    sd = tmp_path / "s"
    sd.mkdir()
    # One example wider than the default ladder top (256).
    wide = "1 " + " ".join(f"{i}:1" for i in range(300))
    _write_lines(sd / "a.txt", [wide] + _numbered(1000, 1007))
    (sd / "a.txt.done").touch()
    (sd / "STOP").touch()
    cfg = _cfg(str(sd), max_features_per_example=0,
               vocabulary_size=4096)
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    src = sl.StreamSource(cfg, tr)
    assert not src._fast  # generic route: no silent truncation
    b = src.next_batch(block=True)
    src.close()
    # All 300 features of the wide example survive.
    assert 300 + 7 == len(_batch_ids(b, cfg.pad_id))


def test_probe_accepts_quiet_sealed_backlog(tmp_path):
    """Under seal_policy = quiet the startup probe must treat an
    mtime-quiet backlog as probeable — fs.sealed is always False
    before any tracker service, and falling back to the default
    bucket on a dense non-empty backlog means chronic spills."""
    sd = tmp_path / "s"
    sd.mkdir()
    # Dense lines: ~40 uniques per 8-example batch per line cluster.
    lines = []
    for j in range(64):
        ids = range(j * 40, j * 40 + 40)
        lines.append("1 " + " ".join(f"{i}:1" for i in ids))
    _write_lines(sd / "a.txt", lines)
    old = time.time() - 60
    os.utime(sd / "a.txt", (old, old))
    cfg = _cfg(str(sd), seal_policy="quiet", vocabulary_size=1 << 14,
               max_features_per_example=64, bucket_ladder=(64,))
    tr = sl.StreamTracker(str(sd), 0.01, "quiet")
    bucket = sl.probe_stream_uniq_bucket(cfg, tr)
    # 8 examples x 40 fresh ids = 320 uniques -> probe picks >= 2x,
    # never the empty-stream fallback driven by density it never saw.
    assert bucket >= 512, bucket


# --- generic tolerant path ------------------------------------------------


def test_tolerant_stream_skips_bad_lines_with_exact_positions(tmp_path):
    sd = tmp_path / "s"
    sd.mkdir()
    lines = _numbered(0, 16)
    lines[5] = "##bad## nope"
    _write_lines(sd / "a.txt", lines)
    (sd / "a.txt.done").touch()
    (sd / "STOP").touch()
    cfg = _cfg(str(sd), bad_line_policy="skip")
    bad = BadLineTracker("skip", 0.9)
    tr = sl.StreamTracker(str(sd), 0.01, "done", bad_lines=bad)
    src = sl.StreamSource(cfg, tr, bad_lines=bad)
    batches = _drain(src)
    src.close()
    assert [b.num_real for b in batches] == [7, 8]
    assert bad.bad == 1 and bad.total == 16
    got = sorted(i for b in batches
                 for i in _batch_ids(b, cfg.pad_id))
    assert got == [i for i in range(16) if i != 5]
    # Final watermark covers the whole file despite the dropped line.
    assert batches[-1].stream_pos["files"][0]["lines"] == 16


def test_tolerant_stream_positions_across_polls(tmp_path):
    """The generic path's decode cursor must CONTINUE across poll
    rounds: a file released in several chunks (the normal tailing
    case) tags later lines with absolute offsets, not offsets
    restarted at the last emitted batch."""
    sd = tmp_path / "s"
    sd.mkdir()
    p = sd / "a.txt"
    _write_lines(p, _numbered(0, 6))  # below one batch: no emission
    cfg = _cfg(str(sd), bad_line_policy="skip")
    bad = BadLineTracker("skip", 0.9)
    tr = sl.StreamTracker(str(sd), 0.01, "done", bad_lines=bad)
    src = sl.StreamSource(cfg, tr, bad_lines=bad)
    assert src.next_batch() is sl.IDLE  # 6 pending lines buffered
    _write_lines(p, _numbered(6, 20), append=True)  # second chunk
    (sd / "a.txt.done").touch()
    (sd / "STOP").touch()
    time.sleep(0.02)
    batches = _drain(src)
    src.close()
    assert [b.num_real for b in batches] == [8, 8, 4]
    total_bytes = p.stat().st_size
    for k, b in enumerate(batches):
        rec = b.stream_pos["files"][0]
        want = min((k + 1) * 8, 20)
        assert rec["lines"] == want, (k, rec)
        assert rec["bytes"] == sum(
            len(ln) + 1 for ln in _numbered(0, want)), (k, rec)
    assert batches[-1].stream_pos["files"][0]["bytes"] == total_bytes


# --- publishing -----------------------------------------------------------


def test_publish_step_verified_pointer_flip(tmp_path):
    from fast_tffm_tpu.checkpoint import (CheckpointState,
                                          read_published)
    cfg = FmConfig(vocabulary_size=256, factor_num=2,
                   model_file=str(tmp_path / "m" / "fm"))
    table = np.zeros((cfg.ckpt_rows, cfg.row_dim), np.float32)
    ckpt = CheckpointState(cfg.model_file)
    ckpt.save(1, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    assert ckpt.publish_step(1) is not None
    ckpt_dir = cfg.model_file + ".ckpt"
    assert read_published(ckpt_dir) == 1
    ckpt.save(2, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    assert ckpt.publish_step(2) is not None
    assert read_published(ckpt_dir) == 2
    # A torn step must NOT be published: pointer stays at the last
    # good step.
    ckpt.save(3, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    truncate_checkpoint(cfg.model_file, step=3)
    assert ckpt.publish_step(3) is None
    assert read_published(ckpt_dir) == 2
    ckpt.close()


def test_published_at_risk_tracks_retention(tmp_path):
    """Retention must never lap the published pointer: at_risk fires
    one save BEFORE max_to_keep eviction would delete the published
    step (and immediately when the pointer already dangles)."""
    from fast_tffm_tpu.checkpoint import CheckpointState
    cfg = FmConfig(vocabulary_size=256, factor_num=2,
                   model_file=str(tmp_path / "m" / "fm"))
    table = np.zeros((cfg.ckpt_rows, cfg.row_dim), np.float32)
    ckpt = CheckpointState(cfg.model_file)  # max_to_keep = 3
    assert not ckpt.published_at_risk()  # nothing published yet
    ckpt.save(1, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    ckpt.publish_step(1)
    assert not ckpt.published_at_risk()
    ckpt.save(2, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    assert not ckpt.published_at_risk()  # 1 newer step: still safe
    ckpt.save(3, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    # 2 newer steps with max_to_keep=3: the NEXT save evicts step 1.
    assert ckpt.published_at_risk()
    ckpt.publish_step(3)
    assert not ckpt.published_at_risk()
    ckpt.close()


def test_rotated_file_detected_across_restart(tmp_path):
    """The watermark persists each file's inode, so a same-path
    rewrite while the run was DOWN is caught like an in-run rotation
    (dead + quarantine-grade) instead of resuming mid-file into
    unrelated content."""
    sd = tmp_path / "s"
    sd.mkdir()
    p = sd / "a.txt"
    _write_lines(p, _numbered(0, 20))
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    cfg = _cfg(str(sd))
    src = sl.StreamSource(cfg, tr)
    wm = src.next_batch(block=True).stream_pos
    src.close()
    assert wm["files"][0]["ino"] == p.stat().st_ino
    # Rewrite the path with NEW content on a NEW inode, same-or-larger
    # size (the case a size check alone cannot see). The hardlink
    # keeps the old inode allocated so the filesystem can't recycle
    # it for the replacement (it would in this fresh tmpdir).
    os.link(p, sd / ".pin-old-inode")  # dotfile: discovery skips it
    p.unlink()
    _write_lines(p, ["0 777:1"] * 40)
    bad = BadLineTracker("skip", 0.9)
    tr2 = sl.StreamTracker(str(sd), 0.01, "done", bad_lines=bad,
                           watermark=wm)
    assert tr2.poll() == []
    assert tr2.files[0].dead
    assert bad.bad == 1
    bad.close()


def test_fmckpt_ls_shows_published_and_watermark(tmp_path, capsys):
    from fast_tffm_tpu.checkpoint import CheckpointState
    from tools.fmckpt import cmd_ls, scan
    cfg = FmConfig(vocabulary_size=256, factor_num=2,
                   model_file=str(tmp_path / "m" / "fm"))
    table = np.zeros((cfg.ckpt_rows, cfg.row_dim), np.float32)
    ckpt = CheckpointState(cfg.model_file)
    ckpt.save(1, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True,
              stream_state={"format": 1, "files": []})
    ckpt.publish_step(1)
    ckpt.close()
    ckpt_dir = cfg.model_file + ".ckpt"
    state = scan(ckpt_dir)
    assert state["published"] == 1
    assert state["steps"][0]["watermark"] is True
    cmd_ls(ckpt_dir)
    out = capsys.readouterr().out
    assert "PUBLISHED" in out and "+watermark" in out


# --- fmstat / health ------------------------------------------------------


def _stream_summary(age, interval, run_end=True):
    return {"counters": {"stream/files_discovered": 3,
                         "stream/publishes": 2},
            "gauges": {"stream/last_publish_age_seconds": age,
                       "stream/publish_interval_seconds": interval},
            "hists": {}, "health_events": [], "crash_events": [],
            "run_starts": 1, "run_ends": 1 if run_end else 0,
            "gauges_by_process": {}, "scalars": [], "meta": {}}


def test_stale_publish_verdict():
    from fast_tffm_tpu.obs.attribution import health_verdict
    ok = health_verdict(_stream_summary(age=100.0, interval=60.0))
    assert ok["verdict"] == "OK"
    stale = health_verdict(_stream_summary(age=400.0, interval=60.0))
    assert stale["verdict"] == "STALE PUBLISH"
    assert "400" in stale["detail"]
    # A LIVE stream (no run_end) with stale publishes reads STALE
    # PUBLISH (actionable), not the unclosed-stream CRASHED heuristic.
    live = health_verdict(_stream_summary(age=400.0, interval=60.0,
                                          run_end=False))
    assert live["verdict"] == "STALE PUBLISH"
    assert "no run_end" in live["detail"]
    # No publishing configured: the gauge pair is absent, never stale.
    none = health_verdict(_stream_summary(age=None, interval=None))
    assert none["verdict"] == "OK"


def test_fmstat_render_streaming_section():
    from fast_tffm_tpu.obs.attribution import render
    out = render(_stream_summary(age=10.0, interval=60.0))
    assert "STREAMING" in out
    assert "files discovered / sealed" in out
    assert "last publish age / interval" in out


# --- watermark exchange / broadcast (single-process identity) -------------


def test_exchange_and_broadcast_identity():
    wm = {"format": 1, "files": [{"path": "a", "bytes": 3, "lines": 1,
                                  "sealed": True, "dead": False,
                                  "end": 3}]}
    assert sl.exchange_watermarks(wm, num_shards=1) == wm
    assert sl.broadcast_blob({"x": 1}, label="t") == {"x": 1}


def _rec(path, b):
    return {"path": path, "bytes": b, "lines": b, "sealed": True,
            "dead": False, "end": 100}


def test_merge_watermark_payloads_owner_wins_over_stale_chief():
    """Ledger entry i comes from its OWNER (i % P) and a stale/short
    chief payload must not truncate the merge — the bug class: the
    chief stepped only fillers, ships {files: []}, and the owner's
    advanced positions for its files would be dropped."""
    chief = {"format": 1, "files": []}  # never adopted a tag
    owner = {"format": 1, "files": [_rec("f0", 0), _rec("f1", 60)]}
    merged = sl.merge_watermark_payloads([chief, owner], num_shards=2)
    assert [f["path"] for f in merged["files"]] == ["f0", "f1"]
    assert merged["files"][1]["bytes"] == 60   # owner (1 % 2) wins
    assert merged["files"][0]["bytes"] == 0    # f0's owner is the
    # chief, which has no entry: the fallback takes any payload's
    # zero-position record
    # And per-index ownership: worker 0 owns even indices.
    w0 = {"format": 1, "files": [_rec("f0", 25), _rec("f1", 0)]}
    w1 = {"format": 1, "files": [_rec("f0", 0), _rec("f1", 60)]}
    merged = sl.merge_watermark_payloads([w0, w1], num_shards=2)
    assert merged["files"][0]["bytes"] == 25
    assert merged["files"][1]["bytes"] == 60


def test_merge_watermark_ownership_reagrees_on_membership_change():
    """Elastic membership changes re-agree ledger ownership simply by
    merging under the NEW num_shards: every member's tracker was
    rebuilt from the same restored merged payload, so the entries a
    worker does not own hold the restored positions — merging with the
    grown membership picks each entry from whoever advances it NOW,
    and a fresh joiner's still-empty payload can never drop restored
    positions (the any-payload fallback has them)."""
    # Restored state after a 1-worker (shrunken) phase: f0/f1 fully
    # consumed, carried identically by the survivor.
    consumed = [_rec("f0", 100), _rec("f1", 100)]
    # Grown back to 2 workers: survivor (shard 0) advanced f2; the
    # joiner (shard 1) has stepped nothing yet — short payload.
    w0 = {"format": 1, "files": consumed + [_rec("f2", 40)]}
    w1 = {"format": 1, "files": []}
    merged = sl.merge_watermark_payloads([w0, w1], num_shards=2)
    assert [f["path"] for f in merged["files"]] == ["f0", "f1", "f2"]
    assert [f["bytes"] for f in merged["files"]] == [100, 100, 40]
    # Once the joiner adopts a tag for its owned f3, IT wins entry 3.
    w1 = {"format": 1,
          "files": consumed + [_rec("f2", 0), _rec("f3", 60)]}
    merged = sl.merge_watermark_payloads([w0, w1], num_shards=2)
    assert [f["bytes"] for f in merged["files"]] == [100, 100, 40, 60]


def test_generic_batch_spanning_files_records_both_positions(tmp_path):
    """A tolerant-path batch spanning a file boundary must advance
    EVERY file it touched in the watermark — not just the last one —
    or a mid-stream checkpoint resumes earlier files at 0 and
    double-trains them."""
    sd = tmp_path / "s"
    sd.mkdir()
    _write_lines(sd / "a.txt", _numbered(0, 3))  # 3 lines
    _write_lines(sd / "b.txt", _numbered(3, 20))
    for n in ("a.txt", "b.txt"):
        (sd / f"{n}.done").touch()
    (sd / "STOP").touch()
    cfg = _cfg(str(sd), bad_line_policy="skip")
    bad = BadLineTracker("skip", 0.9)
    tr = sl.StreamTracker(str(sd), 0.01, "done", bad_lines=bad)
    src = sl.StreamSource(cfg, tr, bad_lines=bad)
    first = src.next_batch(block=True)  # 3 lines of a + 5 of b
    recs = {os.path.basename(f["path"]): f
            for f in first.stream_pos["files"]}
    assert recs["a.txt"]["lines"] == 3  # fully consumed, recorded
    assert recs["b.txt"]["lines"] == 5
    src.close()


def test_restored_sealed_file_never_reads_late_bytes(tmp_path):
    """Bytes appended after a file sealed are IGNORED, including on a
    restore that resumes the sealed file mid-way — the watermark's
    `end` caps the read."""
    sd = tmp_path / "s"
    sd.mkdir()
    p = sd / "a.txt"
    _write_lines(p, _numbered(0, 10))
    (sd / "a.txt.done").touch()
    (sd / "STOP").touch()
    cfg = _cfg(str(sd))
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    src = sl.StreamSource(cfg, tr)
    wm = src.next_batch(block=True).stream_pos  # 8 of 10 lines
    src.close()
    assert wm["files"][0]["sealed"] and wm["files"][0]["end"]
    _write_lines(p, ["1 999:1"], append=True)  # late post-seal bytes
    tr2 = sl.StreamTracker(str(sd), 0.01, "done", watermark=wm)
    src2 = sl.StreamSource(cfg, tr2)
    batches = _drain(src2)
    src2.close()
    got = sorted(i for b in batches for i in _batch_ids(b, cfg.pad_id))
    assert got == list(range(8, 10))  # never feature 999
    assert batches[-1].stream_pos["files"][0]["bytes"] == \
        wm["files"][0]["end"]


# --- ISSUE 49: the read plane's spans and counters, the backlog ----------

def _shards(sd, n, lines_each=60, seed=3, done=True):
    """``n`` seeded shards of multi-feature lines; the paths, in
    ledger order."""
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(n):
        lines = []
        for j in range(lines_each):
            ids = rng.choice(500, size=int(rng.integers(1, 6)),
                             replace=False)
            lines.append(" ".join([str(j % 2)] + [
                f"{k}:{rng.random():.3f}" for k in ids]))
        _write_lines(sd / f"p{i:03d}.txt", lines)
        if done:
            (sd / f"p{i:03d}.txt.done").touch()
        paths.append(str(sd / f"p{i:03d}.txt"))
    return paths


STREAM_LEAVES = ("stream/discover", "stream/read", "stream/scan",
                 "stream/harvest", "pipeline/emit", "stream/snapshot")


@pytest.fixture(scope="module")
def stream_run(tmp_path_factory):
    """One whole ``train()`` in stream mode over five sealed shards and
    a STOP marker, four build workers, spans traced: its last metrics
    snapshot and the names of its span events, by thread."""
    from fast_tffm_tpu.data import cparser
    from fast_tffm_tpu.obs.sink import read_events
    from fast_tffm_tpu.train import train
    if not cparser.available():
        pytest.skip("C++ extension unavailable")
    tmp = tmp_path_factory.mktemp("stream_run")
    sd = tmp / "s"
    sd.mkdir()
    _shards(sd, 5)
    (sd / "STOP").touch()
    cfg = _cfg(str(sd), vocabulary_size=512, batch_size=16,
               host_threads=4, model_file=str(tmp / "m" / "fm"),
               metrics_file="auto", metrics_flush_steps=2, log_steps=0,
               trace_spans=True)
    train(cfg)
    events = list(read_events(cfg.model_file + ".metrics.jsonl"))
    snap = [e for e in events if e["event"] == "metrics"][-1]
    spans = {}
    for e in events:
        if e["event"] == "span":
            spans.setdefault(e["name"], set()).add(e["tid"])
    return snap["counters"], snap["gauges"], spans


@pytest.mark.parametrize("counter", [
    "stream/pumps", "stream/bytes_read", "stream/pump_seconds",
    "stream/read_seconds", "stream/snapshot_seconds",
    "stream/discover_seconds", "stream/scan_seconds",
    "stream/harvest_seconds", "stream/gets",
    "train/input_wait_seconds", "pipeline/worker_build_seconds",
    "pipeline/emit_seconds", "pipeline/batches", "pipeline/uniq_rows",
    "pipeline/uniq_slots"])
def test_a_stream_run_counts_its_read_plane(stream_run, counter):
    """What ``batch_iterator``'s plane counts, the stream's ring counts
    too (the benchmark holds every cell to ``uniq_slot_fill`` and
    ``host_build_s_per_batch``), and the read plane has counters of its
    own; the loop's wait in ``pf.get`` is its ``train/input_wait``."""
    counters, _, _ = stream_run
    assert counters.get(counter, 0) > 0, sorted(counters)


def test_a_stream_run_counts_what_it_read_and_stepped(stream_run):
    counters, gauges, _ = stream_run
    assert gauges["stream/ledger_files"] == 5
    assert counters["stream/bytes_read"] == counters["pipeline/bytes_fed"]
    assert counters["pipeline/batches"] == counters["train/steps"]
    assert counters["stream/gets"] >= counters["train/steps"]
    assert "stream/gets_idle" in counters
    assert counters["pipeline/uniq_rows"] <= counters["pipeline/uniq_slots"]


def test_the_pump_holds_its_leaves(stream_run):
    """``stream/pump`` is one service round; its leaves are disjoint
    and inside it, so their counters cannot sum to more than its own
    (sealed by ``PUMP_LEAVES``, which the benchmark's driver prints)."""
    counters, _, _ = stream_run
    assert sl.PUMP_LEAVES == STREAM_LEAVES
    leaves = sum(counters[name + "_seconds"] for name in STREAM_LEAVES)
    assert 0 < leaves <= counters["stream/pump_seconds"]


@pytest.mark.parametrize("span", ("stream/pump",) + STREAM_LEAVES)
def test_the_read_planes_spans_lie_on_the_producer_thread(stream_run, span):
    _, _, spans = stream_run
    assert spans.get(span) == {"fm-stream-prefetch"}, spans.get(span)


def test_the_loops_wait_is_a_span_on_the_loops_thread(stream_run):
    _, _, spans = stream_run
    assert spans["train/input_wait"] == spans["train/step"]
    assert "fm-stream-prefetch" not in spans["train/input_wait"]


@pytest.mark.parametrize("workers", [1, 4])
def test_a_stream_yields_the_unshuffled_epoch_runs_batches(tmp_path,
                                                           workers):
    """The stream is a log: over the sealed files of an epoch run with
    ``shuffle`` off it builds that run's batches, array for array,
    whatever shard boundaries fall inside a batch (60 lines a shard, 16
    a batch), on the serial route and through the ring."""
    from fast_tffm_tpu.data import cparser
    from fast_tffm_tpu.data.pipeline import batch_iterator
    if not cparser.available():
        pytest.skip("C++ extension unavailable")
    sd = tmp_path / "s"
    sd.mkdir()
    paths = _shards(sd, 3)
    (sd / "STOP").touch()
    cfg = _cfg(str(sd), vocabulary_size=512, batch_size=16,
               host_threads=workers)
    tr = sl.StreamTracker(str(sd), 0.01, "done")
    src = sl.StreamSource(cfg, tr, workers=workers)
    streamed = _drain(src)
    src.close()
    ecfg = FmConfig(vocabulary_size=512, factor_num=2, batch_size=16,
                    shuffle=False, seed=0, host_threads=workers,
                    train_files=tuple(paths))
    epoch = list(batch_iterator(ecfg, paths, training=True, epochs=1))
    assert len(streamed) == len(epoch) == 12       # 180 lines: 11.25
    for a, b in zip(streamed, epoch):
        assert a.num_real == b.num_real
        for name in ("labels", "weights", "uniq_ids", "local_idx", "vals"):
            np.testing.assert_array_equal(getattr(a, name),
                                          getattr(b, name))


def test_a_backlog_is_read_a_round_at_a_time_and_waited_for(tmp_path,
                                                            monkeypatch):
    """Found at 1,024 sealed shards (ISSUE 49): every pump read another
    round into the ring route's scan buffer while the builders drained
    it a group a batch (270 MB more every 50 batches, each pump copying
    all of it), and a producer with groups in flight slept a poll
    instead of waiting for the head. Now the buffer never holds two
    rounds and a backlog is eaten without one sleep; the batches are
    the serial route's, bit for bit."""
    from fast_tffm_tpu.data import cparser
    if not cparser.available():
        pytest.skip("C++ extension unavailable")
    sd = tmp_path / "s"
    sd.mkdir()
    _shards(sd, 12)
    (sd / "STOP").touch()
    cfg = _cfg(str(sd), vocabulary_size=512, batch_size=16)
    monkeypatch.setattr(sl, "MAX_POLL_BYTES", 2048)     # a shard: ~1.9 KB
    sleeps = []
    monkeypatch.setattr(sl.time, "sleep", sleeps.append)

    def run(workers):
        tr = sl.StreamTracker(str(sd), 0.01, "done")
        src = sl.StreamSource(cfg, tr, workers=workers)
        out, held = [], 0
        while True:
            b = src.next_batch(block=True)
            if b is sl.DONE:
                break
            out.append(b)
            if workers > 1:
                held = max(held, len(src._buf) - src._buf_pos)
        src.close()
        return out, held

    serial, _ = run(1)
    del sleeps[:]
    ring, held = run(4)
    assert sleeps == []
    assert 0 < held < 2 * sl.MAX_POLL_BYTES + 200       # + a held-back line
    assert len(ring) == len(serial) == 45
    for a, b in zip(ring, serial):
        np.testing.assert_array_equal(a.local_idx, b.local_idx)
        np.testing.assert_array_equal(a.uniq_ids, b.uniq_ids)
        # what was CONSUMED agrees; how far ahead the tracker has read
        # and sealed (``sealed``, ``end``, ``ino``) is each route's own
        assert ([(f["path"], f["bytes"], f["lines"])
                 for f in a.stream_pos["files"]]
                == [(f["path"], f["bytes"], f["lines"])
                    for f in b.stream_pos["files"]])


@pytest.mark.parametrize("start,end", [(0, None), (0, 0), (3, 40), (7, 8),
                                       (10, 10_000), (0, 57)])
def test_newlines_is_the_scanners_count_and_bytes_count(start, end,
                                                        monkeypatch):
    """The read plane counts lines with the C++ scanner's memchr walk
    (a torn last line, blank lines and lines of blanks all count by
    their newline) and, without the extension, with ``bytes.count``."""
    from fast_tffm_tpu.data import cparser
    data = b"1 2:1\n\n0 3:0.5 4:1\n   \n1 9:1\n0 torn"
    want = data.count(b"\n", start, len(data) if end is None else end)
    if cparser.available():
        assert sl._newlines(data, start, end) == want
    monkeypatch.setattr(cparser, "available", lambda: False)
    assert sl._newlines(data, start, end) == want
