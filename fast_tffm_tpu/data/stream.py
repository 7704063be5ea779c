"""Append-only streaming source — ``run_mode = stream`` (README
"Streaming / online learning").

Production CTR models retrain continuously: shards ARRIVE (a feed
pipeline appends `part-00017`, seals it, starts `part-00018`) rather
than existing up front. This module puts that arrival process behind
the pipeline's batch abstraction so the train driver can run one
indefinitely-surviving online pass:

- **Discovery**: ``stream_dir`` (a directory, or a glob pattern) is
  polled every ``stream_poll_seconds``; new files join an ordered
  LEDGER in first-seen order (sorted within a poll) and are consumed
  strictly in ledger order — the stream is a log, so batches are the
  same ones a clean single-pass run over the final sealed corpus
  would build (the ``stream-soak`` chaos acceptance pins this
  bit-identity).
- **Hostile filesystem**: a growing file is tailed with the torn
  trailing line HELD BACK until more bytes arrive or the file is
  sealed (a ``<file>.done`` marker, or mtime-quiet — ``seal_policy``);
  truncation/rotation of an in-progress file is detected by
  (inode, size) regression and quarantined through the run's
  :class:`~fast_tffm_tpu.data.badlines.BadLineTracker` instead of
  crashing; a deleted file is logged and skipped; every stat/open/read
  rides ``utils/retry.py``.
- **Durable position**: every emitted batch is tagged with the
  watermark payload (per-file byte/line offsets + sealed/dead flags,
  in ledger order) that holds AFTER its lines. The train loop adopts a
  tag only once the batch is actually stepped, so the watermark
  checkpointed beside the model (``watermark-<step>.json``,
  checkpoint.py) describes exactly what was trained — restore (and the
  PR 4 quarantine walk-back to an older step) resumes the stream with
  no example duplicated or skipped (an older watermark re-reads, never
  skips).
- **Parallel host plane**: with ``host_threads > 1`` the PR 7 bounded
  ordered ring consumes complete line GROUPS cut by the builder's own
  counting rule; held-back unsealed tails never enter the ring (groups
  are only cut from released, newline-terminated bytes), and the
  emitted stream is bit-identical to the serial stream path (pinned by
  tests/test_stream.py).
- **Lockstep multi-worker**: file ownership is by ledger index
  (``i % num_shards``); workers agree on the ledger (and the STOP
  decision) through a chief-broadcast ride on the existing
  ``guarded_collective`` barriers, issued exactly once per driver loop
  iteration so the collective program stays deterministic; per-worker
  watermarks merge at save time (``exchange_watermarks``).

A ``STOP`` marker file in the stream directory ends the run once every
sealed byte is consumed; until then the source reports IDLE and the
driver keeps polling (that is the "survives indefinitely" loop).
"""

from __future__ import annotations

import collections
import functools
import glob as globlib
import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.badlines import BadLineTracker
from fast_tffm_tpu.data.parser import WHITESPACE, ParseError
from fast_tffm_tpu.obs.trace import span
from fast_tffm_tpu.utils.logging import get_logger
from fast_tffm_tpu.utils.retry import (RetryPolicy, open_with_retry,
                                       retry_io)

# Sentinels next_batch returns besides a DeviceBatch: IDLE = no batch
# available right now (keep polling / feed a lockstep filler); DONE =
# the stream ended (STOP marker seen and every sealed byte consumed, or
# the caller's stop() asked for a clean exit).
IDLE = object()
DONE = object()

# Writer protocol markers (documented in README "Streaming / online
# learning"): `<file>.done` seals one shard; `STOP` in the stream root
# declares the whole stream finished.
DONE_SUFFIX = ".done"
STOP_MARKER = "STOP"

# mtime-quiet window, in poll intervals: a file whose mtime is older
# than QUIET_POLLS x stream_poll_seconds is considered sealed under
# seal_policy auto|quiet (a live writer flushes at least once per few
# poll intervals, or uses .done markers).
QUIET_POLLS = 3

# Per-poll read budget: a resumed run facing a large sealed backlog
# (hours of shards behind the watermark) must stream it in bounded
# rounds, not materialize the whole backlog as one bytes object —
# reads past the budget simply continue next poll.
MAX_POLL_BYTES = 64 << 20

# The ring route's round (host_threads > 1). Its bytes are joined to one
# scan buffer and cut into groups on the thread that pumps, before the
# builders see the next group, and the ring holds 2 x workers groups of
# cover: a 64 MB round was seventeen batches of B = 8192 x 39 read,
# joined and cut in one go, 0.75 s on a v5e's host (PERF.md, PR 49), so
# the train loop's get (0.5 s) gave up once a round with the backlog
# still there. Two such batches a round, in pieces the allocator hands
# back without fresh pages.
RING_ROUND_BYTES = 8 << 20

WATERMARK_FORMAT = 1

# The leaves of the span ``stream/pump`` (one service round of a
# StreamSource, on the thread that drives it), each counted under
# ``<name>_seconds``; the emitter's is the plane's own. What is left of
# ``stream/pump_seconds`` lies under no leaf: the ledger walk, the
# per-batch counters, and on the serial and generic routes the parse
# and the build.
PUMP_LEAVES = ("stream/discover", "stream/read", "stream/scan",
               "stream/harvest", "pipeline/emit", "stream/snapshot")

# Lockstep-mode bound on completed-but-unstepped batches: once this
# many are queued, per-iteration pumps run discovery-only until the
# driver drains some (the read plane would otherwise release a whole
# backlog into memory at MAX_POLL_BYTES per iteration).
LOCKSTEP_READY_CAP = 8


class _FileState:
    """One ledger entry: read plane (released/tail) + durable flags."""

    __slots__ = ("path", "ino", "released", "released_lines", "tail",
                 "sealed", "dead", "end", "resume_bytes",
                 "resume_lines", "mtime_seen", "size_seen",
                 "late_warned")

    def __init__(self, path: str):
        self.path = path
        self.ino: Optional[int] = None
        self.released = 0          # bytes handed to the consumer
        self.released_lines = 0    # newlines released (error lineno)
        self.tail = b""            # read but held back (no newline yet)
        self.sealed = False
        self.dead = False          # truncated/rotated/deleted: frozen
        self.end: Optional[int] = None  # final byte size once sealed
        self.resume_bytes = 0      # watermark position restored from a
        self.resume_lines = 0      # checkpoint (consumption restarts
        # there; bytes before it are never re-read)
        self.mtime_seen = 0.0
        self.size_seen = 0
        self.late_warned = False

    @property
    def eof(self) -> bool:
        """Everything this file will ever hold has been released."""
        if self.dead:
            return True
        return (self.sealed and self.end is not None
                and self.released >= self.end)


class StreamTracker:
    """Discovery + read plane of the streaming source: owns the file
    ledger, tails the current head file, makes seal/truncation/deletion
    decisions, and releases newline-terminated byte chunks strictly in
    ledger order. Consumption positions (the watermark) live in
    :class:`StreamSource` — the tracker only knows how far it has READ.

    Single-writer: every method runs on the one thread that pumps the
    owning StreamSource (the prefetch producer thread, or the lockstep
    driver's main thread)."""

    def __init__(self, pattern: str, poll_seconds: float,
                 seal_policy: str, retry: Optional[RetryPolicy] = None,
                 shard_index: int = 0, num_shards: int = 1,
                 bad_lines: Optional[BadLineTracker] = None,
                 watermark: Optional[dict] = None,
                 lockstep: bool = False,
                 clock=time.monotonic):
        if os.path.isdir(pattern) or not globlib.has_magic(pattern):
            self.root = pattern
            self._glob = os.path.join(pattern, "*")
        else:
            self.root = os.path.dirname(pattern) or "."
            self._glob = pattern
        self.poll_seconds = float(poll_seconds)
        self.seal_policy = seal_policy
        self.retry = retry
        self.shard_index = int(shard_index)
        self.num_shards = max(int(num_shards), 1)
        self.bad_lines = bad_lines
        self.lockstep = bool(lockstep)
        self._clock = clock
        self._log = get_logger()
        self.files: List[_FileState] = []
        self._by_path: Dict[str, int] = {}
        self.stop_seen = False
        self._last_fs_poll: Optional[float] = None
        self._newest_unconsumed_since: Optional[float] = None
        if watermark:
            self._restore(watermark)

    # -- watermark restore ------------------------------------------------
    def _restore(self, payload: dict) -> None:
        for rec in payload.get("files", ()):
            fs = _FileState(str(rec["path"]))
            fs.resume_bytes = fs.released = int(rec.get("bytes", 0))
            fs.resume_lines = fs.released_lines = int(
                rec.get("lines", 0))
            fs.sealed = bool(rec.get("sealed", False))
            fs.dead = bool(rec.get("dead", False))
            end = rec.get("end")
            fs.end = int(end) if end is not None else None
            ino = rec.get("ino")
            # Persisted inode extends the in-run rotation detection
            # ACROSS restarts: a same-path rewrite while the run was
            # down would otherwise be adopted and resumed mid-file
            # into unrelated content.
            fs.ino = int(ino) if ino is not None else None
            if fs.end is not None:
                fs.released = min(fs.released, fs.end)
                fs.resume_bytes = fs.released
            self._by_path[fs.path] = len(self.files)
            self.files.append(fs)

    # -- helpers ----------------------------------------------------------
    def path(self, i: int) -> str:
        return self.files[i].path

    def owned(self, i: int) -> bool:
        return i % self.num_shards == self.shard_index

    @property
    def finished(self) -> bool:
        """STOP declared and every owned file fully released."""
        if not self.stop_seen:
            return False
        return all(fs.eof for i, fs in enumerate(self.files)
                   if self.owned(i))

    def watermark_lag_seconds(self) -> float:
        """Seconds unconsumed released data has been waiting (0 when
        the reader is caught up) — the ``stream/watermark_lag_seconds``
        gauge's input; coarse by design (poll granularity)."""
        if self._newest_unconsumed_since is None:
            return 0.0
        return max(0.0, self._clock() - self._newest_unconsumed_since)

    def note_consumed_through(self, caught_up: bool) -> None:
        if caught_up:
            self._newest_unconsumed_since = None

    # -- telemetry --------------------------------------------------------
    @staticmethod
    def _tel():
        from fast_tffm_tpu.obs.telemetry import active
        return active()

    def _count(self, name: str, n: float = 1.0) -> None:
        tel = self._tel()
        if tel is not None:
            tel.count(name, n)

    # -- discovery --------------------------------------------------------
    def _discover_local(self) -> Tuple[List[str], bool]:
        """FS discovery: (new paths in sorted order, stop marker seen).
        Rate-limited to one real glob per poll interval."""
        now = self._clock()
        if (self._last_fs_poll is not None
                and now - self._last_fs_poll < self.poll_seconds):
            return [], self.stop_seen
        self._last_fs_poll = now
        stop = os.path.exists(os.path.join(self.root, STOP_MARKER))
        try:
            hits = retry_io(globlib.glob, self._glob,
                            policy=self.retry, op="stream_discover")
        except OSError:
            self._log.warning("stream discovery failed on %s; will "
                              "retry next poll", self._glob,
                              exc_info=True)
            return [], stop
        new = []
        for p in sorted(hits):
            name = os.path.basename(p)
            if (name == STOP_MARKER or name.startswith(".")
                    or name.endswith(DONE_SUFFIX)):
                continue
            if p in self._by_path:
                continue  # before the stat: a ledger of N files is N
                # stats a poll otherwise (0.2 s at 1,024 on a v5e's host)
            if os.path.isfile(p):
                new.append(p)
        return new, stop

    def _apply_discovery(self, new: Sequence[str], stop: bool) -> None:
        for p in new:
            self._by_path[p] = len(self.files)
            self.files.append(_FileState(p))
            self._count("stream/files_discovered")
            self._log.info("stream: discovered shard %s (ledger index "
                           "%d)", p, self._by_path[p])
        if new:
            tel = self._tel()
            if tel is not None:
                tel.set("stream/ledger_files", len(self.files))
        if stop and not self.stop_seen:
            self.stop_seen = True
            self._log.info("stream: STOP marker seen; will finish once "
                           "every sealed byte is consumed")

    def discover(self) -> None:
        """One discovery round. In lockstep mode (multi-worker) the
        chief's view is broadcast so every worker appends the same
        ledger entries in the same order and agrees on STOP — this is
        the one collective the stream adds, issued exactly once per
        driver-loop iteration (the caller guarantees the cadence)."""
        if not self.lockstep:
            new, stop = self._discover_local()
            self._apply_discovery(new, stop)
            return
        import jax
        if jax.process_index() == 0:
            new, stop = self._discover_local()
            payload = {"new": list(new), "stop": bool(stop)}
        else:
            payload = None
        payload = broadcast_blob(payload, label="stream/discovery")
        self._apply_discovery(payload.get("new", ()),
                              bool(payload.get("stop")))

    # -- the read plane ---------------------------------------------------
    def poll(self, read: bool = True,
             budget: Optional[int] = None) -> List[Tuple[int, bytes]]:
        """One service round: run discovery, then tail the owned head
        file(s), releasing newline-terminated chunks in strict ledger
        order. Several files can drain in one round (a backlog of
        sealed shards); an unsealed head blocks everything behind it —
        the stream is a log and order is the contract.

        ``read=False`` runs ONLY discovery (the collective half, in
        lockstep mode) and skips the local read plane — the lockstep
        driver uses it to keep its per-iteration collective cadence
        while the consumer is already holding enough batches.

        ``budget``: the round's bytes (None: ``MAX_POLL_BYTES``)."""
        with span("stream/discover", seconds="stream/discover_seconds"):
            self.discover()
        if not read:
            return []
        out: List[Tuple[int, bytes]] = []
        if budget is None:
            budget = MAX_POLL_BYTES
        for i, fs in enumerate(self.files):
            if not self.owned(i):
                continue
            if fs.eof:
                continue
            with span("stream/read", seconds="stream/read_seconds"):
                chunk = self._service(fs, budget)
            if chunk:
                out.append((i, chunk))
                budget -= len(chunk)
            if budget <= 0:
                break  # bounded round: the backlog continues next poll
            if not fs.eof:
                break  # strict order: don't read past an open head
        if out:
            if self._newest_unconsumed_since is None:
                self._newest_unconsumed_since = self._clock()
        return out

    def _mark_dead(self, fs: _FileState, why: str,
                   counter: str) -> None:
        fs.dead = True
        fs.tail = b""
        fs.end = fs.released
        self._count(counter)
        self._log.warning("stream: %s: %s; sealing at byte %d and "
                          "skipping the rest", fs.path, why,
                          fs.released)
        if (self.bad_lines is not None
                and counter != "stream/deleted_files"):
            # Quarantine-grade accounting (truncation/rotation is
            # quarantined via the run's BadLineTracker rather than
            # crashing): the event counts toward the max_bad_fraction
            # breaker like any other damaged input.
            self.bad_lines.record(fs.path, fs.released_lines + 1, "",
                                  f"stream file {why}")

    def _service(self, fs: _FileState, budget: int) -> bytes:
        """Tail one live file: read fresh bytes (at most ``budget``),
        hold back the torn trailing line, apply the seal decision.
        Returns the released chunk (possibly empty)."""
        try:
            st = retry_io(os.stat, fs.path, policy=self.retry,
                          op="stream_stat")
        except FileNotFoundError:
            self._mark_dead(fs, "deleted before it was fully consumed",
                            "stream/deleted_files")
            return b""
        except OSError:
            self._log.warning("stream: stat of %s failed; retrying "
                              "next poll", fs.path, exc_info=True)
            return b""
        if fs.ino is None:
            fs.ino = st.st_ino
        elif st.st_ino != fs.ino:
            self._mark_dead(fs, "rotated (inode changed) mid-stream",
                            "stream/truncated_files")
            return b""
        read_off = fs.released + len(fs.tail)
        if st.st_size < read_off:
            self._mark_dead(
                fs, f"truncated mid-stream ({st.st_size} bytes on disk "
                    f"< {read_off} already read)",
                "stream/truncated_files")
            return b""
        limit = st.st_size
        if fs.sealed and fs.end is not None:
            if st.st_size > fs.end and not fs.late_warned:
                fs.late_warned = True
                self._log.warning(
                    "stream: %s grew after it was sealed (%d -> %d "
                    "bytes); late bytes are ignored — fix the writer "
                    "or use seal_policy = done", fs.path, fs.end,
                    st.st_size)
            if st.st_size < fs.end:
                # A SEALED file shrank below its recorded size (e.g. a
                # rewriting producer while the run was down): without
                # this it would never reach eof and wedge the whole
                # strict-order stream in silent IDLE forever.
                self._mark_dead(
                    fs, f"truncated after seal ({st.st_size} bytes on "
                        f"disk < sealed size {fs.end})",
                    "stream/truncated_files")
                return b""
            # "late bytes are ignored" is enforced here, not just
            # warned: a restored sealed file resuming mid-way must
            # read exactly up to its sealed size — bytes appended
            # after the seal never reach training.
            limit = min(limit, fs.end)
        # Bounded round: a huge backlog streams across polls instead
        # of materializing in RAM; the remainder reads next poll.
        limit = min(limit, read_off + max(budget, 0))
        fresh = b""  # joined to the held-back tail ONCE, where it is cut
        if limit > read_off:
            try:
                fresh = self._read_range(fs.path, read_off, limit)
                self._count("stream/bytes_read", len(fresh))
            except FileNotFoundError:
                # Deleted in the stat->open window: same tolerated
                # event as the stat-time deletion, same outcome.
                self._mark_dead(
                    fs, "deleted before it was fully consumed",
                    "stream/deleted_files")
                return b""
            except OSError:
                self._log.warning(
                    "stream: read of %s failed after retries; will "
                    "retry next poll", fs.path, exc_info=True)
                return b""
        fs.size_seen = st.st_size
        fs.mtime_seen = st.st_mtime
        if not fs.sealed and self._seal_due(fs, st):
            fs.sealed = True
            # The file's FULL size at seal time, not the read
            # progress: a budget-capped partial read must not record
            # a short sealed size. RE-stat rather than reuse ``st``:
            # the .done marker may have appeared (with the shard's
            # final bytes) after the stat at the top of this call —
            # sealing at the stale size would silently exclude those
            # last legitimately-written lines forever.
            try:
                fs.end = retry_io(os.stat, fs.path, policy=self.retry,
                                  op="stream_stat").st_size
            except OSError:
                fs.end = st.st_size  # next poll's late-growth warning
                # path reports if this undershot
            self._count("stream/files_sealed")
            self._log.info("stream: sealed %s at %d bytes", fs.path,
                           fs.end)
        at_end = (fs.sealed and fs.end is not None
                  and fs.released + len(fs.tail) + len(fresh) >= fs.end)
        if at_end:
            chunk = fs.tail + fresh
            fs.tail = b""
            fs.released += len(chunk)
            if chunk and not chunk.endswith(b"\n"):
                # Final line missing its newline: terminate it exactly
                # where the epoch path's `feed(tail + b"\n")` would.
                # The synthesized byte is NOT part of the file; the
                # consumer's position accounting clamps at `end`.
                chunk += b"\n"
            fs.released_lines += _newlines(chunk)
            return chunk
        # Not yet at the (sealed or growing) end: release only whole
        # lines — a budget-capped mid-file read must never synthesize
        # a terminator into the middle of a line.
        # The held-back tail is what followed the last newline
        # released, so the last newline in hand lies in the fresh
        # bytes: one copy makes the chunk (a round's bytes were copied
        # three times here, each a byte string of its own size).
        cut = fresh.rfind(b"\n")
        if cut < 0:
            fs.tail += fresh
            return b""  # torn trailing line: held back in full
        chunk = b"".join((fs.tail, memoryview(fresh)[:cut + 1]))
        fs.tail = fresh[cut + 1:]
        fs.released += len(chunk)
        fs.released_lines += _newlines(chunk)
        return chunk

    def _seal_due(self, fs: _FileState, st) -> bool:
        if self.stop_seen:
            return True  # writer declared the whole stream finished
        if self.seal_policy in ("auto", "done") and os.path.exists(
                fs.path + DONE_SUFFIX):
            return True
        if self.seal_policy in ("auto", "quiet"):
            quiet = QUIET_POLLS * self.poll_seconds
            return time.time() - st.st_mtime >= quiet
        return False

    def _read_range(self, path: str, start: int, end: int) -> bytes:
        """[start, end) of ``path`` — chunked, retry-wrapped (the
        chunk-retry seeks back first, like pipeline._iter_owned_chunks:
        a partial buffered read advances the fd)."""
        fh = (open(path, "rb") if self.retry is None else
              open_with_retry(path, "rb", policy=self.retry,
                              op="stream_open"))
        parts = []
        with fh:
            pos = start
            fh.seek(start)
            while pos < end:
                # a ring route's round in one piece: nothing to join
                want = min(max(4 << 20, RING_ROUND_BYTES), end - pos)

                def attempt(p=pos, w=want):
                    fh.seek(p)
                    return fh.read(w)
                b = (attempt() if self.retry is None else
                     retry_io(attempt, policy=self.retry,
                              op="stream_read"))
                if not b:
                    break  # racing writer shrank below stat size
                parts.append(b)
                pos += len(b)
        return b"".join(parts)


def _newlines(data: bytes, start: int = 0,
              end: Optional[int] = None) -> int:
    """The newlines of ``data[start:end]``: the C++ scanner's memchr
    walk where the extension is there (with ``keep_empty`` every
    complete line counts), off the GIL. ``bytes.count`` walks text of
    short lines at under 1 GB/s and holds the GIL while it does: three
    such walks of every byte were 13 of the 17 ms a batch cost the
    stream's producer thread, and the train loop's phases beside it
    read twice their length (PERF.md, PR 49)."""
    from fast_tffm_tpu.data import cparser
    if cparser.available():
        return cparser.scan_examples(data, 1 << 62, True, offset=start,
                                     end=end)[0]
    return data.count(b"\n", start, len(data) if end is None else end)


# -- multi-worker agreement helpers ---------------------------------------


def broadcast_blob(obj, label: str):
    """Chief's JSON-serializable ``obj`` on every process, through the
    deadline-guarded broadcast the restore protocol uses (two phases:
    length, then the padded byte payload — ``broadcast_one_to_all``
    needs identical shapes everywhere). Identity when single-process."""
    import jax
    if jax.process_count() <= 1:
        return obj
    from jax.experimental import multihost_utils
    from fast_tffm_tpu.parallel.liveness import guarded_collective
    proc0 = jax.process_index() == 0
    data = json.dumps(obj).encode("utf-8") if proc0 else b""
    n = int(guarded_collective(
        multihost_utils.broadcast_one_to_all, np.int64(len(data)),
        label=label + "/len"))
    buf = np.zeros(max(n, 1), np.uint8)
    if proc0 and n:
        buf[:n] = np.frombuffer(data, np.uint8)
    out = guarded_collective(multihost_utils.broadcast_one_to_all, buf,
                             label=label)
    # .astype: the transport may widen small dtypes (the gloo CPU
    # client returns int32 elements for a uint8 payload) — the VALUES
    # are the bytes either way.
    raw = np.asarray(out)[:n].astype(np.uint8).tobytes()
    return json.loads(raw.decode("utf-8"))


def exchange_watermarks(local: dict, num_shards: int) -> dict:
    """Merge per-worker watermark payloads at a lockstep save point:
    every worker allgathers its local payload (two fixed-shape
    collectives) and ledger entry ``i`` is taken from its OWNER
    (``i % num_shards``) — the only worker whose positions for that
    file ever advance. All workers return the same merged payload, so
    process 0 can write the one authoritative sidecar."""
    import jax
    if jax.process_count() <= 1 or num_shards <= 1:
        return local
    from jax.experimental import multihost_utils
    from fast_tffm_tpu.parallel.liveness import guarded_collective
    data = json.dumps(local).encode("utf-8")
    lens = np.asarray(guarded_collective(
        multihost_utils.process_allgather, np.int64(len(data)),
        label="stream/watermark_len")).reshape(-1)
    m = int(lens.max())
    buf = np.zeros(max(m, 1), np.uint8)
    buf[:len(data)] = np.frombuffer(data, np.uint8)
    gathered = np.asarray(guarded_collective(
        multihost_utils.process_allgather, buf,
        label="stream/watermark_merge")).reshape(len(lens), -1)
    payloads = [json.loads(gathered[p, :int(lens[p])]
                           .astype(np.uint8).tobytes()
                           .decode("utf-8"))
                for p in range(len(lens))]
    return merge_watermark_payloads(payloads, num_shards)


def merge_watermark_payloads(payloads: Sequence[dict],
                             num_shards: int) -> dict:
    """The pure merge behind ``exchange_watermarks``: ledger entry
    ``i`` is taken from its OWNER's payload (``i % num_shards``).
    Iterates the LONGEST ledger, not the chief's: a worker whose
    adopted watermark is stale (it stepped only fillers lately, or its
    shards drained before newer files were discovered) ships a short —
    possibly empty — file list, and iterating the chief's view would
    silently drop the owner's advanced positions for later ledger
    entries. Ledger ORDER is chief-agreed, so index ``i`` means the
    same file in every non-short payload."""
    merged = {"format": WATERMARK_FORMAT, "files": []}
    n_files = max(len(p.get("files", ())) for p in payloads)
    for i in range(n_files):
        owner_files = payloads[i % num_shards].get("files", ())
        if i < len(owner_files):
            merged["files"].append(owner_files[i])
            continue
        # The owner never adopted a tag covering this file (nothing of
        # it stepped yet): any payload that has the entry carries the
        # correct zero positions + discovery flags.
        for p in payloads:
            files = p.get("files", ())
            if i < len(files):
                merged["files"].append(files[i])
                break
    return merged


# -- the batch source ------------------------------------------------------


class StreamSource:
    """Arrival-ordered DeviceBatch source over a StreamTracker.

    ``next_batch(block=...)`` returns a DeviceBatch, ``IDLE`` (nothing
    available right now) or ``DONE`` (stream finished / caller stop).
    Every emitted batch carries ``batch.stream_pos`` — the watermark
    payload after its lines (see module docstring).

    Three consumption routes, mirroring the epoch pipeline's routing:
    the serial C++ fast path (one persistent BatchBuilder — spills
    under a fixed unique budget re-feed exactly like the epoch path),
    the parallel fast plane (``host_threads > 1``: complete line
    groups through the PR 7 bounded ordered ring, bit-identical to the
    serial route), and the generic tolerant path (bad_line_policy
    skip/quarantine, or no C++ extension — per-line Python with the
    run's BadLineTracker). Route choice is ``stream_workers`` +
    cparser availability, resolved once at construction."""

    def __init__(self, cfg: FmConfig, tracker: StreamTracker,
                 stop=None, fixed_shape: bool = False,
                 uniq_bucket: int = 0, raw_ids: bool = False,
                 workers: int = 1,
                 bad_lines: Optional[BadLineTracker] = None,
                 vocab=None, row_shards=None):
        from fast_tffm_tpu.data import cparser
        from fast_tffm_tpu.data.pipeline import (_BatchEmitter,
                                                 effective_L_cap)
        self.cfg = cfg
        # The BUILD-side config (vocab_mode = admit): parsers/builders
        # mod ids into the hash space; every emitted batch is remapped
        # to physical rows (vocab.remap) before it reaches the ready
        # deque — the same seam batch_iterator applies in epoch mode.
        self._vocab = vocab
        # A mesh train step's feed (pipeline.RowShards): unique rows
        # ordered by owning row shard; under vocab the remap orders
        # them, once the rows are known.
        self._row_shards = row_shards if vocab is None else None
        bcfg = cfg if vocab is None else vocab.build_cfg(cfg)
        self._bcfg = bcfg
        self.tracker = tracker
        self._stop_cb = stop or (lambda: False)
        self.B = cfg.batch_size
        self.fixed_shape = fixed_shape
        self.uniq_bucket = uniq_bucket
        self.raw_ids = raw_ids
        self.bad_lines = bad_lines
        self._log = get_logger()
        # Stream mode is arrival-ordered by design: the emitter's
        # shuffle window is off (cfg.shuffle has no effect here), which
        # is also what makes the watermark a per-file prefix.
        from fast_tffm_tpu.data.pipeline import SpillStats
        self.stats = SpillStats()
        self._emitter = _BatchEmitter(bcfg, self.B,
                                      effective_L_cap(bcfg),
                                      fixed_shape, uniq_bucket,
                                      shuffle=False, seed=cfg.seed,
                                      stats=self.stats,
                                      shards=self._row_shards)
        self._ready: collections.deque = collections.deque()
        self._pos: Dict[int, Tuple[int, int]] = {}  # idx -> (bytes, lines)
        for i, fs in enumerate(tracker.files):
            if fs.resume_bytes or fs.resume_lines:
                self._pos[i] = (fs.resume_bytes, fs.resume_lines)
        self._flushed = False
        self._closed = False
        tolerant = getattr(cfg, "bad_line_policy", "error") != "error"
        # Route conditions mirror the epoch path's _fast_path_eligible:
        # max_features_per_example = 0 ("unlimited") must stay generic
        # — the C++ builder writes fixed-stride rows and would silently
        # truncate long examples at the ladder cap, training a
        # different model than the same corpus under run_mode=epochs.
        self._fast = (cparser.available() and not tolerant
                      and cfg.max_features_per_example > 0)
        self._workers = max(int(workers), 1) if (
            self._fast and not fixed_shape) else 1
        self._ring = None
        if self._fast:
            pl = _pipeline()
            if self._workers > 1:
                # Ring builders consume whole pre-cut groups; positions
                # come from cut-time accounting, so the threaded feed
                # is safe (same rule as the epoch plane).
                feed_threads = pl._worker_feed_threads(self._workers,
                                                       False)
                self._make_builder = functools.partial(
                    pl._make_builder, bcfg, self.B, raw_ids, False,
                    fixed_shape, uniq_bucket, feed_threads,
                    shards=self._row_shards)
                self._init_ring()
            else:
                # The serial stream builder REQUIRES the single-thread
                # feed: the watermark needs the byte-exact consumed
                # offset of every batch close, which the threaded
                # feed's pending queue hides (it consumes the whole
                # chunk up front) — same constraint as the epoch
                # plane's spill rewind.
                self._make_builder = functools.partial(
                    pl._make_builder, bcfg, self.B, raw_ids, False,
                    fixed_shape, uniq_bucket, 1, shards=self._row_shards)
                self._bb = self._make_builder()
        else:
            self._pending: List[Tuple[str, int, int, int]] = []
            # (line, file_idx, abs_byte_end, abs_lineno)
            self._decoded: Dict[int, Tuple[int, int]] = {}
            # raw decode position per file (covers trailing blanks
            # at the final flush)
        # Error-provenance spans: (stream_lines_before, file_idx,
        # resume_line_offset) per file as it starts feeding.
        self._spans: List[Tuple[int, int, int]] = []
        self._stream_lines = 0

    # -- shared plumbing --------------------------------------------------
    def _snapshot(self) -> dict:
        files = []
        for i, fs in enumerate(self.tracker.files):
            b, l = self._pos.get(i, (0, 0))
            if fs.end is not None:
                b = min(b, fs.end)
            files.append({"path": fs.path, "bytes": int(b),
                          "lines": int(l), "sealed": bool(fs.sealed),
                          "dead": bool(fs.dead), "end": fs.end,
                          "ino": fs.ino})
        return {"format": WATERMARK_FORMAT, "files": files}

    def _advance(self, fi: int, nbytes: int, nlines: int) -> None:
        b, l = self._pos.get(fi, (self.tracker.files[fi].resume_bytes,
                                  self.tracker.files[fi].resume_lines))
        self._pos[fi] = (b + nbytes, l + nlines)

    def _emit(self, out, spilled: bool) -> None:
        for batch in self._emitter.emit_drain(out, spilled):
            if self._vocab is not None:
                # Hash-space -> physical rows (vocab/table.py), before
                # telemetry sees the batch: the pad-waste counter
                # below reads the PHYSICAL pad_id.
                batch = self._vocab.remap(batch)
            with span("stream/snapshot",
                      seconds="stream/snapshot_seconds"):
                batch.stream_pos = self._snapshot()
            tel = StreamTracker._tel()
            if tel is not None:
                tel.pipeline_batch(batch, self.cfg.pad_id)
            self._ready.append(batch)

    def _note_file_start(self, fi: int) -> None:
        if not self._spans or self._spans[-1][1] != fi:
            fs = self.tracker.files[fi]
            self._spans.append((self._stream_lines, fi,
                                fs.resume_lines))

    def _attach_source(self, e: ParseError) -> ParseError:
        """Builder-stream "line N" -> file + absolute lineno, through
        the span map + each file's resume offset (a resumed stream's
        builder never saw the lines before the watermark)."""
        import re as _re
        m = _re.match(r"^line (\d+): (.*)$", str(e), _re.S)
        if not m or not self._spans:
            return e
        n = int(m.group(1))
        owner = self._spans[0]
        for rec in self._spans:
            if rec[0] < n:
                owner = rec
            else:
                break
        base, fi, resume = owner
        path = self.tracker.path(fi)
        return ParseError(f"{path} line {resume + (n - base)}: "
                          f"{m.group(2)}")

    # -- the pump ---------------------------------------------------------
    def _pump(self, read: bool = True, wait: bool = False) -> None:
        """One service round of the source, on whichever thread drives
        it (span ``stream/pump``; its leaves: the tracker's
        ``stream/discover`` and ``stream/read``, the ring route's
        ``stream/scan`` and ``stream/harvest``, the emitter's own
        ``pipeline/emit`` and the watermark tag ``stream/snapshot``;
        the serial and generic routes parse and build under no leaf).

        The ring route cuts its groups from one scan buffer, which a
        backlog of sealed shards would fill a round a pump while the
        builders drain it a group a batch: a round (``RING_ROUND_BYTES``)
        is read only while the buffer holds less than one, so it never
        holds two (found at 1,024 sealed shards: the buffer grew 270 MB
        every 50 batches and every pump copied all of it). ``wait``
        (the prefetch producer): a round that leaves nothing ready
        while the builders hold groups waits for the ring's head, as
        the epoch plane's emitter does, where the caller would sleep a
        poll (0.2 s where a batch takes 10 ms)."""
        tel = StreamTracker._tel()
        if tel is not None:
            tel.count("stream/pumps")
        with span("stream/pump", seconds="stream/pump_seconds"):
            budget = None
            if self._ring is not None:
                budget = min(RING_ROUND_BYTES, MAX_POLL_BYTES)
                read = read and len(self._buf) - self._buf_pos < budget
            chunks = self.tracker.poll(read=read, budget=budget)
            for fi, data in chunks:
                if self._fast:
                    if self._ring is not None:
                        self._scan_feed(fi, data)
                    else:
                        self._note_file_start(fi)
                        self._serial_feed(fi, data)
                else:
                    self._generic_feed(fi, data)
            if self._ring is not None:
                self._ring_drive()
                if wait and not self._ready:
                    self._harvest(block=True, once=True)
            if self.tracker.finished and not self._flushed:
                self._flush_final()
            self.tracker.note_consumed_through(
                caught_up=not self._ready and not chunks)

    def _flush_final(self) -> None:
        self._flushed = True
        if self._fast:
            if self._ring is not None:
                self._ring_flush()
            else:
                out = self._emitter.finish(self._bb)
                if out[0]:
                    self._emit(out, spilled=False)
        else:
            self._generic_flush(final=True)

    # -- serial fast path -------------------------------------------------
    def _serial_feed(self, fi: int, data: bytes) -> None:
        off = 0
        while True:
            try:
                full, c = self._bb.feed(data, off)
            except ParseError as e:
                raise self._attach_source(e) from None
            nl = _newlines(data, off, off + c)
            self._advance(fi, c, nl)
            self._stream_lines += nl
            off += c
            if not full:
                return
            try:
                out = self._emitter.finish(self._bb)
            except ParseError as e:
                raise self._attach_source(e) from None
            # A finish() under the fixed unique budget that closed
            # early (n < B) is the spill signal, exactly like the epoch
            # fast path; the offending line is still at data[off:] and
            # re-feeds on the next loop turn.
            self._emit(out, spilled=bool(self.fixed_shape
                                         and out[0] < self.B))

    # -- parallel fast plane (host_threads > 1) ---------------------------
    def _init_ring(self) -> None:
        pl = _pipeline()
        self._ring = pl._BuildRing(
            self._workers, depth=2 * self._workers,
            work=pl._fast_group_work,
            make_state=lambda: pl._FastWorkerState(
                self._make_builder, self._emitter.finish))
        self._buf = b""
        self._buf_pos = 0
        self._segments: collections.deque = collections.deque()
        # [file_idx, remaining_length] per appended chunk, FIFO
        self._inflight: collections.deque = collections.deque()
        # (seq, positions) in submit order
        # Cut-side counters are SEPARATE from the emission-side
        # watermark (self._pos): groups are cut ahead of their build,
        # and the watermark on an emitted batch must never include a
        # later group's lines. _pos only advances at harvest time, in
        # emission order.
        self._cut_pos: Dict[int, Tuple[int, int]] = dict(self._pos)
        tel = StreamTracker._tel()
        if tel is not None:
            tel.set("pipeline/host_threads", self._workers)

    def _scan_feed(self, fi: int, data: bytes) -> None:
        with span("stream/scan", seconds="stream/scan_seconds"):
            # one new string, what is left of the old one copied once
            self._buf = b"".join(
                (memoryview(self._buf)[self._buf_pos:], data))
            self._buf_pos = 0
            self._segments.append([fi, len(data)])

    def _cut_positions(self, consumed: int,
                       lines: int) -> Dict[int, Tuple[int, int]]:
        """Advance the scanner-side counters by ``consumed`` bytes, the
        scanner's ``lines`` lines, off the buffer head; returns the
        ABSOLUTE (bytes, lines) position per touched file after the
        cut. Also records the error-span map in cut-line units (the
        units group.line_start uses). Only a cut that spans chunks
        walks bytes again: every part but the last is counted, the last
        holds what is left of ``lines``."""
        out: Dict[int, Tuple[int, int]] = {}
        taken = 0
        while taken < consumed:
            seg = self._segments[0]
            fi, seg_len = seg
            self._note_file_start(fi)
            n = min(seg_len, consumed - taken)
            if taken + n == consumed:
                nl = lines
            else:
                nl = _newlines(self._buf, self._buf_pos + taken,
                               self._buf_pos + taken + n)
            lines -= nl
            b, l = self._cut_pos.get(
                fi, (self.tracker.files[fi].resume_bytes,
                     self.tracker.files[fi].resume_lines))
            self._cut_pos[fi] = (b + n, l + nl)
            self._stream_lines += nl
            out[fi] = self._cut_pos[fi]
            taken += n
            if n == seg_len:
                self._segments.popleft()
            else:
                seg[1] -= n
        return out

    def _cut_one_group(self, consumed: int, lines: int) -> None:
        """Hand the ring the next ``consumed`` bytes of the scan buffer,
        ``lines`` lines by the scanner's own count, as one group."""
        blob = self._buf[self._buf_pos:self._buf_pos + consumed]
        line_start = self._stream_lines
        positions = self._cut_positions(consumed, lines)
        self._buf_pos += consumed
        seq = self._ring.submit(_pipeline()._Group(blob, line_start, lines))
        self._inflight.append((seq, positions))

    def _ring_drive(self) -> None:
        """Cut complete groups, submit to the ring, and harvest every
        finished head — only COMPLETE groups (B example lines of
        released, newline-terminated bytes) ever enter the ring;
        held-back torn tails stay in the tracker and sub-B leftovers
        stay in this buffer."""
        from fast_tffm_tpu.data.cparser import scan_examples
        with span("stream/scan", seconds="stream/scan_seconds"):
            while len(self._inflight) < self._ring.depth:
                found, consumed, lines = scan_examples(
                    self._buf, self.B, False, offset=self._buf_pos)
                if found < self.B:
                    break
                self._cut_one_group(consumed, lines)
        self._harvest(block=False)

    def _harvest(self, block: bool, once: bool = False) -> None:
        """Emit the finished heads of the ring, in submit order;
        ``block`` waits for each (``once``: for the first alone)."""
        while self._inflight:
            seq, positions = self._inflight[0]
            with span("stream/harvest", seconds="stream/harvest_seconds"):
                if not block and not self._ring.has(seq):
                    return
                self._inflight.popleft()
                kind, payload = self._ring.wait(seq)
            if kind == "error":
                if isinstance(payload, ParseError):
                    raise self._attach_source(payload) from None
                raise payload
            out, _consumed = payload
            for fi, pos in positions.items():
                self._pos[fi] = pos
            self._emit(out, spilled=False)
            if once:
                return

    def _ring_flush(self) -> None:
        from fast_tffm_tpu.data.cparser import scan_examples
        while True:
            found, consumed, lines = scan_examples(
                self._buf, self.B, False, offset=self._buf_pos)
            if not found:
                break
            self._cut_one_group(consumed, lines)
            if found < self.B:
                break  # the final short group
        self._harvest(block=True)

    # -- generic tolerant path --------------------------------------------
    def _generic_feed(self, fi: int, data: bytes) -> None:
        # Decode-plane positions continue from _decoded (the raw
        # per-file decode cursor), NOT from _pos: _pos only advances at
        # batch emission, so a file released across several polls would
        # otherwise restart its byte counter at the last emitted batch
        # and tag later lines with bogus offsets.
        b, l = self._decoded.get(
            fi, (self.tracker.files[fi].resume_bytes,
                 self.tracker.files[fi].resume_lines))
        for raw in data.split(b"\n")[:-1]:
            b += len(raw) + 1
            l += 1
            line = raw.decode("utf-8")
            if line.strip(WHITESPACE):
                self._pending.append((line, fi, b, l))
            self._stream_lines += 1
        fs = self.tracker.files[fi]
        if fs.end is not None:
            b = min(b, fs.end)
        self._decoded[fi] = (b, l)
        while len(self._pending) >= self.B:
            self._generic_flush(final=False)

    def _generic_flush(self, final: bool) -> None:
        from fast_tffm_tpu.data.pipeline import (_parse_block,
                                                 _salvage_block,
                                                 _strip_line_prefix,
                                                 make_device_batch)
        take = self._pending[:self.B]
        if not take:
            if final:
                self._final_positions()
            return
        del self._pending[:self.B]
        lines = [t[0] for t in take]
        if self.bad_lines is None:
            try:
                block = _parse_block(lines, self._bcfg, None)
            except ParseError as e:
                _, fi, _, ln = take[0]
                raise ParseError(
                    f"{self.tracker.path(fi)} near line {ln}: "
                    f"{_strip_line_prefix(str(e))}") from None
        else:
            bads: List[Tuple[int, str, str]] = []
            block = _salvage_block(lines, self._bcfg, False, bads)
            self.bad_lines.count_ok(len(lines) - len(bads))
            for i, raw, msg in bads:
                _, fi, _, ln = take[i]
                self.bad_lines.record(self.tracker.path(fi), ln, raw,
                                      _strip_line_prefix(msg))
        if block.batch_size:
            out_batch = make_device_batch(
                block, self._bcfg, batch_size=self.B,
                fixed_shape=self.fixed_shape,
                uniq_bucket=self.uniq_bucket, raw_ids=self.raw_ids,
                shards=self._row_shards)
            if self._vocab is not None:
                out_batch = self._vocab.remap(out_batch)
            # EVERY file the chunk touches advances — a batch spanning
            # a file boundary must record the earlier files' final
            # included positions too, or a mid-stream checkpoint would
            # resume them at 0 and double-train (files consume in
            # strict ledger order, so each file's last line in the
            # chunk IS its consumed-through position).
            for _, fi, byte_end, line_end in take:
                self._pos[fi] = (byte_end, line_end)
            with span("stream/snapshot",
                      seconds="stream/snapshot_seconds"):
                out_batch.stream_pos = self._snapshot()
            if self.stats is not None:
                self.stats.count(out_batch.num_real, self.B, False)
            tel = StreamTracker._tel()
            if tel is not None:
                tel.pipeline_batch(out_batch, self.cfg.pad_id)
            self._ready.append(out_batch)
        if final:
            while self._pending:
                self._generic_flush(final=False)
            self._final_positions()

    def _final_positions(self) -> None:
        for fi, pos in self._decoded.items():
            self._pos[fi] = pos

    # -- the public surface -----------------------------------------------
    def next_batch(self, block: bool = False):
        """One batch, or IDLE/DONE.

        ``block=True`` (the single-process prefetch producer) sleeps
        between polls, heartbeating the watchdog, and honors the
        caller's stop() (preemption) promptly.

        ``block=False`` with a LOCKSTEP tracker (the multi-worker
        driver) performs EXACTLY one pump per call — one tracker poll,
        hence one discovery collective — even when a batch is already
        queued or this worker is drained, so every worker's collective
        program stays aligned; preemption/exit agreement is the
        driver's flags-allgather, never a local decision here."""
        if self.tracker.lockstep:
            # The discovery collective must run EVERY call (cadence
            # alignment), but the read plane is purely local — skip it
            # while enough batches are already queued, or a deep
            # sealed backlog would be released (64 MB/call) far faster
            # than one-batch-per-iteration consumption drains it and
            # accumulate unboundedly in the ready deque.
            self._pump(read=len(self._ready) < LOCKSTEP_READY_CAP)
            if self._ready:
                return self._ready.popleft()
            return DONE if self._flushed else IDLE
        if self._stop_cb():
            return DONE
        if not block:
            if self._ready:
                return self._ready.popleft()
            if not self._flushed:
                self._pump()
            if self._ready:
                return self._ready.popleft()
            return DONE if self._flushed else IDLE
        while True:
            if self._ready:
                return self._ready.popleft()
            if self._flushed:
                return DONE
            if self._stop_cb() or self._closed:
                # _closed: the consumer tore down (error path) — the
                # producer thread must exit its poll loop, not keep
                # polling a dead run's directory forever.
                return DONE
            self._pump(wait=True)
            if self._ready or self._flushed:
                continue
            tel = StreamTracker._tel()
            if tel is not None:
                tel.heartbeat()
                tel.set("stream/watermark_lag_seconds",
                        self.tracker.watermark_lag_seconds())
            time.sleep(min(self.tracker.poll_seconds, 0.2))

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._ring is not None:
            self._ring.close()


class StreamPrefetcher:
    """Single-process build/compute overlap for a StreamSource: a
    producer thread pulls ``next_batch(block=True)`` (which sleeps,
    heartbeats, and polls while the stream idles) into a bounded
    queue; the consumer's ``get(timeout)`` returns a batch, ``IDLE``
    on timeout — which is what lets the driver keep its publish clock
    and preemption checks ticking while the stream is quiet — or
    ``DONE``. Producer errors re-raise at the next get. Unlike
    pipeline.prefetch there is no GIL-bound passthrough: an idle
    stream must never park the driver in a blocking get, and the
    thread is idle-cheap (the producer sleeps between polls)."""

    _SENTINEL_DONE = ("done", None)

    def __init__(self, source: StreamSource, depth: int = 2):
        import queue
        import threading
        self._q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
        self._stop = threading.Event()
        self._source = source
        self._thread = threading.Thread(target=self._main,
                                        name="fm-stream-prefetch",
                                        daemon=True)
        self._thread.start()

    def _put(self, item) -> None:
        """Bounded put + stop checks: an abandoned consumer must never
        strand the producer thread holding batches."""
        import queue
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def _main(self) -> None:
        try:
            while not self._stop.is_set():
                b = self._source.next_batch(block=True)
                self._put(self._SENTINEL_DONE if b is DONE
                          else ("batch", b))
                if b is DONE:
                    return
        except BaseException as e:  # re-raised at the consumer's get
            self._put(("error", e))

    def get(self, timeout: float):
        """A DeviceBatch, IDLE (nothing within ``timeout``), or DONE."""
        import queue
        try:
            kind, val = self._q.get(timeout=max(timeout, 0.01))
        except queue.Empty:
            return IDLE
        if kind == "error":
            raise val
        if kind == "done":
            return DONE
        return val

    def close(self) -> None:
        self._stop.set()
        # Close the source FIRST: the producer may be parked inside
        # next_batch's poll-sleep loop, which exits on the source's
        # _closed flag — without this every error-path teardown would
        # burn the full join timeout waiting for a thread that only
        # the (later) source close can release. Idempotent, so the
        # driver's own source.close() safety net stays harmless.
        self._source.close()
        self._thread.join(timeout=5.0)


def _pipeline():
    """Late import of data.pipeline (stream <-> pipeline would be a
    cycle at import time; pipeline imports nothing from here)."""
    from fast_tffm_tpu.data import pipeline
    return pipeline


def stream_workers(cfg: FmConfig, fixed_shape: bool = False) -> int:
    """The parallel-plane worker count the stream source will ACTUALLY
    use — resolve_host_threads when the fast parallel route exists
    (C++ available, strict bad-line policy, a bounded per-example
    feature cap, not the fixed-U lockstep shape whose spill-rewind
    protocol is serial-feed only), else 1. Must stay in lockstep with
    StreamSource's own ``_fast`` routing — the shared predicate exists
    so train's startup log can't overclaim."""
    pl = _pipeline()
    workers = pl.resolve_host_threads(cfg)
    if workers <= 1 or fixed_shape:
        return 1
    from fast_tffm_tpu.data import cparser
    if not cparser.available():
        return 1
    if getattr(cfg, "bad_line_policy", "error") != "error":
        return 1
    if cfg.max_features_per_example <= 0:
        return 1  # "unlimited" features: the generic (serial) route
    return workers


def probe_stream_uniq_bucket(cfg: FmConfig, tracker: StreamTracker,
                             shards=None) -> int:
    """Fixed unique-row bucket for lockstep stream mode: probe the
    SEALED files present at startup (same math as
    pipeline.probe_uniq_bucket), or a safe default when the stream is
    still empty. The chief decides and the value is broadcast —
    workers must never probe racing, possibly-mid-write bytes
    independently. Call once, on every worker, before the step loop
    (the embedded discovery is collective in lockstep mode)."""
    pl = _pipeline()
    import jax
    tracker.discover()  # collective in lockstep mode: all call it

    def decide() -> int:
        top = pl.uniq_bucket_top(cfg, shards=shards)
        quiet_ok = tracker.seal_policy in ("auto", "quiet")
        quiet = QUIET_POLLS * tracker.poll_seconds
        candidates = []
        for fs in tracker.files:
            try:
                st = os.stat(fs.path)
                # "Probe-safe" mirrors the seal signals: a .done
                # marker, an already-sealed restore flag, or — under
                # the quiet policies — an mtime past the quiet window
                # (no tracker service has run yet at probe time, so
                # fs.sealed alone would leave every quiet-policy
                # stream on the fallback bucket and spill chronically).
                if st.st_size > 0 and not fs.dead and (
                        fs.sealed
                        or os.path.exists(fs.path + DONE_SUFFIX)
                        or (quiet_ok
                            and time.time() - st.st_mtime >= quiet)):
                    candidates.append(fs.path)
            except OSError:
                continue
        if not candidates:
            return min(1 << 10, top)
        return pl.probe_uniq_bucket(cfg, candidates, shards=shards)

    if not tracker.lockstep:
        return decide()
    if jax.process_index() == 0:
        val = {"bucket": decide()}
    else:
        val = None
    return int(broadcast_blob(val,
                              label="stream/uniq_bucket")["bucket"])
