"""Cross-file streaming scorer core (ROADMAP item 2: the predict gap).

The pre-refactor predict path tore its overlap pipeline down at every
file boundary: a fresh ``batch_iterator`` (fresh builder warmup), a
fresh ``ChunkedFetcher`` drain, and a telemetry ``barrier_flush`` per
file serialized the sweep into parse -> score -> D2H -> write, per
file, with nothing overlapping across the boundary. This module is the
single continuous alternative both predict drivers build on:

- ONE ``batch_iterator`` runs over ALL files (batches freely cross
  file boundaries — the C++ builder feeds straight through), tagged by
  the pipeline's ``FileMarks`` ledger: ``(path, examples_before)`` per
  file, appended before any batch holding that file's first example is
  yielded (the same idea as stream.py's watermark tags).
- ONE ``ChunkedFetcher`` (overlap=True) lives for the whole sweep, so
  file N's D2H rides the background thread while file N+1 scores and
  file N+2 parses.
- ``ScoreDemux`` cuts the ordered score stream back into per-file
  arrays as each file's LAST example lands, and hands them to the
  caller's ``on_file`` — which submits to the bounded ``ScoreWriter``
  thread, overlapping file N's disk write with everything above.

``keep_empty`` is load-bearing everywhere here: every input line is
exactly one example (blank lines become zero-feature rows — C++ block
parser ABI 7 and the BatchBuilder agree on the rule), so the ledger's
example offsets ARE line offsets and the score files stay line-aligned
with their inputs.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import (FileMarks, batch_iterator,
                                         gil_bound_iteration, prefetch)
from fast_tffm_tpu.obs.telemetry import active
from fast_tffm_tpu.obs.trace import span
from fast_tffm_tpu.utils.fetch import ChunkedFetcher

# Output-order buffer depth buckets (batches retained between bulk
# fetches): powers of two up to 4x FETCH_CHUNK_BATCHES.
DEPTH_BUCKETS = tuple(2 ** i for i in range(11))


class CompiledScorer:
    """The long-lived compiled-scorer handle both inference surfaces
    share: batch predict's cross-file sweep (score_sweep below) and the
    online serving process (serve/server.py). Wraps the one dispatch
    over the three inference paths (models/fm.make_batch_scorer), the
    raw-batch policy (ships_raw_batches — the pipeline must build
    batches in the shape the compiled program expects, and a drifted
    copy of that condition is how a raw-gather scorer ends up fed
    host-deduped batches), and the spec resolution, so a caller can't
    pair a scorer with the wrong batch builder.

    The config's resolution gives a sweep (batch predict, like a
    validation sweep) the host unique on one device too since PR 45:
    ``raw`` is false, the C++ builder dedups while it parses, U rides
    the quarter-octave ladder and the scorer gathers U fitted slots
    where a raw-id scorer walks B*L cells twice (PERF.md section 6,
    PR 45). ``dedup`` overrides that — the serving process forces
    ``"device"`` (the raw-gather path: no U axis, so its pre-compiled
    shape ladder is exactly [B rung, L rung] and every padded request
    shape is known at warmup; a request of a few lines has nothing to
    dedup). jit executables are cached per (spec, shape) process-wide
    (models/fm lru caches), so a handle is cheap to construct and
    compiled code outlives it."""

    def __init__(self, cfg: FmConfig, mesh=None, backend=None,
                 dedup: Optional[str] = None, serve_ladder: bool = False):
        import dataclasses
        from fast_tffm_tpu.models.fm import (ModelSpec,
                                             make_batch_scorer,
                                             ships_raw_batches)
        from fast_tffm_tpu.wire import WireEncoder, resolve_wire
        spec = ModelSpec.from_config(cfg)
        if dedup is not None:
            spec = dataclasses.replace(spec, dedup=dedup)
        self.spec = spec
        self.mesh = mesh
        self.backend = backend
        # Whether batch builders must ship RAW ids ([B, L], uniq_ids
        # None) for this scorer — threaded into batch_iterator /
        # make_device_batch by every caller.
        self.raw = ships_raw_batches(spec, mesh=mesh, backend=backend)
        self._score = make_batch_scorer(spec, mesh=mesh, backend=backend)
        # Wire format (README "Wire format"; wire.py): the one encoder
        # every inference surface dispatches through. Packed mode ships
        # flat CSR and the jitted program rebuilds the rectangles
        # on-device; the offload path withholds uniq_ids for its host
        # gather and ships only the gathered rows + flat CSR.
        self.wire = resolve_wire(cfg, mesh=mesh, backend=backend)
        # ``serve_ladder``: the server's encoder buckets flat arrays to
        # the coarse rect-fraction ladder so its pre-compiled shape
        # matrix stays bounded (wire.rect_fraction_rungs).
        self.encoder = WireEncoder(self.wire, pad_id=cfg.pad_id,
                                   host_uniq=backend is not None,
                                   rect_fraction=serve_ladder)
        # Explicit async device_put (the depth-2 double buffer) applies
        # on the plain single-device path only — mesh placement and the
        # offload host gather have their own protocols.
        self._stage = mesh is None and backend is None
        if self.wire.packed:
            from fast_tffm_tpu.models.fm import (make_packed_rows_score_fn,
                                                 make_packed_score_fn)
            self._packed_fn = (make_packed_rows_score_fn(spec)
                               if backend is not None
                               else make_packed_score_fn(spec))

    def score_batch(self, table, batch) -> "object":
        """Raw [B] scores (device-resident) for one DeviceBatch —
        labels/weights dropped here so callers can't accidentally ship
        them. Deliberately does not materialize to numpy (see
        make_batch_scorer: a per-batch fetch collapses async
        dispatch). The ONE dispatch for batch predict and serving,
        so it runs under oom_guard: RESOURCE_EXHAUSTED re-raises with
        the per-owner ledger attached (obs/memory.py)."""
        from fast_tffm_tpu.obs.memory import oom_guard
        with oom_guard("score/dispatch"):
            wb = self.encoder.encode_score(batch)
            if wb.packed:
                if self.backend is not None:
                    gathered = self.backend.gather(wb.host_uniq)
                    return self._packed_fn(wb.L, gathered, **wb.args)
                args = self.encoder.device_put(wb)
                return self._packed_fn(wb.L, table, **args)
            args = (self.encoder.device_put(wb) if self._stage
                    else dict(wb.args))
            return self._score(table, args)

    def score_packed_shape(self, table, B: int, L: int, P: int):
        """Dispatch an all-padding synthetic batch at one
        (B, L, flat-rung) shape — the serving warmup walks every rung a
        flush could encode to, so packed mode keeps the no-recompile
        guarantee (serve/server._warmup). Raw-ids (dedup=device)
        scorers only — exactly the shape the server forces."""
        if not self.wire.packed or not self.raw:
            raise ValueError("score_packed_shape warms the packed "
                             "raw-ids scorer only")
        from fast_tffm_tpu.wire import NARROW_VALUE_DTYPE
        vdt = (NARROW_VALUE_DTYPE if self.wire.narrow else np.float32)
        args = {"uniq_ids": None,
                "lengths": np.zeros(B, dtype=np.int32),
                "flat_idx": np.full(P, self.spec.vocabulary_size,
                                    dtype=np.int32),
                "flat_vals": np.zeros(P, dtype=vdt)}
        if self.spec.model_type == "ffm":
            args["flat_fields"] = np.zeros(P, dtype=np.int32)
        return self._packed_fn(L, table, **args)


class ScoreWriter:
    """Ordered score-file writer on a small background thread, so the
    next file's parse/score/D2H overlaps the previous file's disk
    write instead of serializing behind it. Submission order IS write
    order (one queue, one writer), the queue is bounded (at most 2
    files' scores buffered — the sweep's backpressure), and
    ``close()`` in the caller's finally flushes everything and
    surfaces any deferred write error — a predict() return means every
    score file is on disk. Each write is a ``predict/write`` span on
    the ``fm-score-writer`` track in fmtrace plus an always-on
    ``predict/write_seconds`` counter (the write share of the fmstat
    predict attribution).

    ``submit(..., marker=path)`` additionally creates an empty marker
    file AFTER the score file is durably written+closed — the
    multi-process chief's merge thread keys on these, so a marker's
    existence certifies its part file is complete."""

    def __init__(self, logger):
        import queue
        self._logger = logger
        self._q: "queue.Queue" = queue.Queue(maxsize=2)
        self._sentinel = object()
        self._lock = threading.Lock()  # guards _error (worker writes,
        # submit/close read; fmlint R008)
        self._error: Optional[BaseException] = None
        self._closed = False
        self._thread = threading.Thread(target=self._run,
                                        name="fm-score-writer",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while True:
            job = self._q.get()
            if job is self._sentinel:
                return
            with self._lock:
                dead = self._error is not None
            if dead:
                # Drain-and-discard: the run is already doomed (the
                # error surfaces at the next submit()/close()); keep
                # unblocking producers, stop burning I/O on writes
                # that would land beside a failed one.
                continue
            out_path, vals, marker = job
            try:
                # The always-on predict/write_seconds counter is the
                # fmstat write-share row.
                with span("predict/write",
                          seconds="predict/write_seconds",
                          path=os.path.basename(out_path)):
                    with open(out_path, "w") as fh:
                        for v in vals:
                            fh.write(f"{v:.6f}\n")
                    if marker is not None:
                        # Created only after the score file closed: the
                        # marker certifies completeness to the merge
                        # thread watching the shared filesystem.
                        with open(marker, "w"):
                            pass
                self._logger.info("wrote %d scores to %s", len(vals),
                                  out_path)
            except BaseException as e:  # surfaced at submit()/close()
                with self._lock:
                    if self._error is None:  # keep the FIRST failure
                        self._error = e

    def submit(self, out_path: str, vals: np.ndarray,
               marker: Optional[str] = None) -> None:
        with self._lock:
            err = self._error
        if err is not None:
            raise err
        self._q.put((out_path, vals, marker))

    def close(self, raise_error: bool = True) -> None:
        if not self._closed:
            self._closed = True
            self._q.put(self._sentinel)
            self._thread.join()
        if raise_error:
            with self._lock:
                err = self._error
            if err is not None:
                raise err


class ScoreDemux:
    """Cut an ordered score stream into per-file arrays via the
    pipeline's ``FileMarks`` ledger.

    ``consume(scores)`` appends the next in-order slice of the sweep's
    example stream; whenever the ledger shows a LATER file has started
    (entry i+1 exists and the consumed count has reached its start),
    file i is complete — its span ``[starts[i], starts[i+1])`` is cut
    and handed to ``on_file(path, vals)`` in sweep order. One batch can
    complete several small files (a batch spanning files A|B|C cuts A
    and B in one consume); ``finalize()`` (call only after every score
    landed) cuts the tail — the last file ends at the consumed total,
    and trailing EMPTY files get their zero-length arrays (a zero-line
    input still owes a zero-line ``.score``).

    Threading: the single-process sweep calls ``consume`` from the
    ChunkedFetcher overlap worker (one thread, in add order) and
    ``finalize`` from the caller thread after ``flush()`` joined that
    worker; the lockstep sweep is single-threaded. State here is
    therefore single-writer at any moment and needs no lock — the
    ledger reads go through FileMarks' own lock."""

    def __init__(self, marks: FileMarks,
                 on_file: Callable[[str, np.ndarray], None]):
        self._marks = marks
        self._on_file = on_file
        self._bufs: "collections.deque" = collections.deque()
        self._buf_start = 0   # sweep offset of the first buffered score
        self._consumed = 0    # total scores consumed so far
        self._next = 0        # index of the next file to cut
        self.files_emitted = 0

    def consume(self, scores: np.ndarray) -> None:
        if len(scores):
            self._bufs.append(scores)
            self._consumed += len(scores)
        self._cut_ready(self._marks.snapshot())

    def _cut_ready(self, starts) -> None:
        while (self._next + 1 < len(starts)
               and self._consumed >= starts[self._next + 1][1]):
            self._emit(starts[self._next][0], starts[self._next + 1][1])
            self._next += 1

    def _emit(self, path: str, end: int) -> None:
        n = end - self._buf_start
        take: List[np.ndarray] = []
        while n > 0:
            head = self._bufs[0]
            if len(head) <= n:
                take.append(self._bufs.popleft())
                n -= len(head)
            else:
                take.append(head[:n])
                self._bufs[0] = head[n:]
                n = 0
        self._buf_start = end
        vals = (np.concatenate(take) if take
                else np.zeros(0, dtype=np.float32))
        self.files_emitted += 1
        self._on_file(path, vals)

    def finalize(self) -> None:
        """Cut everything still open. Only call once every score has
        been consumed (after ChunkedFetcher.flush / the lockstep drain):
        the files the ledger still holds open end at the consumed
        total."""
        starts = self._marks.snapshot()
        self._cut_ready(starts)
        for i in range(self._next, len(starts)):
            end = (starts[i + 1][1] if i + 1 < len(starts)
                   else self._consumed)
            self._emit(starts[i][0], end)
        self._next = len(starts)
        if self._buf_start != self._consumed:
            raise AssertionError(
                f"score demux leak: {self._consumed - self._buf_start} "
                f"scores consumed but never assigned to a file (ledger "
                f"has {len(starts)} entries)")


def score_sweep(cfg: FmConfig, table, files: Sequence[str],
                on_file: Callable[[str, np.ndarray], None],
                mesh=None, backend=None, vocab=None,
                before_first_dispatch: Optional[Callable[[], None]] = None
                ) -> int:
    """Single-process continuous scoring sweep: one batch stream over
    ALL ``files`` (keep_empty: score files stay line-aligned), one
    overlap ChunkedFetcher for the whole sweep, per-file RAW score
    arrays demuxed to ``on_file`` in sweep order as each file's last
    batch lands. Returns the number of examples scored.

    ``on_file`` runs on the fetch worker thread mid-sweep (tail files
    on the caller thread at finalize) — callers hand the arrays to a
    ScoreWriter/accumulator, both safe there. No per-file warmup, no
    per-file fetcher drain: the compiled scorer and the D2H overlap
    worker live across every boundary, which is where the 15x
    predict-vs-train gap lived (ISSUE 10; ROADMAP S4 re-measures).

    The host loop's phases are spans (obs/trace.py): ``predict/
    input_wait`` around each next() and ``predict/score_dispatch``
    around each dispatch on this thread, ``predict/drain`` for the
    tail, ``fetch/bulk`` and ``predict/write`` on their workers (and
    predict()'s ``predict/write_wait``); ``before_first_dispatch`` lets
    predict() end its ``predict/setup`` phase where the sweep's work
    begins."""
    files = list(files)  # consumed twice (span field + iterator)
    scorer = CompiledScorer(cfg, mesh=mesh, backend=backend)
    from fast_tffm_tpu.models.fm import regime_line
    from fast_tffm_tpu.utils.logging import get_logger
    get_logger().info("predict regime: %s", regime_line(scorer.spec, cfg))
    marks = FileMarks()
    demux = ScoreDemux(marks, on_file)
    fetcher = ChunkedFetcher(
        lambda s, num_real: demux.consume(s[:num_real]), overlap=True)
    tel = active()
    if tel is not None:
        # The active wire mode, as gauges — fmstat's transfer-bound
        # attribution names it (README "Wire format").
        tel.set("wire/packed", 1.0 if scorer.wire.packed else 0.0)
        tel.set("wire/narrow", 1.0 if scorer.wire.narrow else 0.0)
    n_examples = 0
    # try/finally (ADVICE round 5): an exception mid-sweep must not
    # leave the overlap worker parked on queue.get forever with a
    # queued chunk of device score arrays pinned in HBM — close()
    # drains and joins the worker without masking the original error.
    try:
        with span("predict/sweep", leaf=False, files=len(files)):
            # ``vocab`` (vocab_mode = admit): the pipeline builds in
            # the hashed space and remaps through the checkpoint's
            # slot map — the sweep scores exactly the rows training
            # assigned (predict.py loads the (table, slot map, step)
            # triple together).
            it = prefetch(
                batch_iterator(cfg, files, training=False, epochs=1,
                               keep_empty=True, raw_ids=scorer.raw,
                               file_marks=marks, vocab=vocab),
                depth=cfg.prefetch_depth,
                gil_bound=gil_bound_iteration(cfg, keep_empty=True))
            while True:
                with span("predict/input_wait"):
                    batch = next(it, None)
                if batch is None:
                    break
                if before_first_dispatch is not None:
                    before_first_dispatch()
                    before_first_dispatch = None
                with span("predict/score_dispatch"):
                    scores = scorer.score_batch(table, batch)
                fetcher.add(scores, batch.num_real)
                n_examples += batch.num_real
                if tel is not None:
                    tel.count("predict/examples", batch.num_real)
                    # Output-order buffer: device score arrays held
                    # back so results land in input order — its depth
                    # is the D2H backlog.
                    tel.observe("predict/fetch_depth",
                                fetcher.pending_depth,
                                bounds=DEPTH_BUCKETS)
                    # Watchdog beat: a scored batch is progress
                    # (obs/health.py).
                    tel.heartbeat()
            # The sweep's tail: this thread waits for the fetch worker
            # to bring the last chunk home, then cuts the tail files
            # (all scores are host-side once flush joined the worker).
            with span("predict/drain"):
                fetcher.flush()
                demux.finalize()
    finally:
        fetcher.close()
    return n_examples


def scrub_stale_parts(out_paths: Sequence[str]) -> List[str]:
    """Remove leftover ``<out>.part*`` files (parts AND ``.done``
    markers, any part index) from a crashed prior multi-process sweep
    into the same ``score_path``. The PartMerger polls markers from
    construction, so a stale marker set would satisfy its first poll
    instantly and merge the OLD run's parts into this run's ``.score``
    — the caller must scrub before any worker writes a fresh part (and
    barrier after, so no fresh part can race the scrub). Returns the
    removed paths (for the caller's log line)."""
    import glob
    removed: List[str] = []
    for out_path in out_paths:
        for stale in sorted(glob.glob(glob.escape(out_path) + ".part*")):
            os.remove(stale)
            removed.append(stale)
    return removed


# The merge thread polls the shared filesystem for part markers at this
# period — cheap (P stat calls) and far below any real file's write
# time.
_MERGE_POLL_SECONDS = 0.05

# After every worker passed the parts-done barrier, every marker is
# durable — a marker still missing this long after that point is a bug
# (or a dead shared filesystem), not a slow writer; raise with the path
# instead of polling forever.
_MERGE_GRACE_SECONDS = 300.0


class PartMerger:
    """The multi-process chief's background merge thread: as each
    file's P part files become complete (their ``.done`` markers
    appear on the shared filesystem), stream-merge them into the final
    ``.score`` file IN FILE ORDER and delete the parts — so the merge
    of file N overlaps the lockstep scoring of file N+1 instead of
    serializing behind two barriers per file (the pre-refactor
    protocol). Byte ranges are contiguous: process i's lines all
    precede process i+1's, so the merge is part order.

    ``finish()`` (after the sweep's parts-done barrier) bounds the
    remaining wait: every marker is durable by then, so a missing one
    is raised by name. ``stop()`` is the error-path teardown — the
    thread exits at the next poll."""

    def __init__(self, out_paths: Sequence[str], num_parts: int,
                 logger):
        self._outs = list(out_paths)
        self._P = num_parts
        self._logger = logger
        self._stop = threading.Event()
        self._done_barrier = threading.Event()  # set after the
        # parts-done collective: flips the poll loop to a deadline
        self._error: Optional[BaseException] = None  # single-writer
        # (merge thread); read by finish() after join
        self.merged: List[str] = []  # merge thread appends, callers
        # read after finish() joined
        self._thread = threading.Thread(target=self._run,
                                        name="fm-part-merger",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        try:
            for out_path in self._outs:
                if not self._wait_parts(out_path):
                    return  # stopped (error path) or grace exceeded
                self._merge_one(out_path)
        except BaseException as e:  # surfaced by finish()
            # fmlint: disable=R008 -- single-writer: only this thread
            # assigns, finish() reads strictly after join()
            self._error = e

    def _wait_parts(self, out_path: str) -> bool:
        missing = [f"{out_path}.part{i}.done" for i in range(self._P)]
        deadline = None
        while True:
            missing = [m for m in missing if not os.path.exists(m)]
            if not missing:
                return True
            if self._stop.is_set():
                return False
            if self._done_barrier.is_set():
                if deadline is None:
                    # fmlint: disable=R003 -- deadline bookkeeping on
                    # the merge thread, not a timed hot loop
                    deadline = time.monotonic() + _MERGE_GRACE_SECONDS
                elif time.monotonic() > deadline:
                    raise FileNotFoundError(
                        f"predict part marker(s) never appeared after "
                        f"the parts-done barrier: {missing[:3]} — a "
                        f"worker's writer claimed success but the "
                        f"shared filesystem never showed its part")
            self._stop.wait(_MERGE_POLL_SECONDS)

    def _merge_one(self, out_path: str) -> None:
        n = 0
        with span("predict/merge", path=os.path.basename(out_path)):
            # Stream the merge in bounded chunks: reading a whole part
            # with fh.read() holds multi-GB strings on the chief for
            # billion-line predicts.
            with open(out_path, "wb") as out_fh:
                for i in range(self._P):
                    with open(f"{out_path}.part{i}", "rb") as fh:
                        while True:
                            chunk = fh.read(8 << 20)
                            if not chunk:
                                break
                            n += chunk.count(b"\n")
                            out_fh.write(chunk)
        for i in range(self._P):
            os.remove(f"{out_path}.part{i}")
            os.remove(f"{out_path}.part{i}.done")
        # fmlint: disable=R008 -- single-writer: only the merge thread
        # appends; finish() reads strictly after join()
        self.merged.append(out_path)
        self._logger.info("wrote %d scores to %s (merged %d parts)",
                          n, out_path, self._P)

    def finish(self) -> List[str]:
        """Called on the chief after the parts-done barrier: every part
        marker is durable, so the thread finishes its remaining merges
        promptly (bounded by the per-marker grace). Joins and re-raises
        any merge error; returns the merged file list in order."""
        self._done_barrier.set()
        self._thread.join()
        if self._error is not None:
            raise self._error
        if len(self.merged) != len(self._outs):
            raise RuntimeError(
                f"part merger finished {len(self.merged)}/"
                f"{len(self._outs)} files — merge thread exited early")
        return list(self.merged)

    def stop(self) -> None:
        """Error-path teardown: ask the thread to exit at its next
        poll and join briefly; never raises (an exception is already
        propagating on the caller)."""
        self._stop.set()
        self._thread.join(timeout=5.0)
