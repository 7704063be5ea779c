"""Chunked device->host fetches for scoring sweeps.

A PER-BATCH fetch syncs the dispatch pipeline every step (a mid-stream
fetch stalls async dispatch until the device has caught up), while
holding an unbounded sweep's scores grows device memory linearly.
``ChunkedFetcher`` is the one implementation of the middle road, shared
by every scoring sweep: accumulate device arrays, bulk-``device_get``
every ``chunk`` additions, deliver host arrays to a consumer in input
order.
"""

from __future__ import annotations

from typing import Any, Callable, List, Tuple

import jax
import numpy as np

# Large enough to amortize a fetch's sync, small enough to bound live
# device arrays on huge sweeps (256 x [B] f32 ~ 8 MB at B=8192).
FETCH_CHUNK_BATCHES = 256

# close() gives the worker this long to drain before abandoning it: a
# worker wedged inside a hung device_get (the very stall scenario the
# error path exists for) must not turn teardown into a silent hang
# that masks the propagating exception. An abandoned worker is a
# daemon thread — leaked, but the process stays live and honest.
CLOSE_DRAIN_TIMEOUT_S = 10.0

def bulk_fetch(pairs, consume) -> None:
    """One-shot bulk device->host fetch: ``pairs`` of (value, meta) are
    fetched as ChunkedFetcher.flush fetches a chunk and delivered to
    ``consume(host_array, meta)`` in order. The one entry point for a
    barrier's drain of buffered scalars (ScalarSummaries.flush, the
    metrics sink's): no streaming chunk bookkeeping needed."""
    f = ChunkedFetcher(consume, chunk=len(pairs) + 1)
    for value, meta in pairs:
        f.add(value, meta)
    f.flush()


class ChunkedFetcher:
    """``add(device_array, meta)`` accumulates; every ``chunk`` adds (and
    at the final explicit ``flush()``) the pending arrays are fetched in
    ONE ``jax.device_get`` and ``consume(host_array, meta)`` runs for
    each, in add order.

    ``overlap=True`` double-buffers: full chunks are handed to ONE
    background thread that fetches + consumes while the caller keeps
    dispatching the next chunk's device work — without it the consumer
    loop stalls for the whole D2H transfer each chunk (the dominant
    cost of the predict sweep where D2H is slow). The queue holds at most one chunk (a second
    full chunk blocks the producer), bounding live device arrays to
    3x chunk (one fetching + one queued + the producer's in-build
    pending list); ``consume`` then runs on the worker thread, in add
    order — callers must not read their accumulator state until
    ``flush()`` returns (both callers aggregate and read only after).
    Worker exceptions re-raise at the next ``add``/``flush``; the
    ``flush`` that re-raises also RESETS the fetcher (queued chunks
    were discarded), so a caller may catch and start a fresh sweep on
    the same instance."""

    def __init__(self, consume: Callable[[np.ndarray, Any], None],
                 chunk: int = FETCH_CHUNK_BATCHES,
                 overlap: bool = False):
        self._consume = consume
        self._chunk = chunk
        self._overlap = overlap
        self._pending: List[Tuple[Any, Any]] = []
        self._queue = None
        self._worker = None
        self._err: List[BaseException] = []
        self._abandon = None  # per-worker Event; set by close()

    @property
    def pending_depth(self) -> int:
        """Entries currently held back for in-order delivery: the
        in-build pending list plus any full chunk queued behind the
        worker. A cheap host-side read for telemetry (the predict
        path's output-order buffer-depth gauge) — approximate by
        design: the worker may be mid-fetch on one more chunk."""
        q = self._queue
        return len(self._pending) + (q.qsize() * self._chunk if q else 0)

    def add(self, arr, meta: Any = None) -> None:
        if self._err:
            # Deliver the worker's error through the same drain + join +
            # clear path flush uses — raising here directly would leave
            # the worker parked on its queue forever and the error
            # sticky, breaking the documented reset-for-reuse contract.
            self.flush()
        self._pending.append((arr, meta))
        if len(self._pending) >= self._chunk:
            self._dispatch()

    def _dispatch(self) -> None:
        if not self._pending:
            return
        batch, self._pending = self._pending, []
        if not self._overlap:
            self._fetch_and_consume(batch)
            return
        if self._worker is None:
            import queue
            import threading
            self._queue = queue.Queue(maxsize=1)
            self._abandon = threading.Event()
            # The worker captures ITS queue/error-list/abandon-flag as
            # arguments: an abandoned worker (close() timed out on a
            # wedged fetch) that later unwedges must only ever touch
            # its own orphaned state — never a reused fetcher's fresh
            # queue or errors.
            self._worker = threading.Thread(
                target=self._worker_loop,
                args=(self._queue, self._err, self._abandon),
                name="fetcher", daemon=True)
            self._worker.start()
        self._queue.put(batch)  # blocks while the previous chunk fetches

    def _worker_loop(self, q, err, abandon) -> None:
        while True:
            batch = q.get()
            try:
                if batch is None:
                    return
                if not err and not abandon.is_set():
                    # after an error (or an abandon-path close), drain
                    # without work
                    self._fetch_and_consume(batch)
            except BaseException as e:  # noqa: BLE001 - re-raised to caller
                err.append(e)
            finally:
                q.task_done()

    def flush(self) -> None:
        """Fetch + consume everything added so far; with overlap, also
        drains and joins the worker so callers may read their
        accumulated results after this returns. On a worker error this
        re-raises it ONCE and leaves the fetcher clean for reuse."""
        self._dispatch()
        if self._worker is not None:
            self._queue.put(None)
            self._worker.join()
            self._worker = None
            self._queue = None
        if self._err:
            e = self._err[0]
            self._err.clear()
            raise e

    def close(self) -> None:
        """Abandon-path teardown, for ``finally`` blocks (ADVICE round
        5): without it, an exception mid-sweep leaves the overlap
        worker parked on ``queue.get`` forever and up to one queued
        chunk of device arrays pinned in device memory for the life of
        the process. Drops pending work, drains + joins the worker, and
        swallows worker errors — an exception is usually already
        propagating, and masking it with a secondary fetch error would
        hide the real failure. Idempotent; a no-op after a clean
        ``flush()``; the fetcher remains reusable."""
        self._pending.clear()
        if self._worker is not None:
            import queue
            import time
            self._abandon.set()
            try:
                # Bounded drain: normally at most one queued chunk
                # precedes the sentinel and the worker drops it fast
                # once abandoned; a worker wedged in a hung fetch never
                # frees the slot, so give up at the deadline rather
                # than hang the error path.
                deadline = time.monotonic() + CLOSE_DRAIN_TIMEOUT_S
                sent = False
                while time.monotonic() < deadline:
                    try:
                        self._queue.put(None, timeout=0.1)
                        sent = True
                        break
                    except queue.Full:
                        continue
                if sent:
                    self._worker.join(
                        timeout=max(0.0, deadline - time.monotonic())
                        + 1.0)
                if self._worker.is_alive():
                    # Abandoned (still wedged): orphan its error list
                    # too — its captured abandon flag stays set, so if
                    # it ever unwedges it drains its own queue and
                    # exits without touching this fetcher again.
                    self._err = []
            finally:
                self._worker = None
                self._queue = None
                self._abandon = None
        self._err.clear()

    def _fetch_and_consume(self, pending) -> None:
        # span (obs/trace): every bulk D2H — predict/evaluate chunks
        # AND barrier scalar drains — shows up on the timeline, on the
        # thread that paid for it. Its always-on fetch/d2h_seconds
        # counter is the D2H share of the fmstat predict attribution
        # (one sample per CHUNK — FETCH_CHUNK_BATCHES batches — not
        # per batch).
        from fast_tffm_tpu.obs.trace import span
        with span("fetch/bulk", seconds="fetch/d2h_seconds",
                  n=len(pending)):
            self._fetch_and_consume_inner(pending)

    def _fetch_and_consume_inner(self, pending) -> None:
        arrs = [a for a, _ in pending]
        # device_get on a LIST transfers per array. Device arrays are
        # grouped by (shape, dtype) and each multi-member group of
        # ARRAYS is fetched as ONE stacked transfer (one compiled stack
        # per (arity, shape), compile-cached): a sweep's [B] score
        # chunks. Scalars are never stacked: a program per group size,
        # made ready inside the steady state at the first barrier that
        # fetches the group, costs more than the fetches it saves. They,
        # singletons and non-array values (python floats pass through
        # device_get) ride a single final list fetch.
        groups: dict = {}
        for i, a in enumerate(arrs):
            if isinstance(a, jax.Array):
                groups.setdefault((a.shape, str(a.dtype)), []).append(i)
        fetched: dict = {}
        for (shape, _), idxs in groups.items():
            if len(idxs) > 1 and shape != ():
                import jax.numpy as jnp
                try:
                    host = np.asarray(jax.device_get(
                        jnp.stack([arrs[i] for i in idxs])))
                except (ValueError, TypeError):
                    # (shape, dtype) grouping can still collide arrays
                    # on different devices/shardings, which jnp.stack
                    # rejects; fall back to the per-array list fetch for
                    # that group rather than fail the whole flush.
                    continue
                for i, h in zip(idxs, host):
                    fetched[i] = h
        rest = [i for i in range(len(arrs)) if i not in fetched]
        if rest:
            for i, h in zip(rest, jax.device_get([arrs[i] for i in rest])):
                fetched[i] = h
        for i, (_, meta) in enumerate(pending):
            self._consume(np.asarray(fetched[i]), meta)
