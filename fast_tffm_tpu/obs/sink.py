"""Buffered JSONL event sink with ScalarSummaries' sync-safety rules.

Events buffer in host memory and reach disk only at ``flush()`` —
called from the flush-step cadence and epoch barriers, never per step.
Device scalars (a jitted step's loss is a device array; materializing
it mid-stream stalls the async dispatch pipeline until the device has
caught up, on any device) are buffered AS DEVICE REFERENCES and fetched
in ONE ``utils/fetch.bulk_fetch`` at ``barrier()``, the epoch-boundary
call, under a 1024-entry safety cap (``SCALAR_BUFFER_MAX``). A plain
``flush()`` performs zero device fetches, so a mid-epoch flush cadence
(``metrics_flush_steps``) costs file I/O only.

Thread-safety: ``emit``/``flush``/``close`` serialize on one internal
lock — span events arrive from the prefetch and fetcher worker
threads, and health events from the watchdog thread, concurrently with
the driver's flush cadence. ``add_scalar``/``barrier`` stay
driver-thread-only (they are the device-reference path; see the
sync-safety contract above).

The barrier drain is also the run-health seam for non-finite values
(obs/health.py): the loss scalars are ALREADY host-side right after
the one bulk fetch, so checking them there detects NaN/Inf loss with
zero added device fetches — a ``health`` event with the offending
name and step range rides the same stream.

Crash forensics: the last ``RING_EVENTS`` emitted events are kept in
an in-memory ring; the drivers' ``crash`` event embeds that ring, so
the stream's final line answers "what was the run doing just before
it died" even when everything since the last flush was lost.

One line per event, ``json.dumps``-encoded. ``metrics`` events carry
the run metadata dict every time ("one event per flush with run
metadata"), so any single line is attributable to its run without
scanning backwards for a header.
"""

from __future__ import annotations

import collections
import json
import math
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

# Buffered device-scalar cap: a tiny cadence on a months-long epoch
# must not retain unbounded device scalars; one rare mid-epoch bulk
# sync is the lesser evil.
SCALAR_BUFFER_MAX = 1024

# Host-event buffer cap: spans at per-batch cadence with an epoch-only
# flush would otherwise grow the buffer for a whole epoch. Hitting the
# cap forces a plain flush — file I/O only, safe anywhere, any thread.
EVENT_BUFFER_MAX = 4096

# In-memory ring of recent events embedded in a crash event.
RING_EVENTS = 32


class JsonlSink:
    """Append-mode JSONL writer; see module docstring for the buffering
    and sync-safety contract."""

    def __init__(self, path: str, meta: Optional[Dict[str, Any]] = None):
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self.path = path
        self.meta = dict(meta or {})
        self._lock = threading.Lock()
        self._events: List[str] = []
        self._scalars: List[Tuple[str, int, Any]] = []
        self.recent: "collections.deque" = collections.deque(
            maxlen=RING_EVENTS)
        self._fh = open(path, "a", encoding="utf-8")
        self._closed = False
        self._fh_closed = False
        self.emit("run_start", {"meta": self.meta})

    def emit(self, event: str, fields: Optional[Dict[str, Any]] = None
             ) -> None:
        """Queue one host-value event (no device arrays — those go
        through add_scalar). Buffered until flush(). Thread-safe: span/
        health events arrive from worker threads."""
        rec = {"event": event, "t": time.time()}
        if fields:
            rec.update(fields)
        line = json.dumps(rec, default=_json_default)
        overflow = False
        with self._lock:
            if self._fh_closed:
                # A late span from a never-joined daemon thread
                # (prefetch, fetcher) after run_end: drop it — writing
                # would raise on the closed handle in that thread.
                return
            self._events.append(line)
            self.recent.append(rec)
            overflow = len(self._events) >= EVENT_BUFFER_MAX
        if overflow:
            self.flush()  # host file I/O only — safe from any thread

    def recent_snapshot(self) -> List[Dict[str, Any]]:
        """A stable copy of the recent-event ring. Must take the lock:
        worker threads append concurrently, and iterating a mutating
        deque raises — which would lose the crash event exactly when
        it matters."""
        with self._lock:
            return list(self.recent)

    def emit_metrics(self, step: int, snapshot: Dict[str, Any]) -> None:
        """One metrics event per flush, run metadata included."""
        self.emit("metrics", {"step": int(step), "run": self.meta,
                              **snapshot})

    def add_scalar(self, name: str, step: int, value: Any) -> None:
        """Queue one scalar whose value may be a DEVICE array; it is
        not fetched here — barrier() bulk-fetches the whole buffer.
        Driver-thread-only (the device-reference path)."""
        self._scalars.append((name, int(step), value))
        if len(self._scalars) >= SCALAR_BUFFER_MAX:
            self._drain_scalars()

    def flush(self) -> None:
        """Write buffered events to disk. ZERO device fetches: queued
        device scalars stay queued until the next barrier()."""
        with self._lock:
            if self._fh_closed:
                self._events.clear()
                return
            events, self._events = self._events, []
            if events:
                self._fh.write("\n".join(events) + "\n")
            self._fh.flush()

    def _drain_scalars(self) -> None:
        if not self._scalars:
            return
        # ONE bulk fetch for the whole buffer (the entry point
        # ScalarSummaries.flush uses too).
        from fast_tffm_tpu.utils.fetch import bulk_fetch
        rows: List[Tuple[str, int, float]] = []
        bulk_fetch([(v, (name, step))
                    for name, step, v in self._scalars],
                   lambda v, m: rows.append(
                       (m[0], m[1], float(v))))  # host array post-fetch
        self._scalars.clear()
        bad: Dict[str, List[int]] = {}
        for name, step, val in rows:
            self.emit("scalar", {"name": name, "step": step, "value": val})
            # Only LOSS scalars escalate to a health event: a NaN
            # validation AUC is a legitimate value (a shard with no
            # positives or no negatives — StreamingAUC.result), and
            # flagging it would mark healthy runs NONFINITE. The raw
            # scalar event above still records it for forensics.
            if "loss" in name and not math.isfinite(val):
                bad.setdefault(name, []).append(step)
        # Non-finite detection rides the fetch that just happened: the
        # values are host floats here, so this costs zero extra device
        # traffic (obs/health.py's contract).
        for name, steps in bad.items():
            self.emit("health", {
                "status": "nonfinite_loss",
                "name": name,
                "step_first": min(steps), "step_last": max(steps),
                "count": len(steps),
            })

    def barrier(self) -> None:
        """Epoch/shutdown barrier: bulk-fetch queued device scalars into
        scalar events, then flush everything to disk."""
        self._drain_scalars()
        self.flush()

    def discard_scalars(self) -> int:
        """Drop queued device scalars WITHOUT fetching them — the
        compute-plane recovery path (parallel/liveness.py): after a
        peer dies, a buffered loss scalar may be the output of a
        collective program that will never complete, and draining it
        would park the survivor in the exact hang the deadline guard
        just escaped. Returns the number dropped (recorded by the
        caller's telemetry so the gap is visible, not silent)."""
        n = len(self._scalars)
        self._scalars.clear()
        return n

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            # Drain scalars BEFORE queueing run_end so the stream's
            # last event is always run_end (readers key "run finished
            # cleanly" off it).
            self._drain_scalars()
            self.emit("run_end", {})
            self.flush()
        finally:
            # Close the handle UNDER the lock and flag it first: a
            # worker-thread emit/flush racing this sequence sees the
            # flag and drops its event instead of writing to (or
            # overflowing into) a closed file.
            with self._lock:
                self._fh_closed = True
                self._fh.close()


def _json_default(o: Any):
    """Numpy scalars/arrays sneak into host-value events (counter sums,
    batch shapes); coerce rather than crash a telemetry flush."""
    for attr in ("item",):
        f = getattr(o, attr, None)
        if callable(f):
            try:
                return f()
            except Exception:  # fmlint: disable=R004 -- probing an
                # .item() coercion; a failure falls through to the
                # tolist/str fallbacks below, nothing is swallowed
                pass
    if hasattr(o, "tolist"):
        return o.tolist()
    return str(o)


def read_events(path: str) -> Iterator[Dict[str, Any]]:
    """Parse a metrics JSONL file (or a worker shard of one). Tolerates
    a torn final line — a crashed run's file must still summarize."""
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except ValueError:
                continue  # torn tail of a crashed run
