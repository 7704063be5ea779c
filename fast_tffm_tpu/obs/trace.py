"""The one seam that times a stage of a run: ``span()``.

``with span("train/h2d", seconds="train/h2d_seconds"):`` brackets one
stage, and whatever is listening gets the same interval:

- the **counter** named by ``seconds=`` (``*_seconds`` in obs/registry)
  whenever a run's telemetry is active, tracing or not: the always-on
  aggregate fmstat and the benchmark's ``telemetry_window`` reader use;
- a ``span`` **event** (wall-clock start, duration, thread, ``fields``)
  in the run's JSONL stream when the run has ``trace_spans`` on, which
  ``tools/fmtrace`` replays in ui.perfetto.dev (all worker shards, one
  track per process, one row per thread);
- a ``jax.profiler.TraceAnnotation`` of the span's plain name while a
  profiler session is live in the process, **whoever started it**
  (``profile_dir``, a benchmark, ``jax.profiler.start_trace`` by
  hand): the span lands on its thread's line in the trace's
  ``/host:CPU`` plane, on the clock of the device's operations, so an
  idle gap of the device can be named after the phase the host was in.
  The name stays plain so that names group; ``fields`` ride the
  annotation as the event's stats (the step, the width a batch shipped
  at), so a reader of the trace can tell one step's execution from
  another's. ``leaf=False`` (a span
  that encloses a loop: a sweep, a validation pass) keeps the span out
  of the profiler: a gap is named after the host event that covers
  most of it, and that should be the phase, not the loop around it.

These replace the hand-rolled ``perf_counter`` pairs (fmlint R003) and
the train loop's own profiler-gated annotation: counter, event and
annotation cannot drift apart, because one object reads the clock.

Cost discipline: nothing listening (no counter asked for or no active
run, ``trace_spans`` off, no profiler session) is one module-global
read, one ``TraceMe`` flag read (0.1 us) and a shared
``contextlib.nullcontext``: no allocation, nothing timed, so hot loops
call it unconditionally. Listening: two clock reads, plus a locked
float add (counter), one buffered ``sink.emit`` (event), one TraceMe
(annotation). Host values only: a span can NEVER cause a device fetch,
so tracing keeps the zero-mid-stream-fetch contract (pinned by
tests/test_health_trace.py and tests/test_span_seam.py). PERF.md has
the cost measured on the chip.

Spans nest by time containment: Perfetto draws an inner span inside
its enclosing one when both ran on the same (pid, tid) track, so no
explicit parent ids are needed — the thread name IS the track.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Any, Dict, Optional

from fast_tffm_tpu.obs import telemetry as _telemetry

# Shared no-op context: nullcontext instances are stateless and
# reentrant, so every inactive span() returns this one object.
_NULL = contextlib.nullcontext()


def _annotation():
    """jax's TraceMe wrapper, or None in a process that has not
    imported jax (fmstat, fmtrace, chip_smoke.py's parent): no
    profiler session can be live there, and a span must not be what
    imports jax."""
    jax = sys.modules.get("jax")
    return None if jax is None else jax.profiler.TraceAnnotation


def span(name: str, seconds: Optional[str] = None, leaf: bool = True,
         **fields):
    """Context manager timing one stage; see the module docstring for
    who receives the interval. ``seconds`` names the counter it adds
    to; ``leaf=False`` keeps a span that encloses a loop out of the
    profiler's trace.

    ``fields`` (step/epoch/path/...) land verbatim on the span event.
    Two field names are a cross-rank JOIN CONTRACT, not free-form
    annotations (obs/anatomy.py; README "Step anatomy"): ``step`` is
    the global step id and ``wid`` the lockstep window id — every rank
    stamps the same id onto the spans of the same barrier'd step/window
    (the collective protocol guarantees the sequences match), so
    ``fmtrace --anatomy`` can align per-rank clocks on the matched
    release edges and split a collective wait into straggler-wait vs
    transport. Producers gate the stamping on ``anatomy_on()``."""
    tel = _telemetry.active()
    sink = (tel.sink if tel is not None
            and getattr(tel, "trace_spans", False) else None)
    counted = tel if seconds is not None else None
    ann = _annotation() if leaf else None
    annotate = ann is not None and ann.is_enabled()
    if sink is None and counted is None and not annotate:
        return _NULL
    return _Span(name, fields or None, sink, counted, seconds,
                 ann(name, **fields) if annotate else None)


class _Held:
    """A span entered by hand; ``end()`` exits it once."""

    __slots__ = ("_span",)

    def __init__(self, sp):
        self._span = sp
        sp.__enter__()

    def end(self) -> Optional[float]:
        """Exit the span; its seconds the first time, where something
        listened, else None."""
        sp, self._span = self._span, None
        if sp is None:
            return None
        sp.__exit__(None, None, None)
        return getattr(sp, "dur", None)


def begin(name: str, seconds: Optional[str] = None, **fields) -> _Held:
    """``span()`` for a phase that ends in another function or loop
    iteration than it began in (the epoch barrier ends inside the next
    epoch's first step; predict's set-up ends at the sweep's first
    dispatch): opened here, closed by ``.end()``, which does nothing
    the second time, so the holder's ``finally`` may call it too."""
    return _Held(span(name, seconds, **fields))


def anatomy_on() -> bool:
    """Whether the active run wants step-anatomy join keys stamped
    (the ``anatomy`` config knob, default on). Same cost discipline as
    ``span()``: one module-global read + one attribute read, so hot
    producers may call it per window/step."""
    tel = _telemetry.active()
    return tel is not None and getattr(tel, "anatomy", False)


class _Span:
    """One live span. One ``perf_counter`` pair serves the counter and
    the event's duration (monotonic); ``time.time`` gives the event's
    start (the cross-process alignment fmtrace needs to line worker
    tracks up); the annotation opens just inside the pair. ``dur``
    holds the seconds after exit, for a caller that counted with
    ``seconds=`` (so got a real span) and must also keep the pause out
    of a neighbouring sample."""

    __slots__ = ("_name", "_fields", "_sink", "_tel", "_seconds", "_ann",
                 "_wall", "_t0", "dur")

    def __init__(self, name: str, fields: Optional[Dict[str, Any]],
                 sink, tel, seconds: Optional[str], ann):
        self._name = name
        self._fields = fields
        self._sink = sink
        self._tel = tel
        self._seconds = seconds
        self._ann = ann

    def __enter__(self) -> "_Span":
        self._wall = time.time()
        self._t0 = time.perf_counter()
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self.dur = dur = time.perf_counter() - self._t0
        if self._tel is not None:
            self._tel.count(self._seconds, dur)
        if self._sink is not None:
            rec = {"name": self._name, "ts": self._wall, "dur": dur,
                   "tid": threading.current_thread().name}
            if self._fields:
                rec.update(self._fields)
            if exc_type is not None:
                # A span cut by an exception is exactly the one
                # forensics wants flagged on the timeline.
                rec["error"] = exc_type.__name__
            self._sink.emit("span", rec)
        return False
