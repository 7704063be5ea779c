"""Per-run telemetry wiring: registry + sink + the active-run lookup.

Drivers (train/predict/bench) create a ``RunTelemetry`` from the config
(``make_telemetry``) and run their loops under ``activate(tel)``;
instrumented library code (data pipeline, lockstep sharded path, C++
parser wrapper) calls ``active()`` and does nothing when no run is
active — so the default-off cost at every instrumented site is one
module-global read, and no signature anywhere grows a telemetry
parameter.

Multi-process: every process gets its own sink file — process 0 writes
``metrics_file`` itself, process p > 0 writes ``<metrics_file>.p<p>``
(same shared-filesystem assumption checkpoints already make) — with
the process index stamped into the run metadata of every event. The
streams merge at read time (``tools/fmstat`` accepts several files and
folds them through the registry's merge rules), not at run time: a
run-time merge would need a cross-process collective on the hot path.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import time
from typing import Any, Dict, NamedTuple, Optional, Tuple

from fast_tffm_tpu.obs.registry import MetricsRegistry
from fast_tffm_tpu.obs.sink import JsonlSink

_ACTIVE: Optional["RunTelemetry"] = None

# jax.monitoring's names for what makes a program ready to run: the
# backend compile (a persistent-cache load reports under it too, with
# the load's time), the Python trace, and the persistent cache's
# verdict on each request.
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile/cache_hits",
    "/jax/compilation_cache/cache_misses": "compile/cache_misses",
}


def active() -> Optional["RunTelemetry"]:
    """The run telemetry instrumented library code should feed, or None
    (the common, zero-cost case)."""
    return _ACTIVE


def push_active(tel: Optional["RunTelemetry"]):
    """Install ``tel`` as the process-wide active telemetry; returns
    the previous value for ``pop_active``. The non-contextmanager form
    exists for drivers whose try/finally spans hundreds of lines —
    re-indenting the whole train loop under a ``with`` would be worse
    than a push in setup and a pop in the existing finally."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tel
    return prev


def pop_active(prev: Optional["RunTelemetry"]) -> None:
    global _ACTIVE
    _ACTIVE = prev


@contextlib.contextmanager
def activate(tel: Optional["RunTelemetry"]):
    """Make ``tel`` the process-wide active telemetry for the body.
    None passes through (callers don't need their own conditional)."""
    if tel is None:
        yield None
        return
    prev = push_active(tel)
    try:
        yield tel
    finally:
        pop_active(prev)


def config_hash(cfg) -> str:
    """Stable short hash of the full config — two JSONL files with the
    same hash measured the same run shape."""
    import dataclasses
    d = dataclasses.asdict(cfg)
    blob = json.dumps(d, sort_keys=True, default=str).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:12]


def _git_rev() -> Optional[str]:
    import os
    import subprocess
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:
        return None  # telemetry must never block a run on git


def run_meta(cfg, kind: str, process_index: Optional[int] = None,
             process_count: Optional[int] = None) -> Dict[str, Any]:
    """Run metadata stamped into every metrics event: config hash,
    backend, the first device's platform and kind as jax reports them
    (what a reader needs to tell a chip run from a CPU run), the model
    (``model_type``, ``order``, ``factor_num``: an order-3 run's step
    holds a scan an order-2 run's does not), device/process topology,
    git rev. ``process_index`` /
    ``process_count`` override jax's view — the train driver creates
    telemetry BEFORE the cluster join (so bring-up failures land in
    the stream), when jax would still claim a 1-process local world on
    every worker; the launcher-assigned task index and the config's
    worker count are the stable identities. (backend/device_count are
    the pre-join LOCAL view in that case; the driver refreshes the
    meta dict in place once the cluster is up, so metrics events
    carry the real topology.)"""
    import os
    import jax
    dev = jax.devices()[0]
    return {
        "kind": kind,
        "config_hash": config_hash(cfg) if cfg is not None else None,
        "model": None if cfg is None else {
            "model_type": cfg.model_type, "order": cfg.order,
            "factor_num": cfg.factor_num},
        "backend": jax.default_backend(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "process_index": (jax.process_index() if process_index is None
                          else int(process_index)),
        "process_count": (jax.process_count() if process_count is None
                          else int(process_count)),
        "git_rev": _git_rev(),
        "pid": os.getpid(),
        "start_time": time.time(),
    }


class RunTelemetry:
    """One run's registry + sink + flush cadence.

    ``maybe_flush(step)`` writes a metrics event every ``flush_steps``
    steps — host values only, zero device fetches. ``barrier_flush``
    (epoch boundaries, close) additionally bulk-fetches buffered device
    scalars, the only point device arrays are materialized.
    """

    def __init__(self, path: str, meta: Dict[str, Any],
                 flush_steps: int = 0, trace_spans: bool = False,
                 protocol_trace: bool = False,
                 watchdog_stall_seconds: float = 0.0,
                 anatomy: bool = True,
                 mem_pressure_fraction: float = 0.0):
        self.registry = MetricsRegistry()
        self.sink = JsonlSink(path, meta=meta)
        self.flush_steps = max(0, int(flush_steps))
        self._closed = False
        # The loop thread's wall (loop_start .. loop_stop): where the
        # clock stood when train/loop_seconds was last brought up to
        # date, and the partition's counters as the last flush wrote
        # them (what a slow_step event differences against).
        self._loop_t: Optional[float] = None
        self._loop_flushed: Dict[str, float] = {}
        # Every counter as the last flush wrote it, and when (what a
        # feed_stall event differences the feed's against).
        self._flushed: Dict[str, float] = {}
        self._flushed_t = time.perf_counter()
        # The step of the latest heartbeat: what a ``compile`` event is
        # stamped with, so a program compiled mid-run says when.
        self.step = -1
        self._watch_compiles()
        # Span tracing (obs/trace.py): span() reads this flag through
        # active(), so the off cost at every site stays one global read.
        self.trace_spans = bool(trace_spans)
        # Collective-protocol tracing (parallel/liveness.py):
        # guarded_collective reads this through active() the same way.
        self.protocol_trace = bool(protocol_trace)
        # Step anatomy (obs/anatomy.py; README "Step anatomy"): gates
        # the window/step join-key stamping at the producers (train,
        # sharded) and the pre-aggregated anatomy/* phase gauges every
        # flush derives from host counters below — near-zero cost, and
        # NEVER a device fetch (pinned by tests/test_anatomy.py).
        self.anatomy = bool(anatomy)
        # HBM pressure threshold (obs/memory.py; README "Memory
        # observability"): fraction of device capacity at which a
        # flush emits health: hbm_pressure (once per episode). 0
        # disables; also inert when the backend reports no capacity.
        self.mem_pressure_fraction = float(mem_pressure_fraction or 0.0)
        # Compute-plane liveness (parallel/liveness.py): the train/
        # predict drivers attach their HeartbeatLease here so every
        # metrics flush carries per-worker liveness gauges (the fmstat
        # worker table) without the registry growing a liveness import.
        self.lease = None
        # Run-health watchdog (obs/health.py): a daemon thread fed by
        # heartbeat(); owns the stall/stack-dump forensics.
        self.watchdog = None
        if watchdog_stall_seconds and watchdog_stall_seconds > 0:
            from fast_tffm_tpu.obs.health import Watchdog
            self.watchdog = Watchdog(
                self.sink, watchdog_stall_seconds,
                stacks_path=path + ".stacks").start()

    # -- registry passthroughs (the instrumented-site surface) ----------
    def count(self, name: str, n: float = 1.0) -> None:
        self.registry.count(name, n)

    def set(self, name: str, v: float) -> None:
        self.registry.set(name, v)

    def observe(self, name: str, v: float, bounds=None) -> None:
        self.registry.observe(name, v, bounds)

    def add_scalar(self, name: str, step: int, value) -> None:
        """Buffer one (possibly device-array) scalar for the next
        barrier; never fetches here."""
        self.sink.add_scalar(name, step, value)

    def heartbeat(self, step: Optional[int] = None) -> None:
        """Touch the watchdog's progress beat — the train/predict loops
        call this once per step. No watchdog configured: one attribute
        read and out."""
        if step is not None:
            self.step = step
        w = self.watchdog
        if w is not None:
            w.beat(step)

    def record_crash(self, exc: BaseException, step: int = -1) -> None:
        """Write the stream's final forensic event before the sink
        closes: exception type/message, traceback tail, and the ring of
        recent in-memory events (obs/sink.RING_EVENTS) — the "what was
        it doing just before" answer for a crashed run."""
        from fast_tffm_tpu.obs.health import format_crash
        recent = self.sink.recent_snapshot()
        self.sink.emit("crash", {
            "error": f"{type(exc).__name__}: {exc}",
            "traceback": format_crash(exc),
            "step": int(step),
            "recent_events": recent,
        })
        self.sink.flush()

    # -- compiles (jax.monitoring) --------------------------------------
    def _watch_compiles(self) -> None:
        """Count every program jax makes ready while this run is open,
        and write one ``compile`` event a program (``fun_name``,
        seconds, the step it happened at): a steady loop compiles
        nothing, so a count that moves mid-run names a shape the
        warm-up missed. The counters start at 0 so that a reader's
        difference between two snapshots reads 0, not "absent".
        Listeners run on whichever thread compiled; registry and sink
        take their own locks. Removed in close()."""
        import jax.monitoring
        for name in ("compile/backend_compiles",
                     "compile/backend_compile_seconds", "compile/traces",
                     *_CACHE_EVENTS.values()):
            self.registry.count(name, 0)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_jax_duration)
        jax.monitoring.register_event_listener(self._on_jax_event)

    def _on_jax_duration(self, event: str, seconds: float, **kw) -> None:
        if event == _BACKEND_COMPILE:
            self.registry.count("compile/backend_compiles")
            self.registry.count("compile/backend_compile_seconds", seconds)
            self.sink.emit("compile", {"fun_name": kw.get("fun_name"),
                                       "seconds": seconds,
                                       "step": self.step})
        elif event == _JAXPR_TRACE:
            self.registry.count("compile/traces")

    def _on_jax_event(self, event: str, **kw) -> None:
        name = _CACHE_EVENTS.get(event)
        if name is not None:
            self.registry.count(name)

    # -- flush cadence --------------------------------------------------
    def flush_due(self, step: int) -> bool:
        return bool(self.flush_steps) and step % self.flush_steps == 0

    def maybe_flush(self, step: int) -> None:
        if self.flush_due(step):
            self._emit_metrics(step)
            self.sink.flush()

    def barrier_flush(self, step: int) -> None:
        from fast_tffm_tpu.obs.trace import span
        self.heartbeat(step)  # a barrier IS progress — don't let a long
        # epoch-end fetch read as a stall
        # A leaf of the loop's partition, so counted while the loop's
        # clock runs (a train run's epoch barriers) and not in a
        # teardown, a predict sweep or a fleet poll.
        with span("obs/barrier_flush", step=step,
                  seconds=("obs/barrier_flush_seconds"
                           if self._loop_t is not None else None)):
            self._emit_metrics(step)
            self.sink.barrier()

    # -- the loop thread's wall ------------------------------------------
    def loop_start(self) -> None:
        """Start the clock of ``train/loop_seconds``: the wall of the
        thread that drives ``StepLoop.step``, on one anchor that no
        epoch re-sets. The leaf phases (``LOOP_LEAVES``) and the
        residue every flush derives partition it."""
        self._loop_t = time.perf_counter()
        for name in ("train/loop_seconds", "train/slow_steps",
                     *LOOP_LEAVES, *FEED_PLACE, *FEED_STALL):
            self.registry.count(name, 0)
        self._loop_flushed = {}

    def loop_stop(self) -> None:
        """Stop it: what the thread does after its last step (final
        save, export) is no part of the loop's wall."""
        if self._loop_t is not None:
            self._loop_tick(time.perf_counter())
            self._loop_t = None

    def _loop_tick(self, now: float) -> None:
        self.registry.count("train/loop_seconds", now - self._loop_t)
        self._loop_t = now

    def slow_step(self, step: int, wall: float, what: str = "step",
                  **fields) -> None:
        """One ``slow_step`` event: a step (or an epoch barrier) whose
        wall reached ``train.SLOW_STEP_SECONDS`` says which phases the
        time went to, as the growth of every leaf's counter and of the
        residue since the last flush's snapshot (at most
        ``flush_steps`` steady steps beside the stall). Host values
        only."""
        self.count("train/slow_steps")
        if self._loop_t is not None:
            self._loop_tick(time.perf_counter())
        now = loop_partition(self.registry.snapshot()["counters"])
        grew = {k: v - self._loop_flushed.get(k, 0.0)
                for k, v in now.items()}
        self.sink.emit("slow_step", {
            "step": int(step), "what": what, "wall": wall,
            "phases": {k: v for k, v in sorted(
                grew.items(), key=lambda kv: -kv[1]) if v > 0},
            **fields})

    def waited(self, step: int, wall: float, first: bool) -> None:
        """The epochs loop's ``next(feed)`` before ``step`` took
        ``wall`` seconds: an epoch's ``first`` counts them under
        ``pipeline/first_batch_seconds`` too, and one that reached
        ``FEED_STALL_SECONDS`` is a stall of the feed (a shorter one
        costs that comparison and no more)."""
        if first:
            self.count("pipeline/first_batch_seconds", wall)
        if wall >= FEED_STALL_SECONDS:
            self.feed_stall(step, wall)

    def feed_stall(self, step: int, wall: float) -> None:
        """One ``feed_stall`` event: where the feed was, as the growth
        of every counter of ``FEED_STAGES`` since the last flush's
        snapshot, ``window`` seconds ago (a stage that grew by
        ``window`` was at that the whole time; the builders by
        ``window`` times their number). Host values only."""
        stalls, seconds = FEED_STALL
        self.count(stalls)
        self.count(seconds, wall)
        c = self.registry.snapshot()["counters"]
        grew = {k: float(c.get(k, 0.0)) - self._flushed.get(k, 0.0)
                for k in TRAIN_FEED}
        self.sink.emit("feed_stall", {
            "step": int(step), "wall": wall,
            "window": time.perf_counter() - self._flushed_t,
            "stages": {k: v for k, v in sorted(
                grew.items(), key=lambda kv: -kv[1]) if v > 0}})

    def _snapshot(self) -> Dict[str, Any]:
        """The registry as a ``metrics`` event carries it. A run with
        a loop clock gets the loop's wall brought up to this instant
        (so that it holds every leaf counted so far) and the residue
        ``train/loop_unnamed_seconds`` derived beside it: like the
        anatomy gauges at no per-step cost, but under ``counters``,
        where a reader differences it like one."""
        if self._loop_t is not None:
            self._loop_tick(time.perf_counter())
        snap = self.registry.snapshot()
        self._flushed, self._flushed_t = snap["counters"], time.perf_counter()
        if "train/loop_seconds" in snap["counters"]:
            self._loop_flushed = loop_partition(snap["counters"])
            snap["counters"][LOOP_UNNAMED] = self._loop_flushed[
                LOOP_UNNAMED]
        return snap

    def _emit_metrics(self, step: int) -> None:
        snap = self._snapshot()
        lease = self.lease
        if lease is not None:
            # Per-worker liveness row (fmstat worker table): this
            # worker's own heartbeat age plus its share of the lockstep
            # work, as GAUGES — counters fold across processes at merge
            # time, gauges stay per-process (gauges_by_process).
            c = snap["counters"]
            age = lease.age()
            rows = {
                "worker/heartbeat_age_seconds":
                    round(age, 3) if age is not None else -1.0,
                "worker/windows": c.get("lockstep/windows", 0.0),
                "worker/examples": c.get("train/examples",
                                         c.get("predict/examples", 0.0)),
            }
            for k, v in rows.items():
                self.registry.set(k, v)
            snap["gauges"].update(rows)
        if self.anatomy:
            rows = anatomy_gauges(snap)
            for k, v in rows.items():
                self.registry.set(k, v)
            snap["gauges"].update(rows)
        # Device-memory ledger (obs/memory.py): per-owner bytes, live
        # total, peak watermark, capacity + utilization — pure host
        # arithmetic over registered owners, NEVER a device fetch
        # (pinned by tests/test_memory.py, same contract as anatomy).
        from fast_tffm_tpu.obs import memory as _mem
        rows = _mem.ledger_gauges()
        if rows:
            for k, v in rows.items():
                self.registry.set(k, v)
            snap["gauges"].update(rows)
            _mem.maybe_emit_pressure(self)
        self.sink.emit_metrics(step, snap)

    def close(self, step: int = -1) -> None:
        if self._closed:
            return
        self._closed = True
        import jax.monitoring
        for unregister, listener in (
                (jax.monitoring.unregister_event_duration_listener,
                 self._on_jax_duration),
                (jax.monitoring.unregister_event_listener,
                 self._on_jax_event)):
            try:
                unregister(listener)
            except (AssertionError, ValueError):
                # Already gone: something in the process called
                # jax.monitoring.clear_event_listeners().
                pass
        if self.watchdog is not None:
            # Stop BEFORE the final emit/close: a watchdog firing into
            # a closing sink would race the file handle.
            self.watchdog.stop()
        if step >= 0:
            self._emit_metrics(step)
        else:
            self.sink.emit_metrics(-1, self._snapshot())
        self.sink.close()

    # -- shared instrumentation helpers ---------------------------------
    def pipeline_batch(self, batch, pad_id: int,
                       build_seconds: Optional[float] = None,
                       prefix: str = "pipeline") -> None:
        """Per-DeviceBatch pipeline counters: examples/lines, padding
        waste, dedup hit rate inputs, build time. Runs on the pipeline
        (prefetch worker) thread; everything here is host numpy.
        ``prefix``: the plane's (data/pipeline.py ``TRAIN_PLANE``; a
        validation sweep's plane counts under its own)."""
        import numpy as np
        B, L = batch.local_idx.shape

        def count(name, n=1):
            self.count(f"{prefix}/{name}", n)

        count("batches")
        count("examples", batch.num_real)
        # Real feature cells: the builder's own count where it made
        # one, else a pass over the batch's B x L cells here, on the
        # thread every batch goes through (at B = 32768 that pass was
        # 7 to 20 ms a batch and set a four-chip run's pace; PERF.md
        # section 6, PR 33).
        real = batch.nnz
        if batch.uniq_ids is None:
            # raw-ids mode (dedup=device): pad cells hold pad_id
            # directly; the unique set is computed on device, so no
            # dedup-rate numerator exists host-side.
            if real is None:
                real = int((batch.local_idx != pad_id).sum())
        else:
            real_slot = np.asarray(batch.uniq_ids) != pad_id
            # One segment per row shard of the mesh (one list off it).
            shard_rows = real_slot.reshape(batch.row_shards, -1).sum(1)
            if real is None:
                real = int(np.count_nonzero(np.take(real_slot,
                                                    batch.local_idx)))
            count("uniq_rows", int(shard_rows.sum()))
            # The U shipped (ladder rung, pad slots included): rows
            # over slots is the fill of the fitted unique table, the
            # share of the step's gather/scatter slots that do work.
            count("uniq_slots", len(batch.uniq_ids))
            # The fullest shard's rows over a segment's slots: U is the
            # rung the fullest shard fits (pipeline.segment_plan), so
            # this ratio near 1 is a batch near the next rung, which
            # doubles every shard's gather and scatter walk.
            count("shard_rows_max", int(shard_rows.max()))
            count("shard_slots", len(batch.uniq_ids) // batch.row_shards)
        count("feature_slots", B * L)
        count("feature_nnz", real)
        # Cells the lines had and the batch has not (the parsers' cut
        # at max_features_per_example): 0 on a sound configuration.
        count("truncated_cells", batch.truncated)
        if build_seconds is not None:
            count("build_seconds", build_seconds)
            self.observe(f"{prefix}/batch_build_seconds", build_seconds)

    def train_step(self, dt: float, n_examples: int,
                   h2d_bytes: int,
                   h2d_bytes_logical: Optional[int] = None,
                   placed_ahead: bool = False) -> None:
        """Per-train-step host-side points: wall time between step
        dispatches (NOT a device sync — the honest measurable without a
        fetch), examples, H2D payload bytes.

        ``h2d_bytes`` sizes the arrays ACTUALLY dispatched (the wire
        encoder's output — under wire_format = packed that is the flat
        CSR payload, not the padded rectangles); ``h2d_bytes_logical``
        sizes the padded layout the legacy wire would have shipped, so
        the packed-vs-padded savings ratio is observable per run
        (fmstat's bytes-per-example row). Omitted = same as actual
        (the padded wire). ``placed_ahead``: the step's batch reached
        ``StepLoop.step`` already placed (by the feed's own thread)."""
        self.observe("train/step_seconds", dt)
        self.count("train/steps")
        if placed_ahead:
            self.count(PLACED_AHEAD)
        self.count("train/examples", n_examples)
        self.count("train/h2d_bytes", h2d_bytes)
        self.count("train/h2d_bytes_logical",
                   h2d_bytes if h2d_bytes_logical is None
                   else h2d_bytes_logical)


class Phase(NamedTuple):
    """One phase of the step anatomy: the ``*_seconds`` counter its
    spans count into, the spans' names as a trace and the JSONL show
    them, and fmstat's word for it. ``leaf``: one of the phases that
    partition the wall of the thread driving ``StepLoop.step`` (no
    other thread counts into it, and no two of them are ever open at
    once). ``wait``: a cross-rank coordination wait."""
    counter: str
    spans: Tuple[str, ...]
    label: str
    leaf: bool = True
    wait: bool = False


# The step-anatomy phase map (README "Step anatomy"), and the ONE list
# of the host loop's phases: cumulative host-side seconds counters ->
# per-process anatomy/* gauges. Counters fold across processes at merge
# time; the SAME numbers re-emitted as gauges stay per-process
# (gauges_by_process), which is what the fmstat EFFICIENCY section and
# bench --multihost need to rank stragglers. Everything here is a float
# already sitting in the snapshot dict — deriving the gauges can never
# add a device fetch. fmstat's table (obs/attribution.py), the residue
# (LOOP_LEAVES) and the README's list of phases
# (tests/test_loop_phases.py) are read off this map.
ANATOMY_PHASES: Dict[str, Phase] = {
    # an epoch's first next() is pipeline/first_batch (the job's first
    # is the cold plane's), by its dur also in pipeline/first_batch_seconds
    "anatomy/input_wait_seconds": Phase(
        "train/input_wait_seconds",
        ("train/input_wait", "pipeline/first_batch"), "input wait"),
    "anatomy/batch_checks_seconds": Phase(
        "train/batch_checks_seconds", ("train/batch_checks",),
        "batch checks"),
    "anatomy/encode_seconds": Phase(
        "train/encode_seconds", ("train/encode",), "encode"),
    "anatomy/h2d_seconds": Phase(
        "train/h2d_seconds", ("train/h2d",), "h2d"),
    "anatomy/flags_wait_seconds": Phase(
        "train/step_flags_seconds",
        ("train/step_flags", "stream/step_flags"), "flags wait",
        wait=True),
    "anatomy/dispatch_seconds": Phase(
        "train/dispatch_seconds", ("train/step",), "dispatch"),
    "anatomy/bookkeeping_seconds": Phase(
        "train/bookkeeping_seconds", ("train/bookkeeping",),
        "bookkeeping"),
    "anatomy/loss_sync_seconds": Phase(
        "train/loss_sync_seconds", ("train/loss_sync",), "loss sync"),
    "anatomy/log_line_seconds": Phase(
        "train/log_line_seconds", ("train/log_line",), "log line"),
    "anatomy/flush_seconds": Phase(
        "obs/flush_seconds", ("obs/flush",), "metrics flush"),
    "anatomy/checkpoint_pause_seconds": Phase(
        "train/checkpoint_pause_seconds",
        ("train/checkpoint_pause", "checkpoint/publish"),
        "checkpoint pause"),
    # the epoch barrier's parts, in the order they run
    "anatomy/barrier_reports_seconds": Phase(
        "train/barrier_reports_seconds", ("train/barrier_reports",),
        "barrier reports"),
    # an enclosure: train.evaluate()'s leaves partition it (a lockstep
    # sweep is one leaf, its parts counted apart below)
    "anatomy/validation_seconds": Phase(
        "train/validation_seconds", ("train/validation",), "validation",
        leaf=False),
    "anatomy/validation_open_seconds": Phase(
        "validation/open_seconds", ("validation/open",),
        "validation open"),
    "anatomy/validation_first_batch_seconds": Phase(
        "validation/first_batch_seconds", ("validation/first_batch",),
        "validation first batch"),
    "anatomy/validation_input_wait_seconds": Phase(
        "validation/input_wait_seconds", ("validation/input_wait",),
        "validation input wait"),
    "anatomy/validation_score_dispatch_seconds": Phase(
        "validation/score_dispatch_seconds",
        ("validation/score_dispatch",), "validation dispatch"),
    "anatomy/validation_drain_seconds": Phase(
        "validation/drain_seconds", ("validation/drain",),
        "validation drain"),
    "anatomy/validation_auc_seconds": Phase(
        "validation/auc_seconds", ("validation/auc",), "validation auc"),
    "anatomy/validation_lockstep_seconds": Phase(
        "validation/lockstep_seconds", ("validation/lockstep",),
        "validation lockstep"),
    "anatomy/summary_flush_seconds": Phase(
        "train/summary_pause_seconds", ("train/summary_flush",),
        "summary flush"),
    "anatomy/barrier_flush_seconds": Phase(
        "obs/barrier_flush_seconds", ("obs/barrier_flush",),
        "barrier flush"),
    "anatomy/pipeline_open_seconds": Phase(
        "pipeline/open_seconds", ("pipeline/open",), "pipeline open"),
    # other threads', or inside a lockstep validation sweep: no leaves
    "anatomy/host_build_seconds": Phase(
        "pipeline/build_seconds", ("pipeline/build",), "host build",
        leaf=False),
    "anatomy/window_fill_seconds": Phase(
        "lockstep/window_fill_seconds", ("lockstep/window_fill",),
        "window fill", leaf=False),
    "anatomy/allgather_seconds": Phase(
        "lockstep/allgather_seconds", ("lockstep/allgather",),
        "lockstep allgather", leaf=False, wait=True),
    "anatomy/fetch_seconds": Phase(
        "lockstep/fetch_seconds", ("lockstep/score_fetch",),
        "d2h fetch", leaf=False),
}
LOOP_LEAVES = tuple(p.counter for p in ANATOMY_PHASES.values() if p.leaf)
LOOP_UNNAMED = "train/loop_unnamed_seconds"
# Placement a batch ahead, OFF the loop's thread (train.py
# ``StepLoop.feed_place`` under data/pipeline.py ``place_ahead``): the
# steps whose batch reached ``StepLoop.step`` already placed, beside
# ``train/steps``, and the placing thread's seconds inside encode +
# place (span ``feed/place`` on the thread ``fm-place``). No phase of
# the loop: ``train/h2d`` stays the name of placement done ON the
# loop's thread, so the leaves keep summing to the loop thread's wall.
PLACED_AHEAD, PLACE_SECONDS = FEED_PLACE = ("train/placed_ahead",
                                            "train/place_seconds")


class FeedStage(NamedTuple):
    """One stage of a job's feed (data/pipeline.py ``EpochFeed``:
    ``fm-scan`` -> the coordinator on ``prefetch`` over the ring of
    ``fm-build-<i>`` workers -> ``fm-place`` -> the loop), as three
    ``*_seconds`` counters, each the name behind its prefix or None.
    ``work``: inside the stage's own span. ``starved``: whoever takes
    this stage's output waited for it (the get of a ``_read_ahead``
    queue, the coordinator at the ring's head). ``blocked``: the stage
    stood with its output in hand and no room behind it (the put), or,
    the builders', with no task to take: it is ahead of its
    neighbours. ``loop``: counted under the consumer's prefix
    (``train``, a sweep's ``validation``) and not the plane's."""
    label: str
    work: Optional[str]
    starved: Optional[str]
    blocked: Optional[str]
    loop: bool = False


# The feed's stages from the files to the loop: the ONE list that the
# zero-start (``EpochFeed``), a ``feed_stall`` event's ``stages`` and
# fmstat's feed table (obs/attribution.py) are read off. The stage that
# sets the feed's beat is the one that neither waits nor is blocked;
# every stage before it is blocked, every stage behind it starves. The
# last stage's starved seconds are the loop's own ``input_wait``.
FEED_STAGES: Tuple[FeedStage, ...] = (
    FeedStage("scan", "scan_seconds", "fm_scan_get_wait_seconds",
              "fm_scan_put_wait_seconds"),
    FeedStage("scan: file read", "scan_read_seconds", None, None),
    FeedStage("build (all workers)", "worker_build_seconds",
              "ring_wait_seconds", "worker_idle_seconds"),
    FeedStage("emit", "emit_seconds", "prefetch_get_wait_seconds",
              "prefetch_put_wait_seconds"),
    FeedStage("place", "place_seconds", None,
              "fm_place_put_wait_seconds", loop=True),
)
# One next(feed) of the epochs loop that waits this long is a stall of
# the feed (``RunTelemetry.waited``): five to eight device steps, where
# a ``slow_step`` takes 1.0 s of a whole step; tests patch it down.
FEED_STALL_SECONDS = 0.05
FEED_STALL = ("train/feed_stalls", "train/feed_stall_seconds")


def feed_counters(plane: str = "pipeline",
                  loop: Optional[str] = "train") -> Tuple[str, ...]:
    """Every counter of ``FEED_STAGES`` by its full name, for a plane
    that counts under ``plane`` and a consumer under ``loop`` (None: a
    feed whose consumer places for itself has no stage on that side)."""
    return tuple((loop if st.loop else plane) + "/" + name
                 for st in FEED_STAGES if loop or not st.loop
                 for name in (st.work, st.starved, st.blocked) if name)


TRAIN_FEED = feed_counters()    # the training plane's, under pipeline/


def loop_partition(counters: Dict[str, float]) -> Dict[str, float]:
    """The loop thread's wall as one snapshot's counters split it:
    every leaf's seconds and, under ``LOOP_UNNAMED``, the residue:
    ``train/loop_seconds`` less their sum, the wall under no leaf.
    Leaves are disjoint by construction, so a negative residue means a
    nested pair (tests/test_loop_phases.py forbids it)."""
    parts = {name: float(counters.get(name, 0.0)) for name in LOOP_LEAVES}
    parts[LOOP_UNNAMED] = (float(counters.get("train/loop_seconds", 0.0))
                           - sum(parts.values()))
    return parts


def anatomy_gauges(snap: Dict[str, Any]) -> Dict[str, float]:
    """This process's anatomy/* gauge rows for one registry snapshot:
    the phase-seconds counters above, plus the loop's wall and residue,
    the step wall and the example totals the EFFICIENCY math divides
    by. Phases that never ticked are omitted (a predict run has no
    train/ rows and vice versa)."""
    c = snap.get("counters") or {}
    rows = {g: float(c[p.counter]) for g, p in ANATOMY_PHASES.items()
            if c.get(p.counter)}
    if c.get("train/loop_seconds"):
        rows["anatomy/loop_seconds"] = float(c["train/loop_seconds"])
        rows["anatomy/unnamed_seconds"] = float(c.get(LOOP_UNNAMED, 0.0))
    h = (snap.get("hists") or {}).get("train/step_seconds")
    if h and h.get("count"):
        rows["anatomy/step_wall_seconds"] = float(h["sum"])
        rows["anatomy/steps"] = float(h["count"])
    ex = c.get("train/examples", c.get("predict/examples", 0.0))
    if ex:
        rows["anatomy/examples"] = float(ex)
    return rows


def resolve_metrics_path(cfg,
                         process_index: Optional[int] = None
                         ) -> Optional[str]:
    """The JSONL path this process should write, or None when metrics
    are off. ``metrics_file = auto`` follows the sibling-artifact
    convention (<model_file>.tb/, <model_file>.ckpt/):
    <model_file>.metrics.jsonl. Non-chief processes get a .p<i> shard
    suffix so P workers never interleave writes in one file.
    ``process_index`` overrides jax's view (see run_meta) — and stays
    the worker's ORIGINAL index across elastic re-ranks, so one worker
    writes one shard file for the whole run."""
    path = getattr(cfg, "metrics_file", "") or ""
    if not path:
        return None
    if path == "auto":
        path = cfg.model_file + ".metrics.jsonl"
    if process_index is None:
        import jax
        process_index = jax.process_index()
    p = int(process_index)
    return path if p == 0 else f"{path}.p{p}"


def make_telemetry(cfg, kind: str,
                   process_index: Optional[int] = None,
                   process_count: Optional[int] = None
                   ) -> Optional[RunTelemetry]:
    """The driver entry point: a RunTelemetry per the config's metrics
    knobs, or None (the default — metrics_file unset)."""
    path = resolve_metrics_path(cfg, process_index=process_index)
    if path is None:
        return None
    # getattr defaults: tests (and bench) build pared-down cfg objects
    # that predate the tracing/watchdog knobs.
    return RunTelemetry(
        path, meta=run_meta(cfg, kind, process_index=process_index,
                            process_count=process_count),
        flush_steps=cfg.metrics_flush_steps,
        trace_spans=getattr(cfg, "trace_spans", False),
        protocol_trace=getattr(cfg, "protocol_trace", False),
        watchdog_stall_seconds=getattr(cfg, "watchdog_stall_seconds",
                                       0.0),
        anatomy=getattr(cfg, "anatomy", True),
        mem_pressure_fraction=getattr(cfg, "mem_pressure_fraction",
                                      0.0))


def batch_payload_bytes(args: Dict[str, Any]) -> int:
    """Host-side H2D payload size for one batch's arg dict — the
    arrays ACTUALLY about to be dispatched, so callers must pass the
    wire encoder's output, not the padded batch layout (under
    wire_format = packed the two differ by the padding-waste factor,
    and sizing the padded dict here is exactly how train/h2d_bytes and
    fmstat's transfer-bound attribution would silently lie). No device
    interaction."""
    n = 0
    for v in args.values():
        nb = getattr(v, "nbytes", None)
        if nb is not None:
            n += nb  # a plain int attribute on numpy arrays — no fetch
    return int(n)
