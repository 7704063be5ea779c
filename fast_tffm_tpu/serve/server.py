"""The long-lived scorer process core (README "Serving").

Request path: callers submit libsvm-formatted lines (the predict file
format; labels accepted and ignored). ``submit`` parses on the caller
thread and enqueues one pending request; the single dispatcher thread
micro-batches concurrent requests — the first request in an admission
window waits at most ``serve_max_wait_ms`` for company, a window
flushes early at ``serve_max_batch`` examples — then pads the flush to
the nearest rung of a pre-compiled shape ladder and scores it with the
raw-gather forward pass (scoring.CompiledScorer with dedup='device':
no U axis, so a flush's device shape is exactly [B rung, L rung]).

Shape discipline is the TPU serving contract: B rungs are powers of
two up to ``serve_max_batch``, L rungs are a doubling subset of the
pipeline's ``bucket_ladder`` (``width_rung_ladder``), and every (B, L)
pair is compiled at startup — steady state never recompiles, whatever
request sizes arrive. ``require_bounded_examples`` guarantees no parsed
example can exceed the ladder.

Hot reload (serve/reload.py drives it): ``reload_step`` restores the
named step through the same verified-restore path every driver uses
(an explicit step is verified, never walked past), then swaps the
table reference under the flush lock. In-flight flushes hold the
(table, step) pair they captured — old tables drain naturally with
their last referencing batch, and every response is tagged with the
step that actually scored it.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.parser import ParsedBlock, parse_lines
from fast_tffm_tpu.data.pipeline import (_ladder_fit, make_device_batch,
                                         require_bounded_examples)
from fast_tffm_tpu.metrics import sigmoid
from fast_tffm_tpu.obs.registry import MetricsRegistry
from fast_tffm_tpu.obs.trace import span
# The scoring module's depth buckets, shared so fmstat never merges
# mismatched bucket sets (queue depth here, fetch depth there).
from fast_tffm_tpu.scoring import DEPTH_BUCKETS
from fast_tffm_tpu.utils.logging import get_logger

# Request-latency histogram bounds, in milliseconds (the fmstat SERVING
# section's p50/p99 source). Sub-millisecond CPU flushes and multi-
# second cold paths both land in a real bucket.
LATENCY_BUCKETS_MS = (0.25, 0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0,
                      100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0)


_STOP = object()


@dataclasses.dataclass(frozen=True)
class ScoreResult:
    """One request's response: transformed scores (sigmoid for
    logistic loss, raw for mse — the same transform batch predict
    writes to .score files) plus the checkpoint step that scored it
    (the hot-reload parity handle: these scores are bit-identical to
    batch predict against that step)."""
    scores: np.ndarray
    step: int


class _Pending:
    """One submitted request waiting for its flush."""

    __slots__ = ("block", "n", "t0", "_lock", "_event", "_scores",
                 "_step", "_error")

    def __init__(self, block: ParsedBlock):
        self.block = block
        self.n = block.batch_size
        # fmlint: disable=R003 -- request-latency sample start; closed
        # by the dispatcher's observe at completion
        self.t0 = time.perf_counter()
        # First completion wins: the dispatcher's _complete and the
        # close path's defensive _fail can race (submit vs close), and
        # a delivered result must never be clobbered into an error.
        self._lock = threading.Lock()
        self._event = threading.Event()
        self._scores: Optional[np.ndarray] = None
        self._step = -1
        self._error: Optional[BaseException] = None

    def _complete(self, scores: np.ndarray, step: int) -> None:
        with self._lock:
            if self._event.is_set():
                return
            self._scores = scores
            self._step = step
            self._event.set()

    def _fail(self, error: BaseException) -> None:
        with self._lock:
            if self._event.is_set():
                return
            self._error = error
            self._event.set()

    def result(self, timeout: Optional[float] = None) -> ScoreResult:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"score request ({self.n} examples) not completed "
                f"within {timeout}s")
        if self._error is not None:
            raise self._error
        return ScoreResult(scores=self._scores, step=self._step)


def batch_rung_ladder(serve_max_batch: int) -> Tuple[int, ...]:
    """Padded batch-width rungs: powers of two from 1 up to the first
    one that covers ``serve_max_batch``. Every flush pads to the
    smallest covering rung, so the compiled-executable count stays
    logarithmic in the batch cap."""
    rungs: List[int] = [1]
    while rungs[-1] < serve_max_batch:
        rungs.append(rungs[-1] * 2)
    return tuple(rungs)


def width_rung_ladder(ladder: Sequence[int],
                      max_features: int) -> Tuple[int, ...]:
    """Padded feature-width rungs: the rung of ``ladder`` that covers
    ``max_features`` and, below it, each rung at most half the one kept
    above. The pipeline's ladder is spaced for training batches of
    thousands of examples, where a pad cell costs the step a scatter
    update; a flush holds at most ``serve_max_batch`` examples and a
    width costs one compiled program per batch rung, so the server
    keeps widths that double, whatever spacing it is handed."""
    kept = [_ladder_fit(max(1, max_features), ladder)]
    for b in reversed(ladder):
        if 2 * b <= kept[-1]:
            kept.append(b)
    return tuple(reversed(kept))


def _concat_blocks(blocks: Sequence[ParsedBlock]) -> ParsedBlock:
    """One CSR block over every request in a flush, in submit order
    (the demux back to requests is the per-request example counts)."""
    if len(blocks) == 1:
        return blocks[0]
    poses = [np.zeros(1, dtype=np.int32)]
    base = 0
    for b in blocks:
        poses.append(b.poses[1:] + base)
        base += int(b.poses[-1])
    fields = None
    if blocks[0].fields is not None:
        fields = np.concatenate([b.fields for b in blocks])
    return ParsedBlock(
        labels=np.concatenate([b.labels for b in blocks]),
        poses=np.concatenate(poses).astype(np.int32),
        ids=np.concatenate([b.ids for b in blocks]),
        vals=np.concatenate([b.vals for b in blocks]),
        fields=fields)


class ScorerServer:
    """The long-lived scorer (module docstring). Lifecycle:

        server = ScorerServer(cfg)        # loads the published step,
                                          # pre-compiles the ladder,
                                          # starts dispatch + reload
        res = server.score_lines(lines)   # or submit() for async
        server.close()                    # drains, stops, flushes

    ``watch=False`` skips the reload thread (unit tests drive
    ``reload_step`` directly; the soak runs the real watcher).

    ``warmup="background"`` moves the shape-ladder precompile off the
    constructor onto a daemon thread: the server comes up ``alive``
    immediately (healthz answers, requests queue and score — slowly,
    compiling on demand) but reports ``ready: false`` until the full
    matrix is compiled. The fleet path uses this so a precompiling
    replica is routed AROUND (proxy routes on ready), not restarted
    (supervisor restarts on alive) — and so healthz never again claims
    a still-compiling server is servable."""

    def __init__(self, cfg: FmConfig, logger=None, watch: bool = True,
                 warmup: str = "sync"):
        import jax
        if jax.process_count() > 1:
            raise ValueError("the serving process is single-process: "
                             "run one server per host behind your load "
                             "balancer, not a lockstep cluster")
        if cfg.lookup != "device":
            raise ValueError(
                "serving requires lookup = device: the raw-gather "
                "scorer's pre-compiled shape ladder has no host-gather "
                "protocol (offload-scale tables belong behind the "
                "batch predict path)")
        # Every parsed example must fit the compiled ladder — the
        # no-recompile guarantee is a shape guarantee.
        require_bounded_examples(cfg, "online serving")
        # Pre-flight capacity (obs/memory.py): the serve plan includes
        # the old+new reload transient — a table that fits alone but
        # cannot hot-reload is an operational trap, refused at startup
        # with the planner's breakdown. No-op when the backend reports
        # no capacity (CPU container).
        from fast_tffm_tpu.obs.memory import preflight_capacity
        preflight_capacity(cfg, "serve")
        self.cfg = cfg
        self._logger = logger or get_logger(log_file=cfg.log_file
                                            or None)
        import os
        self.directory = os.path.abspath(cfg.model_file) + ".ckpt"
        # Telemetry: the server holds its own handle (never the
        # process-global active() — the soak runs batch predict in the
        # same process, and the two streams must not cross). A bare
        # registry stands in when metrics are off so /healthz stats
        # always exist.
        from fast_tffm_tpu.obs.telemetry import make_telemetry
        self._tel = make_telemetry(cfg, "serve")
        self._reg = (self._tel.registry if self._tel is not None
                     else MetricsRegistry())
        # Stamp the declared SLO spec into the serve stream too (the
        # slo_p99_ms objective is measured HERE): `fmstat slo` over
        # the serve metrics file then carries its own spec.
        from fast_tffm_tpu.obs.slo import SloSpec
        SloSpec.from_config(cfg).emit_gauges(self._reg)
        from fast_tffm_tpu.scoring import CompiledScorer
        self._scorer = CompiledScorer(cfg, dedup="device",
                                      serve_ladder=True)
        from fast_tffm_tpu.models.fm import regime_line
        self._logger.info("serve regime: %s",
                          regime_line(self._scorer.spec, cfg))
        # The active wire mode, as gauges (README "Wire format"): the
        # serving flush inherits the packed path through the scorer's
        # encoder, and fmstat's attribution names the mode.
        self._reg.set("wire/packed",
                      1.0 if self._scorer.wire.packed else 0.0)
        self._reg.set("wire/narrow",
                      1.0 if self._scorer.wire.narrow else 0.0)
        # Unbounded vocabulary (vocab_mode = admit; README "Unbounded
        # vocabulary"): requests parse into the hashed id space and
        # every flush remaps through the slot map loaded WITH the
        # table — the (table, slot map, step) triple swaps atomically
        # under _table_lock, so in-flight flushes drain on a coherent
        # pair. Unadmitted ids score through the shared cold row.
        self._admit = getattr(cfg, "vocab_mode", "fixed") == "admit"
        if self._admit:
            from fast_tffm_tpu.vocab.table import VocabMap
            self._build_cfg = VocabMap.build_cfg(cfg)
        else:
            self._build_cfg = cfg
        self._vocab_map = None
        self._b_ladder = batch_rung_ladder(cfg.serve_max_batch)
        self._l_rungs = width_rung_ladder(cfg.bucket_ladder,
                                          cfg.max_features_per_example)
        # A flush pads onto the widths that were compiled: its batches
        # are built on the thinned ladder.
        self._build_cfg = dataclasses.replace(
            self._build_cfg, bucket_ladder=self._l_rungs)
        self._table_lock = threading.Lock()  # guards the (table,
        # served_step) pair: a flush must capture both from the same
        # swap (fmlint R008)
        self._table = None
        self._served_step = -1
        self._published_step = -1
        self._q: "queue.Queue" = queue.Queue()
        # Serializes enqueue against shutdown: a submit that passed
        # the closed gate always lands BEFORE the stop sentinel (the
        # dispatcher flushes it), and a submit after close() always
        # raises — no request can ever be enqueued behind _STOP and
        # silently stranded, and none is failed while actually being
        # scored.
        self._submit_lock = threading.Lock()
        self._closed = False
        self._flushes = 0
        self._start_time = time.time()
        # Readiness, split from liveness (README "Serving fleet"):
        # alive = the process answers (always true of a responding
        # healthz); ready = warmed up AND not mid-reload AND the
        # admission queue below the shed depth. The fleet proxy routes
        # on ready; the supervisor restarts on alive.
        self._warmed = threading.Event()
        self._reloading = threading.Event()
        self._warmup_error: Optional[BaseException] = None
        self._shed_depth = max(8, 2 * cfg.serve_max_batch)
        # Which pointer file this scorer follows (serve_pointer): the
        # canary replica reads ``published-canary`` with fallback to
        # ``published`` (checkpoint.read_pointer).
        self._pointer = getattr(cfg, "serve_pointer", "published")
        self._reg.set("serve/ready", 0.0)
        # Startup load: the published pointer IS the serving contract —
        # an unpublished directory is a config/ops error, not a wait.
        # A failed startup must close the sink it already opened (the
        # metrics stream would otherwise hold a run_start forever).
        try:
            from fast_tffm_tpu.checkpoint import read_pointer
            step = read_pointer(self.directory, self._pointer)
            if step is None:
                raise FileNotFoundError(
                    f"no published checkpoint pointer in "
                    f"{self.directory} — publish one with `python -m "
                    "tools.fmckpt publish <model_file> <step>` or run "
                    "a stream trainer with publish_interval_seconds "
                    "> 0")
            self._load_step(step)
            # The startup load IS a pointer observation: /healthz and
            # the STALE MODEL gauge pair must not read published=-1
            # until the first poll tick (or forever under watch=False).
            self.note_published(step)
            if warmup == "background":
                self._warmup_thread = threading.Thread(
                    target=self._warmup_bg, name="fm-serve-warmup",
                    daemon=True)
                self._warmup_thread.start()
            else:
                self._warmup()
                self._mark_warmed()
        except BaseException:
            if self._tel is not None:
                self._tel.close()
            raise
        self._dispatcher = threading.Thread(target=self._dispatch_loop,
                                            name="fm-serve-dispatch",
                                            daemon=True)
        self._dispatcher.start()
        self._watcher = None
        if watch:
            from fast_tffm_tpu.serve.reload import ReloadWatcher
            self._watcher = ReloadWatcher(
                self, poll_seconds=cfg.serve_poll_seconds,
                jitter=getattr(cfg, "serve_poll_jitter", 0.0),
                seed=cfg.serve_port,
                auto_reload=(getattr(cfg, "serve_reload_mode", "poll")
                             == "poll")).start()
        self._logger.info(
            "serving checkpoint step %d from %s (%d batch x %d width "
            "rungs pre-compiled, max_batch=%d, max_wait=%.1fms, "
            "wire=%s)",
            self._served_step, self.directory, len(self._b_ladder),
            len(self._l_rungs), cfg.serve_max_batch,
            cfg.serve_max_wait_ms, self._scorer.wire.describe())

    # -- model load / hot reload ----------------------------------------

    @property
    def served_step(self) -> int:
        with self._table_lock:
            return self._served_step

    @property
    def published_step(self) -> int:
        """Last pointer value the reload poll observed (gauge mirror);
        -1 before the first poll."""
        return self._published_step

    def _load_step(self, step: int) -> None:
        """Verified restore of an explicit step (raises on integrity
        failure — never silently serves other bytes) + atomic swap.
        In-flight flushes keep the (table, slot map) pair they
        captured until their scores are fetched, so requests mid-air
        across a swap drain on the OLD step and say so in their
        result. Admit mode loads the step's vocab sidecar BEFORE the
        swap — a published step missing its slot map fails the reload
        whole (the previous coherent triple keeps serving) rather
        than pairing a new table with an old map."""
        from fast_tffm_tpu.predict import load_table
        from fast_tffm_tpu.obs.memory import (LEDGER,
                                              device_capacity_bytes,
                                              oom_guard, render_ledger,
                                              table_bytes)
        # Reload transient (README "Memory observability"): a hot
        # reload holds old+new tables until the swap — a silent 2x
        # spike, now gauged per reload. A reload that would EXCEED
        # capacity is refused here, which reload_step turns into the
        # counted-failure keep-serving path (the old coherent triple
        # keeps serving) instead of an XLA OOM killing the fleet.
        old_bytes = LEDGER.owners().get("serve_table", 0)
        new_bytes = table_bytes(self.cfg)
        if old_bytes:
            cap = device_capacity_bytes()
            if cap and LEDGER.live_bytes() + new_bytes > cap:
                raise RuntimeError(
                    f"hot reload of step {step} refused: old+new "
                    f"tables would exceed device capacity "
                    f"({LEDGER.live_bytes() + new_bytes:,} > {cap:,} "
                    f"bytes)\n{render_ledger()}")
            LEDGER.register("serve_reload_table", new_bytes)
        vmap = None
        try:
            if self._admit:
                # The shared inference loader: raises on a missing/torn
                # sidecar — the reload fails whole and the previous
                # coherent triple keeps serving.
                from fast_tffm_tpu.checkpoint import load_vocab_map
                vmap = load_vocab_map(self.cfg, self.directory, step)
            else:
                from fast_tffm_tpu.checkpoint import (
                    refuse_fixed_mode_admit_step)
                refuse_fixed_mode_admit_step(self.cfg, self.directory,
                                             step)
            with oom_guard("serve/reload"):
                table = load_table(self.cfg, step=step)
        except BaseException:
            LEDGER.release("serve_reload_table")
            raise
        with self._table_lock:
            self._table = table
            self._vocab_map = vmap
            self._served_step = int(step)
        # The transient is over once the swap commits (the old table
        # frees when in-flight flushes drain); the gauge keeps the
        # spike's size for fmstat/fmtrace.
        LEDGER.release("serve_reload_table")
        LEDGER.register("serve_table", int(table.nbytes))
        self._reg.set("serve/reload_peak_bytes",
                      float(old_bytes + int(table.nbytes)))
        self._reg.set("serve/served_step", float(step))
        if vmap is not None:
            self._reg.set("serve/vocab_live_rows",
                          float(vmap.live_rows))

    def idle_beat(self) -> None:
        """Watchdog liveness for a traffic-idle server: flushes are
        the normal heartbeat, but a healthy scorer with no requests is
        idle BY DESIGN — the reload poll ticks this so a configured
        stall watchdog (watchdog_stall_seconds on a reused training
        cfg) doesn't brand the lull a stall and dump stacks."""
        if self._tel is not None:
            self._tel.heartbeat()

    def note_published(self, step: int) -> None:
        """Reload-poll bookkeeping: the pointer value last seen, as a
        gauge — fmstat's STALE MODEL verdict compares it against
        serve/served_step at the final flush."""
        self._published_step = int(step)
        self._reg.set("serve/published_step", float(step))

    def reload_step(self, step: int) -> bool:
        """Hot-swap to a newly published step; False (and a counted
        failure) when the step fails verification/restore — the
        previous table keeps serving and the next poll retries. The
        server reports ``ready: false`` for the duration: the fleet
        proxy drains around a reloading replica instead of queueing
        behind its table swap."""
        self._reloading.set()
        self._reg.set("serve/ready", 0.0)
        try:
            with span("serve/reload", step=int(step)):
                self._load_step(step)
        except Exception as e:  # noqa: BLE001 - keep serving old state
            self._reg.count("serve/reload_failures")
            self._logger.warning(
                "hot reload of published step %d failed (%s: %s); "
                "continuing to serve step %d", step, type(e).__name__,
                e, self.served_step)
            return False
        finally:
            self._reloading.clear()
            self._reg.set("serve/ready",
                          1.0 if self.is_ready() else 0.0)
        self._reg.count("serve/reloads")
        self._logger.info("hot-reloaded published checkpoint step %d",
                          step)
        return True

    def external_reload(self, step=None) -> Tuple[bool, int]:
        """The ``POST /reload`` control surface — the reload token the
        fleet supervisor's stagger protocol hands each replica in turn
        (serve_reload_mode = external). ``step=None`` resolves this
        server's configured pointer. Synchronous: returns (ok, the
        step now serving) only after the swap (or its counted
        failure), so the caller can re-admit the replica knowing which
        step it serves."""
        if step is None:
            from fast_tffm_tpu.checkpoint import read_pointer
            step = read_pointer(self.directory, self._pointer)
            if step is None:
                return False, self.served_step
        step = int(step)
        self.note_published(step)
        if step == self.served_step:
            return True, step
        ok = self.reload_step(step)
        return ok, self.served_step

    # -- request path ----------------------------------------------------

    def _parse(self, lines: Sequence[str]) -> ParsedBlock:
        # Build-side config: identical to cfg except admit mode parses
        # into the hashed id space (the flush remaps to physical rows).
        cfg = self._build_cfg
        # keep_empty: one score per request line, exactly the predict
        # alignment contract — a blank line scores as the model bias.
        return parse_lines(
            lines, cfg.vocabulary_size,
            hash_feature_id=cfg.hash_feature_id,
            field_aware=cfg.model_type == "ffm",
            field_num=cfg.field_num,
            max_features_per_example=cfg.max_features_per_example,
            keep_empty=True)

    def submit(self, lines: Sequence[str]) -> _Pending:
        """Parse (on the caller's thread — parse cost never serializes
        behind the dispatcher) and enqueue. Returns the pending handle;
        ``.result(timeout)`` blocks for the flush. A malformed line
        raises ParseError HERE, to this caller only — one bad request
        must never poison a micro-batch of strangers."""
        if self._closed:
            raise RuntimeError("ScorerServer is closed")
        lines = list(lines)
        if len(lines) > self.cfg.serve_max_batch:
            raise ValueError(
                f"request of {len(lines)} lines exceeds serve_max_batch "
                f"= {self.cfg.serve_max_batch}; split the request or "
                "raise the knob")
        block = self._parse(lines)
        pending = _Pending(block)
        if pending.n == 0:
            # Nothing to score: complete inline so an empty request
            # can't wedge an admission window open.
            pending._complete(np.zeros(0, dtype=np.float64),
                              self.served_step)
            return pending
        self._reg.observe("serve/queue_depth", self._q.qsize(),
                          bounds=DEPTH_BUCKETS)
        # The parse above ran outside the lock (it's the expensive
        # part); only the closed-check + put are serialized against
        # close() — see _submit_lock.
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("ScorerServer is closed")
            self._q.put(pending)
        return pending

    def score_lines(self, lines: Sequence[str],
                    timeout: Optional[float] = None) -> ScoreResult:
        """Synchronous request: one transformed score per input line,
        plus the step that scored them."""
        return self.submit(lines).result(timeout)

    # -- dispatcher ------------------------------------------------------

    def _dispatch_loop(self) -> None:
        wait_s = self.cfg.serve_max_wait_ms / 1000.0
        max_batch = self.cfg.serve_max_batch
        carry: Optional[_Pending] = None
        stopping = False
        while not stopping:
            if carry is not None:
                first, carry = carry, None
            else:
                first = self._q.get()
                if first is _STOP:
                    break
            window = [first]
            n = first.n
            # fmlint: disable=R003 -- admission-window deadline
            # bookkeeping, not a timed hot-loop sample
            deadline = time.perf_counter() + wait_s
            while n < max_batch:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt is _STOP:
                    stopping = True
                    break
                if n + nxt.n > max_batch:
                    carry = nxt  # head of the NEXT window
                    break
                window.append(nxt)
                n += nxt.n
            self._flush(window, n)
            if stopping:
                # close() gated submit before queueing the sentinel,
                # so everything behind it is already flushed; a carry
                # captured in the same window still owes its scores.
                if carry is not None:
                    self._flush([carry], carry.n)
                    carry = None

    def _flush(self, window: List[_Pending], n: int) -> None:
        reg = self._reg
        try:
            import jax
            # Per-flush latency decomposition (fmstat SERVING "flush
            # queue/pad/device/reply" row; GET /metrics histograms).
            # The stage clocks ride timestamps the flush path already
            # takes or bracket work it already does — no new device
            # fetches; the one blocking fetch stays the score_batch
            # device_get below.
            t0 = time.perf_counter()
            reg.observe("serve/queue_wait_ms",
                        (t0 - min(p.t0 for p in window)) * 1000.0,
                        bounds=LATENCY_BUCKETS_MS)
            block = _concat_blocks([p.block for p in window])
            rung = next(b for b in self._b_ladder if b >= n)
            with self._table_lock:
                table = self._table
                step = self._served_step
                vmap = self._vocab_map
            with span("serve/flush", examples=n, rung=rung):
                t_pad = time.perf_counter()
                batch = make_device_batch(block, self._build_cfg,
                                          batch_size=rung,
                                          raw_ids=True)
                if vmap is not None:
                    batch = vmap.remap(batch)
                t_dev = time.perf_counter()
                reg.observe("serve/pad_ms", (t_dev - t_pad) * 1000.0,
                            bounds=LATENCY_BUCKETS_MS)
                raw = np.asarray(jax.device_get(
                    self._scorer.score_batch(table, batch)))[:n]
                reg.observe("serve/device_ms",
                            (time.perf_counter() - t_dev) * 1000.0,
                            bounds=LATENCY_BUCKETS_MS)
            t_reply = time.perf_counter()
            vals = (sigmoid(raw) if self.cfg.loss_type == "logistic"
                    else raw.astype(np.float64))
            reg.count("serve/flushes")
            reg.count("serve/examples", n)
            reg.count("serve/padded_examples", rung - n)
            pos = 0
            # fmlint: disable=R003 -- closes each request's latency
            # sample (feeds the serve/request_latency_ms histogram the
            # fmstat SERVING p50/p99 rows read)
            done = time.perf_counter()
            for p in window:
                p._complete(vals[pos:pos + p.n], step)
                pos += p.n
                reg.count("serve/requests")
                reg.observe("serve/request_latency_ms",
                            (done - p.t0) * 1000.0,
                            bounds=LATENCY_BUCKETS_MS)
            reg.observe("serve/reply_ms",
                        (time.perf_counter() - t_reply) * 1000.0,
                        bounds=LATENCY_BUCKETS_MS)
        except BaseException as e:  # noqa: BLE001 - per-window failure
            # surface: the window's callers get the error, the server
            # keeps serving (the next window may be fine).
            reg.count("serve/flush_errors")
            self._logger.exception("serve flush of %d example(s) failed",
                                   n)
            for p in window:
                p._fail(e)
        # fmlint: disable=R008 -- single-writer: only the dispatcher
        # thread mutates the flush count; close() reads it strictly
        # after join()
        self._flushes += 1
        if self._tel is not None:
            try:
                self._tel.heartbeat()
                self._tel.maybe_flush(self._flushes)
            except Exception:  # noqa: BLE001 - a failed metrics write
                # (ENOSPC on the sink file) must cost telemetry, not
                # kill the dispatcher thread — a dead dispatcher is a
                # silent total outage.
                self._logger.exception(
                    "serve telemetry flush failed; continuing")

    # -- warmup / teardown ----------------------------------------------

    def _warmup(self) -> None:
        """Compile the full [B rung, L rung] matrix before the first
        request: a request shape can only ever pad onto one of these,
        so steady-state latency never pays a compile. (Compiles are
        cached process-wide per (spec, shape) — jax's jit cache plus
        the persistent compilation cache run_tffm enables.)"""
        import jax
        cfg = self._build_cfg
        t0 = time.monotonic()
        with span("serve/warmup", rungs=len(self._b_ladder)
                  * len(self._l_rungs)):
            for B in self._b_ladder:
                for L in self._l_rungs:
                    ids = np.arange(L, dtype=np.int64) % \
                        cfg.vocabulary_size
                    block = ParsedBlock(
                        labels=np.zeros(1, dtype=np.float32),
                        poses=np.asarray([0, L], dtype=np.int32),
                        ids=ids.astype(np.int32),
                        vals=np.ones(L, dtype=np.float32),
                        fields=(np.zeros(L, dtype=np.int32)
                                if cfg.model_type == "ffm" else None))
                    batch = make_device_batch(block, cfg, batch_size=B,
                                              raw_ids=True)
                    if self._vocab_map is not None:
                        batch = self._vocab_map.remap(batch)
                    jax.device_get(
                        self._scorer.score_batch(self._table, batch))
                    if self._scorer.wire.packed:
                        # Packed wire (README "Wire format"): a flush
                        # encodes to ANY flat rung up to B*L, so the
                        # no-recompile guarantee must cover every rung,
                        # not just the one the synthetic batch above
                        # happened to hit.
                        from fast_tffm_tpu.wire import flat_rungs
                        for P in flat_rungs(B, L):
                            jax.device_get(
                                self._scorer.score_packed_shape(
                                    self._table, B, L, P))
        # fmlint: disable=R008 -- single writer: only the warmup
        # thread assigns (one atomic tuple rebind), and readers are
        # ordered behind the _warmed Event set after this returns
        self.compiled_shapes = tuple(
            (B, L) for B in self._b_ladder for L in self._l_rungs)
        self._reg.set("serve/compiled_shapes",
                      float(len(self.compiled_shapes)))
        self._logger.info(
            "pre-compiled %d serve shapes (B rungs %s x L rungs %s) "
            "in %.1fs", len(self.compiled_shapes),
            list(self._b_ladder), list(self._l_rungs),
            time.monotonic() - t0)

    def _mark_warmed(self) -> None:
        self._warmed.set()
        self._reg.set("serve/ready", 1.0 if self.is_ready() else 0.0)

    def _warmup_bg(self) -> None:
        """Background-warmup thread body: compile the ladder, then
        flip ready. A warmup failure leaves the server alive but
        permanently not-ready (counted + logged) — the fleet routes
        around it and the operator sees serve/warmup_errors, instead
        of a constructor traceback racing the supervisor's spawn."""
        try:
            self._warmup()
        except BaseException as e:  # noqa: BLE001 - surface as state
            # fmlint: disable=R008 -- single writer: only the warmup
            # thread assigns this once (atomic rebind); readers merely
            # surface it in healthz after the fact
            self._warmup_error = e
            self._reg.count("serve/warmup_errors")
            self._logger.exception(
                "serve warmup failed; server stays not-ready")
            return
        self._mark_warmed()

    def is_ready(self) -> bool:
        """The proxy-facing readiness bit: warmed up, not mid-reload,
        not shutting down, admission queue below the shed depth.
        Distinct from alive (an answering process) by design — see the
        class docstring."""
        return (self._warmed.is_set()
                and not self._reloading.is_set()
                and not self._closed
                and self._q.qsize() < self._shed_depth)

    def stats(self) -> dict:
        """The /healthz payload: live counters + latency quantiles
        (server-local registry — exists with metrics on or off)."""
        snap = self._reg.snapshot()
        c = snap["counters"]
        lat = self._reg.histogram("serve/request_latency_ms",
                                  bounds=LATENCY_BUCKETS_MS)
        return {
            "status": "ok",
            "alive": True,
            "ready": self.is_ready(),
            "warmed": self._warmed.is_set(),
            "reloading": self._reloading.is_set(),
            "served_step": self.served_step,
            "published_step": self._published_step,
            "queue_depth": self._q.qsize(),
            "requests": int(c.get("serve/requests", 0)),
            "examples": int(c.get("serve/examples", 0)),
            "flushes": int(c.get("serve/flushes", 0)),
            "flush_errors": int(c.get("serve/flush_errors", 0)),
            "reloads": int(c.get("serve/reloads", 0)),
            "reload_failures": int(c.get("serve/reload_failures", 0)),
            "latency_p50_ms": lat.quantile(0.5),
            "latency_p99_ms": lat.quantile(0.99),
            "uptime_seconds": time.time() - self._start_time,
        }

    def metrics_text(self) -> str:
        """The ``GET /metrics`` body: this server's registry in
        Prometheus text exposition format (obs/prom.py) — scrapeable
        without parsing JSONL, from the same snapshot /healthz
        reads."""
        from fast_tffm_tpu.obs.prom import prometheus_text
        return prometheus_text(self._reg.snapshot())

    def close(self) -> None:
        """Drain and stop: no new submissions, every queued request
        flushed, dispatcher + reload threads joined, telemetry closed.
        Idempotent."""
        with self._submit_lock:
            if self._closed:
                return
            self._closed = True
            # Under the lock: every pending already enqueued precedes
            # this sentinel (the dispatcher flushes them all), and no
            # submit can enqueue after it — nothing can be stranded.
            self._q.put(_STOP)
        if self._watcher is not None:
            self._watcher.stop()
        self._dispatcher.join()
        if self._tel is not None:
            self._tel.close(step=self._flushes)
        from fast_tffm_tpu.obs.memory import LEDGER
        LEDGER.release("serve_table")
        LEDGER.release("serve_reload_table")
        # The scoring dispatch's wire double-buffer (registered by the
        # encoder on the first flush/warmup) dies with the dispatcher.
        LEDGER.release("wire_buffers")
        self._logger.info("scorer server closed after %d flushes",
                          self._flushes)


class ScoreClient:
    """In-process client — the test/soak harness's request surface,
    API-matched to what the HTTP front end does over the wire (parse,
    submit, block, return scores + the serving step)."""

    def __init__(self, server: ScorerServer):
        self._server = server

    def score(self, lines: Sequence[str],
              timeout: Optional[float] = 60.0) -> ScoreResult:
        return self._server.score_lines(lines, timeout=timeout)
