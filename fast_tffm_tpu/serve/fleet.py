"""Replica supervisor for the serving fleet (README "Serving fleet").

``run_tffm.py serve --replicas N`` runs THIS process: it spawns N
single-process ScorerServer children (``python -m
fast_tffm_tpu.serve.replica <cfg>``) on ports ``serve_port + i``,
binds the failover proxy (serve/proxy.py) on ``serve_proxy_port`` as
the client-facing front door, and supervises:

- **Health**: every ``serve_health_poll_seconds`` each replica's
  ``/healthz`` is read. ``alive`` (the process answers) drives
  restarts; ``ready`` (warmed, not mid-reload, queue under the shed
  depth) drives proxy routing — a precompiling or reloading replica
  is routed around, never restarted.
- **Restarts**: a dead replica (exited process, or one that stopped
  answering healthz entirely) respawns under capped exponential
  backoff (``serve_restart_backoff_seconds`` base, doubling, capped
  at 16x, reset once the replica reports healthy) — crash loops
  throttle themselves instead of burning the host.
- **Staggered hot reloads**: children run ``serve_reload_mode =
  external`` (their watcher keeps gauges fresh but never reloads);
  the supervisor watches the ``published`` pointer and, when it
  moves, hands each replica a reload token IN TURN — verify at least
  one OTHER replica is ready, POST /reload (synchronous; the replica
  reports not-ready for the duration), wait for it to come back ready
  on the new step, move on. The fleet never cold-stops together: >= 1
  ready replica at every instant of a fleet-wide reload.
- **Canary**: with ``serve_canary_fraction`` > 0 or
  ``serve_canary_shadow``, the LAST replica follows the
  ``published-canary`` pointer (``fmckpt publish --canary``) and the
  proxy directs the configured traffic fraction (or shadow
  duplicates) at it; per-replica step/latency gauges feed the publish
  gate's comparison before a full promotion.
- **Drain**: SIGTERM/SIGINT stops the watchers, SIGTERMs every child
  (each drains its own admission queue), reaps them, closes the
  proxy and the metrics stream, exits 0.

Fleet telemetry (fmstat's FLEET section + ``FLEET DEGRADED``
verdict) is per-replica gauges in the SUPERVISOR's metrics stream —
``fleet/replica<i>_alive/_ready/_step/_queue_depth`` — flushed
eagerly on every ready-count transition so a mid-incident snapshot
shows the degradation window, not just the happy end state.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Callable, List, Optional

from fast_tffm_tpu.obs.registry import MetricsRegistry
from fast_tffm_tpu.serve.proxy import FleetView, Replica, ScoreProxy
from fast_tffm_tpu.utils.logging import get_logger

# Backoff cap, as a multiple of the base: 1, 2, 4, 8, 16, 16, ...
_BACKOFF_CAP_FACTOR = 16.0
# Seconds of healthz SILENCE from a live process before the
# supervisor declares it wedged and kill-restarts it. Time-based, not
# probe-count-based: the window must not shrink with a fast
# serve_health_poll_seconds, because a freshly spawned replica is
# legitimately silent for several seconds (interpreter + jax import)
# before its front end binds — and it answers healthz (alive, not
# ready) from bind onward, warmup included. The clock restarts at
# every spawn, so a long silence always means the HTTP thread is gone
# or the process wedged before bind.
_WEDGED_SILENCE_SECONDS = 60.0
# How long a child gets to drain after SIGTERM before SIGKILL.
_DRAIN_SECONDS = 15.0
# Per-step budget for one replica's staggered reload (reload + come
# back ready).
_RELOAD_STEP_TIMEOUT = 120.0


class RestartPolicy:
    """Capped exponential backoff over an injected clock (unit tests
    drive it with a fake clock). ``record_death`` schedules the next
    allowed restart; ``can_restart`` gates the respawn;
    ``record_healthy`` resets the streak."""

    def __init__(self, base_seconds: float,
                 cap_factor: float = _BACKOFF_CAP_FACTOR,
                 clock: Callable[[], float] = time.monotonic):
        self._base = float(base_seconds)
        self._cap = self._base * float(cap_factor)
        self._clock = clock
        self._failures = 0
        self._not_before = 0.0

    def record_death(self) -> float:
        """Note one death; returns the backoff delay applied."""
        delay = min(self._base * (2.0 ** self._failures), self._cap)
        self._failures += 1
        self._not_before = self._clock() + delay
        return delay

    def can_restart(self) -> bool:
        return self._clock() >= self._not_before

    def record_healthy(self) -> None:
        self._failures = 0
        self._not_before = 0.0

    @property
    def failures(self) -> int:
        return self._failures


class ReplicaProc:
    """One supervised child: the subprocess, its routing row in the
    proxy's FleetView, and its restart policy."""

    def __init__(self, index: int, cfg, cfg_path: str,
                 canary: bool = False, logger=None,
                 clock: Callable[[], float] = time.monotonic):
        self.index = int(index)
        self.cfg = cfg
        self.cfg_path = os.path.abspath(cfg_path)
        self.port = cfg.serve_port + self.index
        self.canary = bool(canary)
        self.row = Replica(self.index, cfg.serve_host, self.port,
                           canary=self.canary)
        self.policy = RestartPolicy(cfg.serve_restart_backoff_seconds,
                                    clock=clock)
        self.proc: Optional[subprocess.Popen] = None
        self.probe_failures = 0
        self.death_seen = False  # this child's exit has been recorded
        self._clock = clock
        # Wedge clock: last moment this replica answered healthz (or
        # was spawned — a fresh child gets the full silence window to
        # import + bind before it can be declared wedged).
        self.last_answer = clock()
        self._logger = logger or get_logger()
        self._log_fh = None

    # -- process lifecycle ----------------------------------------------

    def spawn(self) -> None:
        env = dict(os.environ)
        # Per-replica knobs ride the FM_<KNOB> env convention the
        # replica entry applies (config.apply_env_overrides): its own
        # port, its own metrics shard, external reload mode (the
        # supervisor owns reloads), and the canary pointer on the
        # canary replica.
        env["FM_SERVE_PORT"] = str(self.port)
        env["FM_SERVE_RELOAD_MODE"] = "external"
        if self.canary:
            env["FM_SERVE_POINTER"] = "canary"
        if self.cfg.metrics_file:
            base = self.cfg.metrics_file
            if base == "auto":
                base = self.cfg.model_file + ".metrics.jsonl"
            env["FM_METRICS_FILE"] = f"{base}.r{self.index}"
        # The package must be importable from wherever the child
        # starts — pin the repo root onto PYTHONPATH rather than
        # trusting the supervisor's cwd to survive.
        import fast_tffm_tpu
        root = os.path.dirname(os.path.dirname(fast_tffm_tpu.__file__))
        env["PYTHONPATH"] = root + (
            os.pathsep + env["PYTHONPATH"]
            if env.get("PYTHONPATH") else "")
        if self._log_fh is None:
            self._log_fh = open(
                f"{self.cfg.model_file}.replica{self.index}.log", "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "fast_tffm_tpu.serve.replica",
             self.cfg_path],
            env=env, stdout=self._log_fh, stderr=subprocess.STDOUT)
        self.probe_failures = 0
        self.death_seen = False
        self.last_answer = self._clock()
        self._logger.info(
            "fleet: replica %d%s spawned (pid %d, port %d)",
            self.index, " (canary)" if self.canary else "",
            self.proc.pid, self.port)

    def pid(self) -> Optional[int]:
        return self.proc.pid if self.proc is not None else None

    def exited(self) -> bool:
        return self.proc is None or self.proc.poll() is not None

    def terminate(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            try:
                self.proc.terminate()
            except OSError:
                pass

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            try:
                self.proc.kill()
            except OSError:
                pass

    def reap(self, timeout: float = _DRAIN_SECONDS) -> None:
        if self.proc is not None:
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.kill()
                self.proc.wait()
        if self._log_fh is not None:
            self._log_fh.close()
            self._log_fh = None

    # -- health ----------------------------------------------------------

    def probe(self, timeout: float = 1.0) -> Optional[dict]:
        """One /healthz read; None when the replica doesn't answer."""
        conn = http.client.HTTPConnection(self.cfg.serve_host,
                                          self.port, timeout=timeout)
        try:
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                return None
            return json.loads(body)
        except (OSError, ValueError, http.client.HTTPException):
            return None
        finally:
            conn.close()

    def reload(self, step: int,
               timeout: float = _RELOAD_STEP_TIMEOUT) -> bool:
        """Hand this replica the reload token: synchronous POST
        /reload — returns only after the swap (or its failure)."""
        conn = http.client.HTTPConnection(self.cfg.serve_host,
                                          self.port, timeout=timeout)
        try:
            conn.request("POST", "/reload", body=str(int(step)),
                         headers={"Content-Type": "text/plain"})
            resp = conn.getresponse()
            resp.read()
            return resp.status == 200
        except (OSError, http.client.HTTPException):
            return False
        finally:
            conn.close()

    def is_ready(self) -> bool:
        """Fresh readiness probe (the stagger protocol's view — never
        a cached row: the invariant is about NOW)."""
        h = self.probe()
        return bool(h and h.get("ready"))


def staggered_reload(handles, step: int, reloaded=None,
                     min_other_ready: int = 1,
                     wait_seconds: float = _RELOAD_STEP_TIMEOUT,
                     poll: float = 0.1,
                     sleep: Callable[[float], None] = time.sleep,
                     clock: Callable[[], float] = time.monotonic,
                     logger=None) -> int:
    """The stagger protocol over anything with ``is_ready()`` /
    ``reload(step)`` (ReplicaProc in production, fakes in tests): for
    each handle in turn, wait until >= ``min_other_ready`` OTHER
    handles are ready, hand it the reload token (synchronous; the
    handle is not-ready for the duration), then wait for IT to come
    back ready before moving on — so a fleet-wide reload never has a
    zero-ready instant. Returns the number of successful reloads.
    ``reloaded`` (optional callable) is invoked after each handle
    finishes — the supervisor's flush hook."""
    log = logger or get_logger()
    done = 0
    for h in handles:
        others = [o for o in handles if o is not h]

        def _ready_others():
            return sum(1 for o in others if o.is_ready())

        if others:
            deadline = clock() + wait_seconds
            while _ready_others() < min_other_ready:
                if clock() >= deadline:
                    log.warning(
                        "fleet: stagger stalled — fewer than %d other "
                        "replicas ready; reloading anyway to avoid "
                        "serving stale state forever",
                        min_other_ready)
                    break
                sleep(poll)
        ok = h.reload(step)
        if ok:
            deadline = clock() + wait_seconds
            while not h.is_ready() and clock() < deadline:
                sleep(poll)
            done += 1
        else:
            log.warning("fleet: reload of step %d failed on a replica;"
                        " it keeps serving its previous step", step)
        if reloaded is not None:
            reloaded(h, ok)
    return done


class FleetSupervisor:
    """Own the children, the proxy, and the watch threads. Drive with
    ``start()`` / ``stop()``; ``run_fleet`` wraps it in the signal
    handling the CLI needs."""

    def __init__(self, cfg, cfg_path: str,
                 replicas: Optional[int] = None, logger=None):
        if replicas is not None:
            import dataclasses
            cfg = dataclasses.replace(cfg,
                                      serve_replicas=int(replicas))
        if cfg.serve_replicas < 2:
            raise ValueError(
                "FleetSupervisor needs serve_replicas >= 2 (one "
                "replica is just `run_tffm.py serve`)")
        self.cfg = cfg
        self._logger = logger or get_logger(log_file=cfg.log_file
                                            or None)
        from fast_tffm_tpu.obs.telemetry import make_telemetry
        self._tel = make_telemetry(cfg, "fleet")
        self._reg = (self._tel.registry if self._tel is not None
                     else MetricsRegistry())
        canary_on = (cfg.serve_canary_fraction > 0
                     or cfg.serve_canary_shadow)
        n = cfg.serve_replicas
        self.replicas: List[ReplicaProc] = [
            ReplicaProc(i, cfg, cfg_path,
                        canary=(canary_on and i == n - 1),
                        logger=self._logger)
            for i in range(n)]
        self.view = FleetView([r.row for r in self.replicas])
        self.proxy = ScoreProxy(
            self.view, retry_budget=cfg.serve_retry_budget,
            affinity_header=cfg.serve_affinity_header,
            canary_fraction=cfg.serve_canary_fraction,
            canary_shadow=cfg.serve_canary_shadow,
            max_inflight=cfg.serve_proxy_max_inflight,
            registry=self._reg, logger=self._logger)
        self.proxy_port: Optional[int] = None
        self.directory = os.path.abspath(cfg.model_file) + ".ckpt"
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._polls = 0
        self._last_ready = -1
        self._reg.set("fleet/replicas", float(n))
        self._reg.set("fleet/ready", 0.0)
        self._reg.set("fleet/alive", 0.0)

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "FleetSupervisor":
        for r in self.replicas:
            r.spawn()
        self.proxy_port = self.proxy.start(self.cfg.serve_proxy_port,
                                           host=self.cfg.serve_host)
        self._logger.info(
            "fleet: %d replicas on ports %d..%d, proxy on http://%s:%d",
            len(self.replicas), self.replicas[0].port,
            self.replicas[-1].port, self.cfg.serve_host,
            self.proxy_port)
        for name, fn in (("fm-fleet-health", self._health_loop),
                         ("fm-fleet-reload", self._reload_loop)):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def wait_ready(self, k: int = 1, timeout: float = 120.0) -> bool:
        """Block until >= k replicas are ready (startup convenience
        for drivers and tests)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if sum(1 for r in self.replicas if r.row.is_ready()) >= k:
                return True
            if self._stop.is_set():
                return False
            time.sleep(0.1)
        return False

    def stop(self) -> None:
        """SIGTERM-drain the whole fleet: watchers down, children
        terminated and reaped (each drains its own queue), proxy and
        metrics stream closed. Idempotent."""
        if self._stop.is_set():
            return
        self._stop.set()
        for t in self._threads:
            t.join()
        self.proxy.shutdown()
        for r in self.replicas:
            r.terminate()
        for r in self.replicas:
            r.reap()
        if self._tel is not None:
            self._tel.close(step=self._polls)
        self._logger.info("fleet: drained %d replicas; supervisor "
                          "down", len(self.replicas))

    def pids(self) -> List[Optional[int]]:
        return [r.pid() for r in self.replicas]

    def flush_metrics(self) -> None:
        if self._tel is not None:
            self._tel.barrier_flush(self._polls)

    # -- health loop -----------------------------------------------------

    def _poll_replica(self, r: ReplicaProc) -> None:
        i = r.index
        if r.exited():
            r.row.set_health(False, False)
            if r.proc is not None and not r.death_seen:
                # First observation of this death: schedule the
                # backed-off restart. A flag of its own, not the probe
                # count: a probe that failed just ahead of the exit (a
                # starved host, a kill between the two checks) must
                # not hide the death from the count and the backoff.
                delay = r.policy.record_death()
                r.death_seen = True
                self._reg.count("fleet/deaths")
                self._logger.warning(
                    "fleet: replica %d (pid %s) exited rc=%s; restart "
                    "in %.1fs (failure #%d)", i, r.proc.pid,
                    r.proc.returncode, delay, r.policy.failures)
            if r.policy.can_restart():
                r.spawn()
                self._reg.count("fleet/restarts")
            return
        h = r.probe(timeout=max(
            0.5, self.cfg.serve_health_poll_seconds))
        if h is None:
            r.probe_failures += 1
            r.row.set_health(False, False)
            silence = r._clock() - r.last_answer
            if silence >= _WEDGED_SILENCE_SECONDS:
                self._logger.warning(
                    "fleet: replica %d silent for %.0fs (%d failed "
                    "probes); kill-restarting", i, silence,
                    r.probe_failures)
                r.kill()
                r.reap(timeout=5.0)
                r.policy.record_death()
                self._reg.count("fleet/wedged_kills")
                r.probe_failures = 0
            return
        r.probe_failures = 0
        r.last_answer = r._clock()
        ready = bool(h.get("ready"))
        if ready:
            r.policy.record_healthy()
        r.row.set_health(True, ready,
                         served_step=int(h.get("served_step", -1)),
                         queue_depth=int(h.get("queue_depth", 0)))
        self._reg.set(f"fleet/replica{i}_step",
                      float(h.get("served_step", -1)))
        self._reg.set(f"fleet/replica{i}_queue_depth",
                      float(h.get("queue_depth", 0)))

    def _health_loop(self) -> None:
        poll = self.cfg.serve_health_poll_seconds
        while not self._stop.wait(poll):
            for r in self.replicas:
                try:
                    self._poll_replica(r)
                except Exception:  # noqa: BLE001 - one replica's bad
                    # poll must not starve the others of supervision
                    self._logger.exception(
                        "fleet: health poll of replica %d failed",
                        r.index)
            alive, ready, total, _rows = self.view.counts()
            for r in self.replicas:
                row = r.row.row()
                self._reg.set(f"fleet/replica{r.index}_alive",
                              1.0 if row["alive"] else 0.0)
                self._reg.set(f"fleet/replica{r.index}_ready",
                              1.0 if row["ready"] else 0.0)
            self._reg.set("fleet/alive", float(alive))
            self._reg.set("fleet/ready", float(ready))
            self._polls += 1
            if self._tel is not None:
                self._tel.heartbeat()
            if ready != self._last_ready:
                # Eager flush on every degradation/recovery edge: a
                # mid-incident fmstat snapshot must SEE the gap.
                if self._last_ready >= 0:
                    self._logger.info(
                        "fleet: ready count %d -> %d (of %d)",
                        self._last_ready, ready, total)
                self._last_ready = ready
                self.flush_metrics()

    # -- reload loop (staggered) ----------------------------------------

    def _reload_loop(self) -> None:
        from fast_tffm_tpu.checkpoint import read_pointer, read_published
        poll = self.cfg.serve_poll_seconds
        while not self._stop.wait(poll):
            try:
                # Staleness is judged against what replicas ACTUALLY
                # serve (their last health rows), not a remembered
                # pointer value — a restarted replica loads the fresh
                # pointer itself, and a publish racing startup can
                # never be silently swallowed. Re-handing the token to
                # an already-current replica is a no-op on its side
                # (external_reload's step == served_step fast path).
                step = read_published(self.directory)
                if step is not None:
                    stale = [
                        r for r in self.replicas
                        if not r.canary and r.row.row()["alive"]
                        and r.row.row()["served_step"] != step]
                    if stale:
                        self._stagger(step)
                canary = next((r for r in self.replicas if r.canary),
                              None)
                if canary is not None:
                    cstep = read_pointer(self.directory, "canary")
                    row = canary.row.row()
                    if (cstep is not None and row["alive"]
                            and row["served_step"] != cstep):
                        self._logger.info(
                            "fleet: canary pointer -> step %d; "
                            "reloading the canary replica", cstep)
                        ok = canary.reload(cstep)
                        self._reg.count("fleet/canary_reloads"
                                        if ok else
                                        "fleet/canary_reload_failures")
            except Exception:  # noqa: BLE001 - same posture as the
                # replica-side watcher: a torn tick heals next poll
                self._logger.exception(
                    "fleet: reload poll failed; retrying next tick")

    def _stagger(self, step: int) -> None:
        primaries = [r for r in self.replicas if not r.canary]
        self._logger.info(
            "fleet: published pointer -> step %d; staggered reload "
            "across %d replicas", step, len(primaries))

        def _after(_h, ok):
            self._reg.count("fleet/reloads" if ok
                            else "fleet/reload_failures")
            self.flush_metrics()

        staggered_reload(primaries, step, reloaded=_after,
                         logger=self._logger)


def run_fleet(cfg, cfg_path: str, replicas: Optional[int] = None
              ) -> int:
    """The ``run_tffm.py serve --replicas N`` driver: supervise until
    SIGTERM/SIGINT, then drain the fleet and exit 0."""
    logger = get_logger(log_file=cfg.log_file or None)
    stop = threading.Event()

    def _on_signal(signum, _frame):
        logger.info("fleet: received signal %d; draining", signum)
        stop.set()

    prev = {s: signal.signal(s, _on_signal)
            for s in (signal.SIGTERM, signal.SIGINT)}
    sup = None
    try:
        sup = FleetSupervisor(cfg, cfg_path, replicas=replicas,
                              logger=logger).start()
        stop.wait()
    finally:
        for s, h in prev.items():
            signal.signal(s, h)
        if sup is not None:
            sup.stop()
    return 0
