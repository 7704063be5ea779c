"""Train driver — the ``py/fm_train.py`` equivalent (SURVEY.md §3.1/§3.2).

Single-process: build state, jit the step, run the hot loop (one device
dispatch per step, Python only loops and logs — the property the
reference gets from ``sess.run`` it gets here from ``jax.jit``).

Distributed: where the reference launches ps/worker roles over TF1's gRPC
runtime with *async* SGD, this framework is synchronous data-parallel over
a device mesh (parallel/), with the table row-sharded across it; the
``dist_train <job> <idx>`` CLI surface is accepted and mapped onto
``jax.distributed`` (parallel/distributed.py).
"""

from __future__ import annotations

import signal
import time
from typing import Optional, Tuple

import jax
import numpy as np

from fast_tffm_tpu.checkpoint import (SAVE_COUNTERS, CheckpointState,
                                      check_restored_vocab,
                                      checkpoint_template, ckpt_state,
                                      export_npz, place_restored,
                                      resume_start_epoch, saver_buffers)
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.badlines import BadLineTracker
from fast_tffm_tpu.data.pipeline import (SPILL_WARN_FRACTION,
                                         VALIDATION_PLANE, EpochFeed,
                                         EpochMark, host_parallel_workers,
                                         uniq_bucket_top)
from fast_tffm_tpu.data.resident import ResidentSweeps
from fast_tffm_tpu.utils.retry import RetryPolicy
from fast_tffm_tpu.metrics import StreamingAUC
from fast_tffm_tpu.models.fm import (ModelSpec, init_accumulator,
                                     init_table, make_batch_scorer,
                                     make_score_placer, make_train_step,
                                     regime_line, score_args,
                                     ships_raw_batches)
from fast_tffm_tpu.obs.memory import (LEDGER, local_bytes_in_use,
                                      oom_guard, preflight_capacity,
                                      table_bytes)
from fast_tffm_tpu.obs.telemetry import (active, make_telemetry,
                                         pop_active, push_active)
from fast_tffm_tpu.obs.trace import begin, span
from fast_tffm_tpu.parallel.sharded import evaluate_distributed
from fast_tffm_tpu.utils.fetch import ChunkedFetcher
from fast_tffm_tpu.utils.logging import get_logger
from fast_tffm_tpu.utils.timing import StepTimer


# A step or epoch barrier whose wall reaches this says where it was
# slow (RunTelemetry.slow_step). Steps take 6 to 20 ms on the chip, the
# stalls this is for 1.5 to 5.4 s (PERF.md); tests patch it down.
SLOW_STEP_SECONDS = 1.0


def sweep_feed(cfg: FmConfig, files, sweeps: range, mesh=None, backend=None,
               max_batches: Optional[int] = None, weight_files=(),
               bad_lines=None, vocab=None) -> ResidentSweeps:
    """The feed ``evaluate()`` reads ``sweeps`` sweeps of ``files``
    from (data/resident.py): a sweep is an epoch of its plane, its
    batches ``batch_iterator(training=False, epochs=1)``'s, at most
    ``max_batches`` of them, each placed for this dispatch path's scorer
    on the plane's own thread. A sweep that fits stays on the device and
    the plane is closed at its mark. Else the plane is held at a mark
    until ``evaluate()`` lets it go: behind the drain; under ``vocab``
    as the next sweep starts (its eval view), and the consumer places."""
    raw = ships_raw_batches(ModelSpec.from_config(cfg), mesh=mesh,
                            backend=backend)
    place = None if vocab is not None else make_score_placer(mesh, backend)
    return ResidentSweeps(
        lambda sweeps: EpochFeed(
            cfg, files, sweeps, place=place, hold=True,
            uniq_bucket=lambda: 0, weight_files=weight_files,
            bad_lines=bad_lines, vocab=vocab, raw_ids=raw, training=False,
            counters=VALIDATION_PLANE, max_batches=max_batches,
            loop="validation"),
        sweeps, tuple(files) + tuple(weight_files),
        places=place is not None, view=vocab is not None)


def evaluate(cfg: FmConfig, table: jax.Array, files,
             max_batches: Optional[int] = None,
             mesh=None, backend=None,
             weight_files=(), bad_lines=None,
             vocab=None, collect=None,
             phases: bool = True,
             feed: Optional[ResidentSweeps] = None) -> Tuple[float, int]:
    """Streamed AUC over ``files``; returns (auc, n_examples). Pass the
    training mesh to score a row-sharded table in place, or a lookup
    ``backend`` (lookup.HostOffloadLookup) to score a host-offloaded
    table (``table`` is then unused). ``weight_files`` (sidecars
    parallel to ``files``) weight each example's AUC contribution the
    same way training weights its loss. ``bad_lines``: the caller's
    run-scoped BadLineTracker — train() shares its tracker so
    per-epoch validation sweeps don't quarantine the same bad line
    once per epoch through fresh dedupe sets. ``collect`` (an
    obs/quality.QualityStats or anything with the same
    ``update(scores, labels, weights)`` surface) is fed the SAME host
    score chunks the AUC update consumes — the publish-gate quality
    loop's zero-added-device-fetch seam. ``phases``: whether the
    sweep's spans count into the loop's partition; a caller that sweeps
    inside a leaf of its own (the stream loop's ``checkpoint/publish``)
    says no, and the interval is counted once.

    ``feed``: the ``sweep_feed`` of a job that sweeps again and again
    (``_Session.validate``: made for these files, this dispatch path
    and the session's cap, which ``max_batches`` and the arguments
    behind it then only repeat). This sweep is its next epoch: its
    batches have been on the device since the job's first sweep or,
    streamed, the first were built and placed while the interval
    trained; it ends at the feed's mark. Without one the sweep reads
    from a feed of ONE sweep, made and closed here: the cold plane."""
    tel = active()
    # The sweep's wall on the calling thread, as leaves of the loop's
    # partition (obs/telemetry.py ANATOMY_PHASES), named as predict's
    # are. ``validation/first_batch`` is the sweep's first next(): a
    # feed's first makes the plane's threads, builders and files (as
    # ``pipeline/first_batch`` does for a job's first epoch), every
    # later sweep of that feed takes a batch that is already placed.
    step = tel.step if tel is not None else -1  # the table's: the last step

    def counter(phase):
        return f"validation/{phase}_seconds" if phases else None

    own = feed is None
    with span("validation/open", seconds=counter("open"), step=step):
        spec = ModelSpec.from_config(cfg)
        score_fn = make_batch_scorer(spec, mesh=mesh, backend=backend)
        auc = StreamingAUC()

        def _consume(scores, m):
            s, y, w = scores[:m[1]], m[0][:m[1]], m[2][:m[1]]
            auc.update(s, y, w)
            if collect is not None:
                collect.update(s, y, w)

        # Chunked fetches (utils/fetch.py): a per-batch sync stalls
        # async dispatch every step, whole-sweep buffering is unbounded.
        fetcher = ChunkedFetcher(
            _consume,
            overlap=True)  # D2H of chunk N overlaps scoring of chunk N+1
        if own:
            feed = sweep_feed(cfg, files, range(1), mesh=mesh,
                              backend=backend, max_batches=max_batches,
                              weight_files=weight_files,
                              bad_lines=bad_lines, vocab=vocab)
        # The interval behind the feed's last sweep is over: counted as
        # fed ahead if this sweep's first batch has left the builders;
        # a vocab's feed (the eval view of now) starts cutting here.
        feed.release(feed.marked)
    n = 0
    n_batches = 0
    # try/finally (ADVICE round 5): an exception mid-sweep must not
    # leave the overlap worker parked on queue.get forever with a
    # queued chunk of device score arrays pinned in HBM — close()
    # drains and joins the worker without masking the original error.
    try:
        while True:
            wait = "input_wait" if n_batches else "first_batch"
            with span("validation/" + wait, seconds=counter(wait),
                      step=step):
                item = next(feed)
            if isinstance(item, EpochMark):
                break
            with span("validation/score_dispatch",
                      seconds=counter("score_dispatch"), step=step):
                batch, args = item
                if args is None:  # a lookup backend's, a held feed's
                    args = score_args(batch)
                fetcher.add(score_fn(table, args),
                            (batch.labels, batch.num_real, batch.weights))
                # the placed arrays are let go under this leaf, not at
                # the next batch's unpacking under none
                item = args = None
                n += batch.num_real
                n_batches += 1
                if tel is not None:
                    # A full validation sweep can outlast the watchdog's
                    # stall budget; scored batches are progress.
                    tel.heartbeat()
        # The tail after the last dispatch: the chip finishes, the last
        # chunk's D2H and its two histogram updates a batch.
        with span("validation/drain", seconds=counter("drain"), step=step):
            fetcher.flush()
        if vocab is None:  # no barrier changes the next sweep's batches:
            feed.release(feed.marked)  # cut them while the interval trains
    finally:
        fetcher.close()
        if own:
            feed.close()
    with span("validation/auc", seconds=counter("auc"), step=step):
        result = auc.result()
    if tel is not None:
        tel.count("validation/sweeps")
        tel.count("validation/batches", n_batches)
        tel.count("validation/examples", n)
    return result, n


class ClusterGrowth(Exception):
    """Control-flow signal out of ``_train_session`` at a safe barrier
    (epoch boundary / publish settle): the chief planned admission of
    replacement worker(s) — ``plan`` is the ``liveness.plan_grow``
    payload — and the barrier state is durably saved, so the elastic
    driver can tear the session down cleanly and reform the grown
    cluster. NOT an error: it must never be recorded as a crash."""

    def __init__(self, plan: dict):
        super().__init__(f"cluster growth planned: generation "
                         f"{plan.get('generation')}")
        self.plan = plan


class _GrowContext:
    """Driver-owned elastic-grow state threaded into the session
    (``elastic = grow``): the CURRENT membership + generation (which
    only the driver's reforms move) and the safe-barrier admission
    check. ``capacity`` is the original cluster size — joiners fill
    the ORIGINAL indices of departed workers, so a healed cluster is
    indistinguishable from one that never shrank."""

    def __init__(self, cfg: FmConfig, lease, members, generation: int):
        self.cfg = cfg
        self.lease = lease
        self.members = tuple(int(m) for m in members)
        self.generation = int(generation)
        self.capacity = max(len(cfg.worker_hosts), 1)

    def adopt(self, members, generation: int) -> None:
        self.members = tuple(int(m) for m in members)
        self.generation = int(generation)

    def check_barrier(self) -> Optional[dict]:
        """The admission check every safe barrier runs: fresh join
        tickets against free original slots -> the next generation's
        plan, or None. Every process runs the same scan and the
        chief's answer is broadcast (identity single-process), so a
        ticket appearing mid-scan can never diverge the cluster —
        all workers raise ClusterGrowth together or nobody does."""
        if self.lease is None or len(self.members) >= self.capacity:
            return None
        from fast_tffm_tpu.parallel import liveness as lv
        tickets = lv.pending_join_tickets(self.lease.directory,
                                          self.lease.stale_after)
        plan = lv.plan_grow(self.generation + 1, self.members,
                            self.capacity, tickets)
        if jax.process_count() > 1:
            from fast_tffm_tpu.data.stream import broadcast_blob
            plan = broadcast_blob(plan, "cluster/grow_decision")
        return plan


def train(cfg: FmConfig, job_name: Optional[str] = None,
          task_index: Optional[int] = None,
          join: bool = False) -> jax.Array:
    """Run training per config; returns the final table (host-fetchable).

    ``job_name``/``task_index`` mirror the reference's ``dist_train``
    argv (SURVEY §3.2); in multi-process mode they identify this process
    in the jax.distributed cluster.

    This is the elastic driver around ``_train_session`` (the actual
    training loop): it owns run-scoped state that must SURVIVE a
    compute-plane recovery — the telemetry stream (one run segment per
    invocation, so worker_lost diagnoses and the recovery both land in
    the same fmstat view), the bad-line tracker (quarantine dedupe
    spans recoveries like it spans epochs), the heartbeat lease, and
    the collective deadline guard. On ``WorkerLostError`` with
    ``elastic = shrink`` the survivors tear the distributed client
    down, reform the cluster from the surviving lease holders
    (``reform_shrunken_cluster``), and re-enter the session — which
    restores from the last verified checkpoint and redistributes the
    lost worker's input shards by re-sharding over the shrunken
    membership. With ``elastic = off`` the error (naming the dead
    peers) propagates: fail fast, never hang.

    ``elastic = grow`` adds the healing direction: the session checks
    for join-request leases at every safe barrier and raises
    ``ClusterGrowth`` (after durably saving the barrier state) when a
    replacement can be admitted — the driver reforms the GROWN cluster
    and re-enters, and the newcomer restores through the same verified
    checkpoint + chief-broadcast path every member uses.

    ``join = True`` is the replacement process itself
    (``run_tffm.py train <cfg> --join``): it rendezvouses into a
    running cluster FIRST (its worker slot is unknown until admitted),
    then runs this same driver loop as an ordinary member."""
    from fast_tffm_tpu.parallel.liveness import (
        HeartbeatLease, WorkerLostError, install_guard, lease_dir,
        restore_guard)
    logger = get_logger(log_file=cfg.log_file or None)
    join_info = None
    if join:
        if cfg.elastic != "grow":
            raise ValueError(
                "train --join requires elastic = grow in [Cluster]: "
                "the running cluster only scans for join tickets when "
                "grow is on")
        if job_name is not None:
            raise ValueError("train --join replaces the dist_train "
                             "role argv: the worker slot is assigned "
                             "by the running cluster, not the launcher")
        from fast_tffm_tpu.parallel.distributed import join_rendezvous
        # Admission BEFORE telemetry: the metrics shard is keyed by
        # the worker slot the cluster assigns, which does not exist
        # until the rendezvous commits.
        join_info = join_rendezvous(cfg, logger)
    # Telemetry BEFORE the cluster join, keyed by the launcher-assigned
    # task index (jax.process_index() is not valid yet): a job that
    # never forms still writes its `health: cluster_bringup_failed`
    # post-mortem into the stream, and elastic recoveries later stay
    # inside this one run segment.
    tel = make_telemetry(cfg, "train",
                         process_index=(join_info[5] if join_info
                                        else (task_index or 0))
                         if (job_name is not None or join_info)
                         else None,
                         process_count=max(len(cfg.worker_hosts), 1)
                         if (job_name is not None or join_info)
                         else None)
    if tel is not None:
        logger.info(
            "writing run metrics to %s (flush every %s steps; summarize "
            "with: python -m tools.fmstat %s)", tel.sink.path,
            tel.flush_steps or "epoch", tel.sink.path)
        # Stamp the configured SLO spec into the stream as slo/*
        # gauges, so `fmstat slo` renders the PASS/FAIL table from the
        # JSONL alone — no config file needed at read time (obs/slo.py).
        from fast_tffm_tpu.obs.slo import SloSpec
        SloSpec.from_config(cfg).emit_gauges(tel)
    # One run-scoped tracker (None under bad_line_policy = error): the
    # max_bad_fraction breaker and the quarantine dedupe must see the
    # WHOLE run — every epoch AND every elastic recovery
    # (data/badlines.py).
    bad_tracker = BadLineTracker.from_config(cfg)
    tel_prev = push_active(tel)  # popped in the finally, crash or not
    lease = None
    guard_prev = None
    guard_installed = False
    try:
        shard_index, num_shards = 0, 1
        generation = 0
        members = [0]
        if join_info is not None:
            lease, shard_index, num_shards, members, generation, _ = \
                join_info
            if tel is not None:
                tel.lease = lease
                tel.sink.meta.update(
                    backend=jax.default_backend(),
                    device_count=jax.device_count(),
                    process_count=jax.process_count())
        elif job_name is not None:
            from fast_tffm_tpu.parallel.distributed import init_from_cluster
            shard_index, num_shards = init_from_cluster(cfg, job_name,
                                                        task_index or 0)
            members = list(range(num_shards))
            if tel is not None:
                # The meta was stamped pre-join with the LOCAL backend
                # view (deliberate: bring-up failures must land in the
                # stream); refresh it in place so every subsequent
                # event's `run` field carries the real topology.
                tel.sink.meta.update(
                    backend=jax.default_backend(),
                    device_count=jax.device_count(),
                    process_count=jax.process_count())
        if (join_info is None and num_shards > 1
                and cfg.heartbeat_seconds > 0):
            lease = HeartbeatLease(
                lease_dir(cfg), process_index=shard_index,
                members=range(num_shards),
                heartbeat_seconds=cfg.heartbeat_seconds).start()
            if tel is not None:
                tel.lease = lease
        if num_shards > 1:
            guard_prev = install_guard(
                lease, cfg.collective_timeout_seconds)
            guard_installed = True
        grow_ctx = (_GrowContext(cfg, lease, members, generation)
                    if cfg.elastic == "grow" and lease is not None
                    else None)
        while True:
            try:
                return _train_session(cfg, logger, tel, bad_tracker,
                                      shard_index, num_shards,
                                      grow_ctx=grow_ctx)
            except ClusterGrowth as g:  # fmlint: disable=R014 -- cluster-wide arm, see below
                # R014: ClusterGrowth is raised off the chief-broadcast
                # grow plan at the admission barrier, so every incumbent
                # takes this arm on the same iteration, and
                # reform_grown_cluster re-synchronizes the collective
                # protocol state before the session restarts.
                # fmlint: disable=R001 -- plan fields are parsed JSON
                # host values (liveness.plan_grow), never device arrays
                generation = int(g.plan["generation"])
                # fmlint: disable=R001 -- same host-JSON plan fields
                planned = sorted(int(s)
                                 for s in g.plan["joiners"].values())
                logger.info(
                    "elastic grow: admitting joiner(s) %s into "
                    "cluster generation %d (barrier state saved)",
                    planned, generation)
                # Disarm the deadline sentinel like the shrink path:
                # no guarded collective completes during a reform.
                if guard_installed:
                    restore_guard(guard_prev)
                    guard_installed = False
                from fast_tffm_tpu.parallel import liveness as lv
                from fast_tffm_tpu.parallel.distributed import (
                    reform_grown_cluster)
                try:
                    if num_shards <= 1 or jax.process_index() == 0:
                        # The plan file is what the JOINER polls for —
                        # the incumbents already share it (chief-
                        # broadcast at the barrier).
                        lv.write_grow_plan(lease.directory, g.plan)
                    # The returned generation is authoritative: the
                    # dead-committed-joiner fallback reforms one past
                    # the plan's, and reusing a consumed generation
                    # would collide with its still-bound coordinator
                    # port on the next reform.
                    shard_index, num_shards, members, generation = \
                        reform_grown_cluster(cfg, lease, generation,
                                             g.plan, logger)
                except BaseException as re:
                    _record_crash(tel, logger, re)
                    raise
                grow_ctx.adopt(members, generation)
                from fast_tffm_tpu.obs.health import (
                    emit_elastic_recovery)
                # fmlint: disable=R001 -- host-JSON plan fields
                incumbents = {int(i) for i in g.plan["incumbents"]}
                joined = sorted(set(members) - incumbents)
                emit_elastic_recovery(
                    generation, members, lost=[], joined=joined,
                    capacity=grow_ctx.capacity, kind="grow")
                logger.info(
                    "elastic recovery complete: %d member(s) "
                    "(admitted %s), input shards re-balanced, "
                    "resuming from the last verified checkpoint",
                    num_shards, joined or "nobody")
                if num_shards > 1:
                    guard_prev = install_guard(
                        lease, cfg.collective_timeout_seconds)
                    guard_installed = True
            except WorkerLostError as e:  # fmlint: disable=R014 -- survivor-wide arm, see below
                # R014: every survivor's deadline guard raises off the
                # same stale lease entry, so the survivors take this arm
                # together; the non-elastic path re-raises (fail fast)
                # and the elastic path re-forms the cluster, which
                # re-synchronizes the protocol state from scratch.
                if (cfg.elastic not in ("shrink", "grow")
                        or num_shards <= 1 or lease is None):
                    _record_crash(tel, logger, e)
                    # Fail FAST: retire (never shutdown — its barrier
                    # cannot complete with a dead peer) so interpreter
                    # exit isn't stalled by the doomed handshake.
                    from fast_tffm_tpu.parallel.distributed import (
                        retire_distributed_client)
                    retire_distributed_client()
                    raise
                generation += 1
                logger.warning(
                    "worker lost (%s); elastic shrink recovery, "
                    "cluster generation %d", e, generation)
                lost_ids = sorted({i.process_index for i in e.lost})
                # Disarm the deadline sentinel for the reform: no
                # guarded collective completes while the cluster is
                # down, and the dead peer stays stale — the sentinel
                # would otherwise read the (healthy, bounded) reform
                # as a hang and hard-exit mid-recovery.
                if guard_installed:
                    restore_guard(guard_prev)
                    guard_installed = False
                from fast_tffm_tpu.parallel.distributed import (
                    reform_shrunken_cluster)
                try:
                    shard_index, num_shards, members = \
                        reform_shrunken_cluster(cfg, lease, generation,
                                                logger)
                except BaseException as re:
                    _record_crash(tel, logger, re)
                    raise
                from fast_tffm_tpu.obs.health import emit_elastic_recovery
                emit_elastic_recovery(
                    generation, members, lost_ids,
                    capacity=max(len(cfg.worker_hosts), 1))
                if grow_ctx is not None:
                    grow_ctx.adopt(members, generation)
                logger.info(
                    "elastic recovery complete: %d survivor(s), input "
                    "shards redistributed, resuming from the last "
                    "verified checkpoint", num_shards)
                if num_shards > 1:
                    # Re-arm for the shrunken cluster (the lease's
                    # expected membership was updated by the reform).
                    guard_prev = install_guard(
                        lease, cfg.collective_timeout_seconds)
                    guard_installed = True
                elif grow_ctx is None:
                    # Lone survivor: no peers left to guard against;
                    # stop the lease so the next multi-worker run in
                    # this rendezvous dir starts from a clean table.
                    # (elastic = grow keeps it: joiners verify
                    # incumbent liveness through it, and the grow
                    # barrier scan reads join tickets beside it.)
                    lease.stop()
                    if tel is not None:
                        tel.lease = None
                    lease = None
    except BaseException as e:
        # Crash forensics for everything the session didn't already
        # record (it records its own loop crashes with the step
        # attached; WorkerLostError and reform failures are recorded
        # above). record_crash is idempotent per event stream read —
        # but avoid double events: only record here if the session
        # never did (it marks recorded exceptions).
        if tel is not None and not getattr(e, "_fm_crash_recorded",
                                           False):
            _record_crash(tel, logger, e)
        raise
    finally:
        if lease is not None:
            try:
                lease.stop()
            except Exception:
                logger.exception("heartbeat lease stop failed")
        if guard_installed:
            restore_guard(guard_prev)
        if tel is not None:
            try:
                tel.close()
            except Exception:
                logger.exception("metrics sink close failed")
        if bad_tracker is not None:
            try:
                bad_tracker.close()
            except Exception:
                logger.exception("quarantine file close failed")
        pop_active(tel_prev)


def _record_crash(tel, logger, e: BaseException, step: int = -1) -> None:
    """Best-effort crash event, marking the exception so the outer
    driver doesn't write it twice."""
    if tel is None or getattr(e, "_fm_crash_recorded", False):
        return
    try:
        tel.record_crash(e, step)
        e._fm_crash_recorded = True
    except Exception:
        logger.exception("crash event emission failed")


class _Session:
    """What one training session is built from and what its parts
    share: the run-scoped arrivals from the elastic driver (telemetry,
    the bad-line tracker, this process's place in the membership), the
    dispatch path this process resolved (mesh, offload, raw ids) and —
    filled in by ``_restore`` and ``_build_state_and_step`` — the
    checkpoint, the vocabulary runtime, the compiled step and the wire
    encoder. The teardown asks this object what exists: every
    attribute it reads has a value from ``__init__`` on."""

    def __init__(self, cfg: FmConfig, logger, tel, bad_tracker,
                 shard_index: int, num_shards: int, grow_ctx=None):
        self.cfg, self.logger, self.tel = cfg, logger, tel
        # Run telemetry (tel) and the bad-line tracker arrive from the
        # elastic driver (train()): both are run-scoped — they must
        # span every session a recovery re-enters, so the driver owns
        # their lifecycle and this session only feeds them.
        self.bad_tracker = bad_tracker
        self.shard_index, self.num_shards = shard_index, num_shards
        self.grow_ctx = grow_ctx
        self.ckpt = self.summaries = None
        self.prev_handlers: dict = {}
        self.preempted: list = []   # signal numbers, in arrival order
        # Filled in by _restore ...
        self.vocab = self.restored = None
        self.restored_step = self.restored_epoch = self.start_epoch = 0
        self.uniq_bucket = self.val_bucket = 0
        self.vocab_fresh_over_restore = False
        # ... by _build_state_and_step ...
        self.lk = self.step_fn = self.packed_step = self.wire_enc = None
        self.snapshot = None    # a periodic saver's host pair
        # ... and by _arm_publish_gate.
        self.gate = None
        self.quality_on = False
        # The feed of the epochs' sweeps: made at the first (validate).
        self.sweeps: Optional[ResidentSweeps] = None
        self.spec = ModelSpec.from_config(cfg)
        logger.info("train regime: %s", regime_line(self.spec, cfg))
        self.multi_process = jax.process_count() > 1
        self.stream_mode = getattr(cfg, "run_mode", "epochs") == "stream"
        self.offload = cfg.lookup == "host"
        if self.offload and self.multi_process:
            # Design position, not a gap: any multi-host v5e job has
            # >= 8 chips, whose aggregate HBM covers config #5's 72 GB
            # state row-sharded; a cross-process host-RAM table would
            # re-implement the mesh with a slower transport.
            raise ValueError(
                "lookup = host is single-process by design: multi-host "
                "scale uses the row-sharded mesh (lookup = device), "
                "whose aggregate HBM holds a table no single chip can")
        self.mesh = None
        if jax.device_count() > 1 and not self.offload:
            # More than one device (one host of a TPU slice, or the
            # whole jax.distributed job): row-shard the table over the
            # global mesh and data-shard the batch
            # (parallel/sharded.py). One device: the plain jitted
            # step, no mesh machinery.
            from fast_tffm_tpu.parallel.sharded import make_mesh
            self.mesh = make_mesh()
        # Pre-flight capacity check (obs/memory.py), here because only
        # the session knows its devices (a cluster has joined by now):
        # when the backend reports a device capacity, a config whose
        # PREDICTED resident bytes per device — table and accumulator
        # divided over the mesh just built — exceed it is refused with
        # the planner's per-owner breakdown, not minutes later as a
        # raw XLA OOM. No-op when capacity is unmeasured (the CPU
        # container).
        self.mesh_devices = (int(self.mesh.devices.size)
                             if self.mesh is not None else 1)
        # How the mesh cuts the rows: the data plane orders a batch's
        # unique rows by owning shard (parallel/sharded.py). None off it.
        from fast_tffm_tpu.data.pipeline import RowShards
        self.row_shards = RowShards.of(cfg, self.mesh_devices)
        preflight_capacity(cfg, "train", shards=self.mesh_devices)
        if tel is not None:
            # Set once: a reader of the stream can tell a mesh run (and
            # over how many devices its rows lie) from a one-device run.
            tel.set("train/mesh_devices", float(self.mesh_devices))
        if self.multi_process:
            from fast_tffm_tpu.data.pipeline import (
                require_bounded_examples)
            require_bounded_examples(cfg, "multi-process training")
        self.raw_mode = self.spec.dedup == "device"
        if self.raw_mode and (self.mesh is not None
                              or self.multi_process):
            # Unreachable via dedup=auto (it resolves to host whenever
            # more than one device exists); an explicit config gets a
            # clear error.
            raise ValueError(
                "dedup = device is single-device only: mesh and "
                "multi-process paths rely on the host-side unique "
                "contract (fixed-U buckets, global_batch local_idx "
                "offsets)")

    def install_signal_handlers(self) -> None:
        """Preemption handling (SURVEY §5 "Failure detection": the
        reference only recovers via restart+restore; we additionally
        save on the way down). SIGTERM/SIGINT sets a flag the loop
        drains at the next step boundary — in multi-process mode the
        flag rides the lockstep allgather so every process saves/exits
        together even when only one received the signal. The handlers
        stay installed (absorbing re-signals) until the session's
        finally — i.e. until the final checkpoint/export is safely on
        disk, the window a second SIGTERM is most likely to arrive in.
        The finally also covers exceptions, so a failed in-process
        train() can't leave the surviving process (pytest, REPL,
        server) with SIGTERM/SIGINT swallowed into a dead flag list."""
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self.prev_handlers[sig] = signal.signal(
                    sig, lambda s, f: self.preempted.append(s))
            except ValueError:  # not the main thread (e.g. under a test)
                pass

    def validate(self, table, collect=None, preempt=None, phases=True,
                 of_epoch: bool = False):
        """One validation sweep of ``table`` on this session's dispatch
        path; returns ``(auc, n_examples)``. ``phases``: whether the
        sweep counts as leaves of the loop's partition (``evaluate``);
        not where the caller's own leaf holds it. ``preempt`` rides the
        lockstep window allgather of a multi-process sweep: a SIGTERM
        mid-sweep stops EVERY worker at the same window boundary (the
        signalled worker alone bailing would desync the collective
        program stream). ``of_epoch``: one of the sweeps an epochs-mode
        job makes a barrier (``_epoch_barrier``): they read from ONE
        feed, made inside the first and closed by ``close_sweeps``
        where the training feed is (``_run_epochs``); any other sweep
        makes and closes its own (``evaluate``)."""
        cfg = self.cfg
        vmb = cfg.validation_max_batches or None
        if self.multi_process:
            # One leaf for the whole lockstep sweep: its parts (window
            # fill, allgather, fetch) are counted apart, as no leaves.
            with span("validation/lockstep",
                      seconds=("validation/lockstep_seconds" if phases
                               else None),
                      step=self.tel.step if self.tel is not None else -1):
                return evaluate_distributed(
                    cfg, table, cfg.validation_files, self.mesh,
                    self.shard_index, self.num_shards,
                    uniq_bucket=self.val_bucket, max_batches=vmb,
                    weight_files=cfg.validation_weight_files,
                    bad_lines=self.bad_tracker, collect=collect,
                    preempt=preempt)
        sweep = dict(mesh=self.mesh, backend=self.lk, max_batches=vmb,
                     weight_files=cfg.validation_weight_files,
                     bad_lines=self.bad_tracker, vocab=self.vocab)
        if of_epoch and self.sweeps is None:
            # a sweep behind every epoch still to run: the last has no
            # next, and nothing is built for one
            self.sweeps = sweep_feed(cfg, cfg.validation_files,
                                     range(self.start_epoch, cfg.epoch_num),
                                     **sweep)
        return evaluate(cfg, table, cfg.validation_files, collect=collect,
                        phases=phases,
                        feed=self.sweeps if of_epoch else None, **sweep)

    def close_sweeps(self) -> None:
        """Stop the sweeps' feed, if one was made: its threads end and
        what it had placed ahead is let go."""
        feed, self.sweeps = self.sweeps, None
        if feed is not None:
            feed.close()


def _train_session(cfg: FmConfig, logger, tel, bad_tracker,
                   shard_index: int, num_shards: int,
                   grow_ctx=None) -> jax.Array:
    """One training session against the CURRENT cluster membership:
    mesh build, checkpoint restore, the epoch/step loop, and the final
    save/export. Raises ``WorkerLostError`` out of any guarded
    collective when a peer dies — the elastic driver (``train``) owns
    what happens next — and ``ClusterGrowth`` out of a safe barrier
    when ``grow_ctx`` plans an admission (the barrier state is saved
    first, so the newcomer restores exactly this point). Everything
    created here (checkpoint manager, summaries, signal handlers,
    profiler) is torn down here, so the driver can safely re-enter
    after a recovery.

    The parts, in the order they run: ``_Session`` (what was
    resolved), ``_restore`` and ``_build_state_and_step`` (checkpoint,
    state, compiled step, wire), ``StepLoop`` (the one step body and
    the state it advances), ``_run_epochs`` or ``_run_stream`` (what a
    mode does around a step), ``_finish`` (final save, exit publish,
    validation, export)."""
    s = _Session(cfg, logger, tel, bad_tracker, shard_index, num_shards,
                 grow_ctx)
    loop = None
    worker_lost = False
    try:
        _restore(s)
        table, acc = _build_state_and_step(s)
        if s.vocab_fresh_over_restore:
            # Fresh admission over a restored table: every row —
            # including row 0, which becomes the shared COLD row but
            # held a fixed-mode mapping's trained embedding — still
            # carries the LOST mapping's weights. Cold-start them all
            # so neither the communal tail nor a newly admitted id
            # ever trains through another id's vector (the documented
            # row-owner invariant).
            table, acc = _reset_rows(
                s, table, acc,
                np.arange(0, cfg.vocabulary_size, dtype=np.int32))
            logger.info(
                "cold-started %d table rows for the fresh admission "
                "state", cfg.vocabulary_size)
        _arm_publish_gate(s)
        s.install_signal_handlers()
        loop = StepLoop(s, table, acc)
        del table, acc  # the loop owns them (donated at every step)
        # TensorBoard scalars (save_summaries_steps; utils/summaries.py).
        # Chief-only, and flushed ONLY at epoch barriers: values buffer
        # as device scalars so the cadence adds zero mid-stream fetches.
        if cfg.save_summaries_steps and jax.process_index() == 0:
            from fast_tffm_tpu.utils.summaries import make_summaries
            s.summaries = make_summaries(cfg)
            if s.summaries is not None:
                logger.info("writing TensorBoard summaries every %d steps "
                            "to %s", cfg.save_summaries_steps,
                            s.summaries.logdir)
        if tel is not None:
            tel.loop_start()  # stopped in _finish, after the last step
        if s.stream_mode:
            _run_stream(s, loop)
        else:
            _run_epochs(s, loop)
        _finish(s, loop)
    except BaseException as e:
        # Crash forensics: the stream's last substantive event carries
        # the traceback and the recent-event ring, with the step
        # attached. A WorkerLostError is NOT a crash yet — the elastic
        # driver may recover it; the driver records it if it decides
        # to re-raise instead.
        from fast_tffm_tpu.parallel.liveness import WorkerLostError
        worker_lost = isinstance(e, WorkerLostError)
        if not worker_lost and not isinstance(e, ClusterGrowth):
            # ClusterGrowth is a planned, durably-saved barrier exit —
            # the driver reforms and re-enters; branding it a crash
            # would flip every healed run's verdict to CRASHED.
            _record_crash(tel, logger, e, _step_now(s, loop))
        raise
    finally:
        if loop is not None:
            loop.end_barrier()
        # The session's resident allocations leave the ledger here —
        # crash or clean exit — so an elastic-recovered session
        # re-registers fresh sizes instead of double-counting, and the
        # peak watermark (deliberately NOT reset) keeps the high-water
        # answer across recoveries.
        for _owner in ("table", "adagrad_acc", "offload_table",
                       "offload_acc", "ckpt_snapshot", "wire_buffers",
                       "prefetch_batches", "lockstep_window"):
            LEDGER.release(_owner)
        try:
            if worker_lost:
                _drop_device_buffers(s)
            else:
                _close_sinks(s, loop)
            if loop is not None and loop.profiling:
                # Window ran past the end of training — or the loop
                # raised with the window open; either way the trace must
                # be closed here or the next start_trace in this process
                # fails with "trace already in progress".
                jax.profiler.stop_trace()
                loop.profiling = False
        finally:
            # Must run even if stop_trace raises (unwritable profile_dir):
            # leaving these handlers installed would swallow SIGTERM/
            # SIGINT into a dead flag list in the surviving process.
            for sig, h in s.prev_handlers.items():
                signal.signal(sig, h)
    logger.info("training done: %d steps, final loss %.6f, %.0f examples/sec",
                loop.global_step, loop.loss_val,
                loop.timer.total_examples_per_sec)
    if s.offload:
        # The logical table as host numpy (the offload analogue of the
        # device table return; dead ckpt-alignment tail sliced off).
        # The pinned backend's table is a jax array in accelerator-host
        # memory: fetch it (callers of train() expect host bytes; at
        # true config-#5 scale callers use the checkpoint instead).
        tbl = (s.lk.table if isinstance(s.lk.table, np.ndarray)
               else np.asarray(jax.device_get(s.lk.table)))
        return tbl[:cfg.num_rows]
    return loop.table


def _step_now(s: _Session, loop) -> int:
    """The step a crash record or the teardown's flush carries: the
    loop's once it exists, else what the restore found."""
    return loop.global_step if loop is not None else s.restored_step


def _drop_device_buffers(s: _Session) -> None:
    """HOST-ONLY teardown: a peer is dead, so any device fetch
    (buffered loss scalars, TB summaries, the deferred log buffer — all
    outputs of collective programs that will never complete) and any
    orbax multi-host commit barrier (ckpt.close) can block forever —
    the exact hang the deadline guard just escaped. Drop the
    device-side buffers (counted, not silent), flush host events, and
    let the elastic driver rebuild the checkpoint manager; the verified
    restore walk-back owns anything torn."""
    tel = s.tel
    if tel is not None:
        try:
            dropped = tel.sink.discard_scalars()
            if dropped:
                tel.count("cluster/scalars_dropped", dropped)
            tel.sink.flush()
        except Exception:
            s.logger.exception("host-only metrics flush failed")
    s.logger.warning(
        "worker lost: skipped checkpoint close and device-scalar "
        "drains (device fetches could hang on the dead peer's "
        "collectives)")


def _close_sinks(s: _Session, loop) -> None:
    """Checkpoint and sink lifecycle on ALL normal exit paths, each
    isolated so one broken writer can't starve the others. (The
    metrics sink and bad-line tracker are DRIVER-scoped: they survive
    elastic recoveries and close in train().)"""
    logger = s.logger
    # An exception (or preemption) between the last periodic save and
    # the normal close must not leave an async save in flight — the
    # process would exit mid-write and tear the newest step. close()
    # waits for the in-flight write, settles the owed integrity
    # manifest, and releases the manager.
    if s.ckpt is not None:
        try:
            s.ckpt.close()
        except Exception:
            logger.exception("checkpoint close failed")
    # A step that raised must not drop what was queued before it: the
    # owed loss line and the TensorBoard scalars drain here.
    if loop is not None:
        try:
            loop.sync_live_line()
        except Exception:
            logger.exception("the owed loss line's sync failed")
    if s.summaries is not None:
        # Buffered scalars must reach the event file even when the
        # loop raised or a preemption cut the final epoch.
        try:
            s.summaries.close()
        except Exception:
            logger.exception("summary writer close failed")
    if s.tel is not None:
        try:
            # Barrier, not close: buffered device scalars and the
            # final counter snapshot reach disk with this session's
            # step attached, and the stream stays open for a recovered
            # session to continue.
            s.tel.barrier_flush(_step_now(s, loop))
        except Exception:
            logger.exception("metrics barrier flush failed")


def _restore(s: _Session) -> None:
    """The session's host-side start: the unique-row buckets, the
    vocabulary runtime, the checkpoint manager and what it restored
    (arrays, step, epoch, the admission sidecar)."""
    cfg, logger = s.cfg, s.logger
    multi_process = s.multi_process
    # Visibility only — the plane lives inside batch_iterator.
    # host_parallel_workers is the SAME predicate the routing uses, so
    # this log never claims a fan-out the pipeline won't perform for
    # THIS run's inputs (C++ missing, weight sidecars, tolerant
    # fixed-shape all route serial).
    host_workers = host_parallel_workers(
        cfg, cfg.weight_files, fixed_shape=multi_process)
    if host_workers > 1 and not s.stream_mode:
        logger.info(
            "host data plane: %d parallel batch-build workers "
            "(host_threads = %s; bounded ordered ring)",
            host_workers, cfg.host_threads)
    if multi_process and not s.stream_mode:
        # Fixed-shape batches need one U for the whole job. Auto mode
        # measures the data (probe is deterministic and identical on
        # every process) instead of assuming the next_pow2(B*L) worst
        # case — a ~50x smaller gather/scatter per step at Criteo-like
        # density; denser-than-probed batches spill, never break.
        # (Stream mode probes the discovered SEALED shards instead,
        # chief-decided — data/stream.probe_stream_uniq_bucket.)
        from fast_tffm_tpu.data.pipeline import probe_uniq_bucket
        s.uniq_bucket = cfg.uniq_bucket or probe_uniq_bucket(
            cfg, cfg.train_files, shards=s.row_shards)
        logger.info("fixed unique-row bucket: %d", s.uniq_bucket)
    if multi_process and cfg.validation_files:
        from fast_tffm_tpu.data.pipeline import probe_uniq_bucket
        s.val_bucket = cfg.uniq_bucket or probe_uniq_bucket(
            cfg, cfg.validation_files)

    # Vocabulary admission (README "Unbounded vocabulary";
    # fast_tffm_tpu/vocab/): the runtime owns the sketch + slot map;
    # the data plane builds batches in the hashed space and remaps
    # through it; barriers run at the existing epoch/publish
    # synchronization points.
    if getattr(cfg, "vocab_mode", "fixed") == "admit":
        if multi_process:
            raise ValueError(
                "vocab_mode = admit is single-process: the slot "
                "map is host state, and lockstep workers would "
                "need a chief-broadcast admission protocol to "
                "agree on it (ROADMAP item 3's sharded-table "
                "leg). Run admit-mode training on one process.")
        from fast_tffm_tpu.vocab.table import VocabRuntime
        s.vocab = VocabRuntime.from_config(cfg)
        s.vocab.row_shards = s.row_shards
        logger.info(
            "vocab admission: %d physical rows (row 0 = shared "
            "cold row) over a 2^30 hashed id space; admit/evict "
            "threshold %.1f, decay %.2f/barrier, sketch %.1f MB",
            cfg.vocabulary_size, cfg.vocab_admit_threshold,
            cfg.vocab_decay, cfg.vocab_sketch_mb)

    s.ckpt = CheckpointState(cfg.model_file,
                             retry=RetryPolicy.from_config(cfg),
                             verify=getattr(cfg, "ckpt_verify", "size"))
    restored = s.restored = s.ckpt.restore(  # off a mesh: to HOST memory
        template=checkpoint_template(cfg, s.mesh,
                                     host=s.offload or s.mesh is None))
    if restored is not None:
        check_restored_vocab(cfg, restored)
        s.restored_step = int(restored["step"])
        s.restored_epoch = int(restored["epoch"])
        logger.info("restored checkpoint at step %d", s.restored_step)
    if s.vocab is not None and restored is not None:
        payload = restored.get("vocab_admission")
        if payload is None:
            logger.warning(
                "restored checkpoint at step %d carries no vocab "
                "admission sidecar (a fixed-mode warm start, or a "
                "lost/garbled sidecar): admission state starts "
                "FRESH — previously admitted ids serve from the "
                "cold row until they re-cross the threshold",
                s.restored_step)
            # The restored table still holds the LOST mapping's
            # trained rows; fresh admission must not hand them to new
            # owners (_train_session cold-starts them once the table
            # is materialized).
            s.vocab_fresh_over_restore = True
        else:
            s.vocab.load(cfg, payload)
            logger.info(
                "restored vocab admission state at step %d: %d "
                "live rows", s.restored_step, s.vocab.live_rows)
    elif restored is not None:
        from fast_tffm_tpu.checkpoint import (
            refuse_fixed_mode_admit_step)
        refuse_fixed_mode_admit_step(
            cfg, s.ckpt.directory, s.restored_step,
            payload=restored.get("vocab_admission"))
    s.start_epoch = resume_start_epoch(s.restored_epoch, cfg.epoch_num)
    if s.start_epoch:
        logger.info("resuming interrupted epoch schedule at epoch %d/%d",
                    s.start_epoch, cfg.epoch_num)


def _build_state_and_step(s: _Session):
    """The train state (``table``, ``acc``; None when the offload
    backend holds them), the compiled step for this dispatch path, the
    ownership-ledger entries and the wire encoder. The benchmark
    rebinds ``make_train_step`` / ``init_table`` on this module and
    the sharded and packed builders on theirs, so every one of them is
    looked up when this runs (benchmarks/drivers/train.py,
    STEP_SEAMS)."""
    cfg, logger, spec, restored = s.cfg, s.logger, s.spec, s.restored
    if s.offload:
        # Offload backend (lookup.py; BASELINE config #5): the table/
        # accumulator live outside HBM. make_offload_backend picks the
        # in-jit pinned-host implementation (whole step stays in the
        # async dispatch stream) where the backend compiles it, else the
        # numpy fallback with its inherent per-step gradient fetch.
        from fast_tffm_tpu.lookup import (PinnedHostLookup,
                                          make_offload_backend,
                                          make_offload_train_step)
        lk = s.lk = make_offload_backend(cfg, cfg.seed, restored=restored)
        if restored is not None:
            # The backend adopted the arrays (numpy backend: zero-copy)
            # or copied them into accelerator-host memory (pinned
            # backend); keeping these references for the rest of
            # train() would pin a SECOND full table+accumulator in
            # local RAM for the whole resumed run — a sustained 2x that
            # is an OOM at config-#5 scale (the same concern
            # HostOffloadLookup.load documents for transient copies).
            restored["table"] = restored["acc"] = None
        kind = (f"pinned-host in-jit ({lk.mode})"
                if isinstance(lk, PinnedHostLookup) else "host-numpy")
        logger.info("offload lookup [%s]: table [%d, %d] outside HBM "
                    "(%.2f GB + accumulator)", kind, lk.rows, lk.dim,
                    lk.rows * lk.dim * 4 / 2**30)
        offload_step = make_offload_train_step(spec, lk,
                                               cfg.learning_rate)
        table = acc = None

        def step_fn(_t, _a, labels, weights, uniq_ids, local_idx, vals,
                    fields=None):
            loss, scores = offload_step(labels, weights, uniq_ids,
                                        local_idx, vals, fields)
            return None, None, loss, scores
    elif s.mesh is not None:
        from fast_tffm_tpu.parallel.sharded import (
            init_sharded_state, make_sharded_train_step)
        if restored is not None:
            # The sharded template already placed these row-sharded on
            # this mesh in the runtime [ckpt_rows, D] layout — use as-is.
            table, acc = restored["table"], restored["acc"]
        else:
            table, acc = init_sharded_state(cfg, s.mesh, cfg.seed)
        # global_batch lays one segmented feed a process side by side
        step_fn = make_sharded_train_step(spec, s.mesh,
                                          blocks=jax.process_count())
        # Logged once the state exists, so the line can say where
        # it landed: a row-sharded table shows near-equal bytes on
        # every local device, one that fell onto the first chip
        # does not (chip_smoke.py fails past 1.5x).
        jax.block_until_ready((table, acc))
        logger.info(
            "mesh training: %s over %d devices, %d processes; "
            "bytes in use per local device: %s",
            dict(s.mesh.shape), jax.device_count(),
            jax.process_count(), local_bytes_in_use() or "unmeasured")
    else:
        if restored is not None:
            table, acc = place_restored(restored, cfg.num_rows)
        else:
            table = init_table(cfg, cfg.seed)
            acc = init_accumulator(cfg)
        step_fn = make_train_step(spec)
        if cfg.save_steps:  # a periodic saver's host pair, for its life
            s.snapshot = saver_buffers(cfg, table, acc)
            LEDGER.register("ckpt_snapshot", 2 * s.snapshot[0].nbytes,
                            host=True)
    s.step_fn = step_fn

    # Ownership ledger (obs/memory.py; README "Memory observability"):
    # the session's long-lived allocations register with their owner
    # tag so every flush carries mem/* gauges and an OOM names which
    # owner grew. .nbytes is host metadata — no fetch. Offload state is
    # host-resident by construction (host=True: gauged, excluded from
    # the device live total). Released in the session's finally.
    if s.offload:
        LEDGER.register("offload_table",
                        table_bytes(rows=lk.rows, dim=lk.dim),
                        host=True)
        LEDGER.register("offload_acc",
                        table_bytes(rows=lk.rows, dim=lk.dim),
                        host=True)
    else:
        # One device's share: the ledger's live total stands beside
        # ONE device's capacity (pressure alarm, mem/utilization).
        LEDGER.register("table", table.nbytes // s.mesh_devices)
        LEDGER.register("adagrad_acc", acc.nbytes // s.mesh_devices)

    # Wire format (README "Wire format"; wire.py): resolve the knobs
    # for THIS dispatch path, build the one encoder every step ships
    # through, and pre-build the packed step when active. Staging (the
    # explicit async device_put double buffer) applies on the plain
    # single-device jit path only — mesh/lockstep placement and the
    # offload host gather have their own protocols.
    from fast_tffm_tpu.wire import WireEncoder, resolve_wire
    wire_spec = resolve_wire(cfg, mesh=s.mesh, backend=s.lk,
                             multi_process=s.multi_process, train=True)
    s.wire_enc = WireEncoder(wire_spec, pad_id=cfg.pad_id)
    if wire_spec.packed:
        from fast_tffm_tpu.models.fm import make_packed_train_step
        s.packed_step = make_packed_train_step(spec)
        logger.info(
            "wire format: %s (flat CSR + on-device unpack, "
            "double-buffered H2D)", wire_spec.describe())
    tel = s.tel
    if tel is not None:
        # The active wire mode, as gauges — fmstat's transfer-bound
        # attribution names it beside the bytes-per-example row.
        tel.set("wire/packed", 1.0 if wire_spec.packed else 0.0)
        tel.set("wire/narrow", 1.0 if wire_spec.narrow else 0.0)
        # What only a sync point or an epoch barrier feeds starts
        # at 0: a reader that differences two snapshots of the
        # stream must find "none yet" as 0, not as absent.
        # So does what only a re-laid state or a second batch shape feeds
        # (models/fm.py, TrainStep): an FM run's answer is 0, not silence.
        for name in ("train/epochs", "train/epoch_barrier_seconds",
                     "train/loss_sync_seconds", "train/state_relayouts",
                     "train/step_programs", "train/program_switches"):
            tel.count(name, 0)
        for name in SAVE_COUNTERS if cfg.save_steps else ():
            tel.count(name, 0)  # so does what only a periodic save feeds
    return table, acc


def _reset_rows(s: _Session, table, acc, rows):
    """Cold-start ``rows`` through the backend's half of the slot seam
    (lookup.reset_rows for offload state, the fixed-width compiled
    scatter for device/mesh state — either way no per-count
    recompiles); returns the state to go on with (the device path
    donates and reassigns it)."""
    if s.offload:
        s.lk.reset_rows(rows, s.cfg.adagrad_init)
        return table, acc
    from fast_tffm_tpu.vocab.table import reset_table_rows
    return reset_table_rows(table, acc, rows, s.cfg.pad_id,
                            s.cfg.adagrad_init)


def _arm_publish_gate(s: _Session) -> None:
    """Per-publish quality loop + publish gate (README "SLOs & quality
    gate"; obs/quality.py). When a stream run has a validation corpus,
    every publish settle runs one validation sweep — AUC/loss/
    calibration gauges ride the sweep's own score fetches (zero added
    device traffic) — and the configured gate decides whether the
    `published` pointer may move. Session-scoped (not the stream
    loop's) because the EXIT publish after the final save is gated
    too."""
    cfg = s.cfg
    from fast_tffm_tpu.obs.quality import PublishGate
    gate = s.gate = PublishGate.from_config(cfg) if s.stream_mode else None
    # "auto" opts in exactly when the run declared a quality objective
    # (a gate knob, or slo_min_auc) — an existing stream config with
    # validation_files must not silently start paying a validation
    # sweep per publish on upgrade.
    qmode = getattr(cfg, "publish_quality_eval", "auto")
    s.quality_on = (s.stream_mode and bool(cfg.validation_files)
                    and float(getattr(cfg, "publish_interval_seconds",
                                      0.0)) > 0
                    and (qmode == "on"
                         or (qmode == "auto"
                             and (gate is not None
                                  or getattr(cfg, "slo_min_auc",
                                             0.0) > 0))))
    if gate is not None:
        # The drop baseline survives restarts beside the pointer
        # (checkpoint.GATE_BASELINE): a preempt-resume must not exempt
        # its first publish from publish_max_auc_drop.
        from fast_tffm_tpu.checkpoint import read_gate_baseline
        gate.note_published(read_gate_baseline(s.ckpt.directory))
        s.logger.info(
            "publish gate armed: min AUC %s, max AUC drop %s%s "
            "(validation sweep at every publish settle)",
            cfg.publish_min_auc or "off",
            cfg.publish_max_auc_drop or "off",
            "" if gate.baseline is None
            else f", restored baseline {gate.baseline:.6f}")


def _gate_published(s: _Session, decision) -> None:
    """Advance (and persist) the drop baseline after a publish actually
    landed — the one baseline-write path for both the interval
    publishes and the exit publish."""
    gate = s.gate
    if gate is None or decision is None:
        return
    gate.note_published(decision.get("auc"))
    if gate.baseline is not None and jax.process_index() == 0:
        from fast_tffm_tpu.checkpoint import write_gate_baseline
        write_gate_baseline(s.ckpt.directory, gate.baseline)


class StepLoop:
    """The one step body both run modes drive, and the state a step
    advances: the train state (``table``, ``acc``), ``global_step``,
    the last ``loss``, the rate timer, the loss lines still owed, the
    profiler window, and what a save has to record beside the arrays
    (completed epochs, the stream watermark). A mode's loop
    (``_run_epochs``, ``_run_stream``) fetches a batch, calls
    ``step`` and does its own work around the call; a caller that
    holds a ``_Session`` can drive ``step`` itself."""

    def __init__(self, s: _Session, table, acc):
        self.s = s
        self.table, self.acc = table, acc
        self.global_step = s.restored_step
        # The profile window counts THIS run's steps (a resumed job
        # would otherwise skip past the window silently).
        self.run_start_step = self.global_step
        self.profiling = False
        self.timer = StepTimer()
        self.loss = None
        self.loss_val = float("nan")
        self.stopping = False
        # (auc, n) of the most recent validation pass of the table as
        # it stands; a step clears it.
        self.last_val = None
        self.live_line: list = []   # the one loss line whose sync is due
        # Where the train/step_seconds sample of the next step starts;
        # a mode's loop re-anchors it around its own pauses.
        self.t_prev = time.perf_counter()
        # The epoch barrier, held open across loop iterations
        # (obs/trace.begin): the first dispatch to return ends it.
        self.barrier = None
        self.barrier_sweep = 0.0    # the validation sweep's wall inside it
        self.completed_epochs = s.start_epoch
        self.placed = None  # (wb, args) of the next batch, where the feed
        # placed it ahead (_run_epochs); step() takes it and lets it go
        self.last_periodic_save = (None, None)  # (step, epoch) of the latest
        # Streaming run mode (README "Streaming / online learning"):
        # the durable stream position adopted from STEPPED batches —
        # what every checkpoint records beside the arrays, so restore
        # resumes with no example duplicated or skipped. None in epoch
        # mode (saves then carry no watermark sidecar).
        self.stream_watermark = None
        if s.mesh is not None:
            from fast_tffm_tpu.parallel.sharded import (global_batch,
                                                        shard_batch)
            self._global_batch, self._shard_batch = global_batch, shard_batch

    # -- one step ---------------------------------------------------

    def place(self, batch, wb, ahead: int = 0):
        """This dispatch path's host-to-device placement of one
        encoded batch (the offload step takes host arrays and never
        gets here); ``ahead``: placed batches queued beyond the two of
        the double buffer."""
        s = self.s
        if s.mesh is not None and batch.row_shards != s.mesh_devices:
            # The mesh step cannot see this: it reads a row outside
            # its shard's segment as zeros and drops its update.
            raise ValueError(
                f"a batch of {batch.row_shards} segment(s) of unique "
                f"rows fed to a mesh of {s.mesh_devices} row shards: "
                "build it with row_shards = RowShards.of(cfg, mesh size)")
        if s.multi_process:
            # The global-array assembly ships every shard's bytes.
            return self._global_batch(s.mesh, len(batch.uniq_ids),
                                      **wb.args)
        if s.mesh is not None:
            return self._shard_batch(s.mesh, **wb.args)
        # Plain single-device jit, depth-2 double buffer: the
        # explicit async put rides the copy stream while the
        # PREVIOUS step is still executing, instead of serializing
        # at the head of this step's dispatch.
        return s.wire_enc.device_put(wb, window=2 + ahead)

    def feed_place(self, batch):
        """A batch as the feed hands it to the loop where batches are
        final when emitted: encoded and placed on the feed's own thread
        (pipeline.place_ahead), up to ``prefetch_depth`` ahead."""
        wb = self.s.wire_enc.encode_train(batch)
        return batch, (wb, self.place(batch, wb, self.s.cfg.prefetch_depth))

    def wire_place(self, batch, step):
        """Encode one batch and place its arrays for dispatch, on the
        loop's own thread (where the feed does not: ``_run_epochs``).
        h2d_bytes = wb.wire_bytes sizes the arrays ACTUALLY shipped; the
        padded layout's size rides on wb.logical_bytes for the savings
        counter. ``step`` rides both spans (the h2d's: cross-rank key)."""
        with span("train/encode", seconds="train/encode_seconds",
                  step=step):
            wb = self.s.wire_enc.encode_train(batch)
        if self.s.offload:
            return wb, wb.args
        with span("train/h2d", seconds="train/h2d_seconds",
                  bytes=wb.wire_bytes, step=step):
            return wb, self.place(batch, wb)

    def dispatch(self, wb, args, step):
        """Dispatch one placed batch through the right compiled step,
        as the ``train/step`` phase: jax dispatch is async (returns at
        enqueue), so time spent HERE is queue backpressure — the
        previous program still executing somewhere. Runs under
        oom_guard: a RESOURCE_EXHAUSTED here re-raises with the
        per-owner ledger attached (obs/memory.py). A loss line
        still owed (log_tick) is synced first."""
        self.sync_live_line()
        s = self.s
        with span("train/step", seconds="train/dispatch_seconds",
                  step=step, width=wb.L):
            with oom_guard("train/step"):
                if s.multi_process:
                    # The sharded step IS a collective program: on a
                    # dead cluster its dispatch blocks inside the
                    # program's collectives exactly like a host
                    # allgather (pinned by the hang-worker chaos stack
                    # dumps), so it runs under the same deadline guard.
                    from fast_tffm_tpu.parallel.liveness import (
                        guarded_collective)
                    return guarded_collective(
                        s.step_fn, self.table, self.acc,
                        label="train/step_dispatch", **args)
                if wb.packed:
                    return s.packed_step(wb.L, self.table, self.acc,
                                         **args)
                return s.step_fn(self.table, self.acc, **args)

    def step(self, batch, epoch: int, summaries=None, gauges=None):
        """Train on one batch: the sequence every step of either run
        mode goes through, in this order. ``summaries`` (the epoch
        loop's TensorBoard writer) and ``gauges`` (a callable the
        stream loop sets its freshness gauges with, just ahead of a
        due telemetry flush) are the two things a mode hands in;
        everything else a mode does, it does around this call. Where
        the feed placed the batch ahead (``self.placed``) it is
        dispatched as it came; else the loop places it (``wire_place``)."""
        s = self.s
        cfg, tel, vocab = s.cfg, s.tel, s.vocab
        step = self.global_step + 1
        if vocab is not None:
            # A publish barrier may have moved the slot map while this
            # batch sat in the prefetch queue — redo its remap so it
            # never scatters into rows the barrier evicted/reset/
            # reassigned (one int compare when nothing moved).
            with span("train/batch_checks",
                      seconds="train/batch_checks_seconds", step=step):
                batch = vocab.ensure_current(batch)
        placed, self.placed = self.placed, None
        ahead = placed is not None  # placed by the feed, on its thread
        wb, args = placed or self.wire_place(batch, step)
        out = self.dispatch(wb, args, step)
        self.end_barrier()  # an enclosure: closed before the next leaf opens
        # From here to the flush: one leaf of the loop's wall. Nothing in
        # it opens a span (a loss line or a flush is queued, not paid).
        with span("train/bookkeeping", seconds="train/bookkeeping_seconds",
                  step=step):
            # The last step's state and loss and this step's placed batch
            # are let go HERE, under the phase (250 us a step: PERF.md).
            self.table, self.acc, self.loss, _ = out
            del out, args, placed
            self.global_step = step
            self.last_val = None  # table advanced; a cached AUC is stale
            if vocab is not None:
                # Adopt-on-step, like the stream watermark: the sketch
                # advances only for TRAINED batches, so the checkpointed
                # admission state and the stream position describe the
                # same prefix.
                vocab.note_trained(batch)
            # Log-line rate: the job-global estimate (x P assumes symmetric
            # shards). The COUNTER is this worker's OWN real examples: shard
            # files merge by sum, anything else would count them P-fold.
            self.timer.tick(batch.num_real * (jax.process_count()
                                              if s.multi_process else 1))
            if tel is not None:
                # Wall time since the previous step's clock read, never a
                # device sync; re-anchored per epoch (no barrier in it).
                # fmlint: disable=R003 -- feeds the train/step_seconds
                # histogram (always-on aggregate)
                now = time.perf_counter()
                dt, self.t_prev = now - self.t_prev, now
                tel.train_step(dt, batch.num_real, wb.wire_bytes,
                               wb.logical_bytes, ahead)
                if dt >= SLOW_STEP_SECONDS:
                    tel.slow_step(step, dt, epoch=epoch)
                tel.heartbeat(step)  # the watchdog's beat (obs/health.py)
            self.profile_tick(step)
            log_due = cfg.log_steps and step % cfg.log_steps == 0
            sum_due = (summaries is not None
                       and step % cfg.save_summaries_steps == 0)
            tel_due = tel is not None and tel.flush_due(step)
            # One windowed-rate read per step: the read consumes the
            # window, so line, summary and gauge all share it.
            eps_now = (self.timer.consume_window_rate()
                       if (log_due or sum_due or tel_due) else None)
            if log_due:
                self.log_tick(step, epoch, self.loss, eps_now)
            if sum_due:
                summaries.add("train/loss", step, self.loss)
                summaries.add("train/examples_per_sec", step, eps_now)
            if tel_due:
                # loss is a DEVICE scalar: buffered, fetched only at the
                # next barrier flush (sink sync-safety contract).
                tel.add_scalar("train/loss", step, self.loss)
                tel.set("train/examples_per_sec_window", eps_now)
                if gauges is not None:
                    gauges()
        if tel_due:
            with span("obs/flush", seconds="obs/flush_seconds", step=step):
                tel.maybe_flush(step)  # file I/O only

    def end_barrier(self) -> None:
        if self.barrier is not None:
            wall, self.barrier = self.barrier.end(), None
            # A barrier that holds a validation sweep is no stall for
            # that: the sweep's own phases say where its wall went.
            wall = (wall or 0) - self.barrier_sweep
            if self.s.tel is not None and wall >= SLOW_STEP_SECONDS:
                self.s.tel.slow_step(self.global_step + 1, wall, "barrier")

    def profile_tick(self, step_done: int) -> None:
        cfg = self.s.cfg
        if not cfg.profile_dir or jax.process_index() != 0:
            return
        step_done -= self.run_start_step
        if (not self.profiling and step_done >= cfg.profile_start_step
                and step_done < cfg.profile_start_step
                + cfg.profile_num_steps):
            jax.profiler.start_trace(cfg.profile_dir)
            self.profiling = True
        elif self.profiling and step_done >= (cfg.profile_start_step
                                              + cfg.profile_num_steps):
            if self.table is not None:
                jax.block_until_ready(self.table)
            jax.profiler.stop_trace()
            self.profiling = False
            self.s.logger.info("profiler trace written to %s",
                               cfg.profile_dir)

    # -- loss lines -------------------------------------------------

    def log_line(self, step, epoch, val, eps) -> None:
        self.loss_val = val
        self.s.logger.info("step %d epoch %d loss %.6f examples/sec %.0f",
                           step, epoch, val, eps)

    def log_tick(self, step, epoch, loss_arr, eps) -> None:
        """Queue one loss line (no span: bookkeeping calls it). float(loss)
        stalls async dispatch until the device has caught up, so the sync
        is taken at the NEXT dispatch, once the next batch is placed: the
        device then waits for the host one dispatch after a line, and not
        a placement too (13 ms of eight steps on the four-chip mesh)."""
        self.live_line.append((step, epoch, loss_arr, eps))

    def sync_live_line(self) -> None:
        """The loop's sync point: the host waits here until the device
        has caught up, so this phase's share of the wall says how far the
        device sets the pace. The line is written in a phase of its own."""
        if not self.live_line:  # at most one: every dispatch syncs first
            return
        step, epoch, loss_arr, eps = self.live_line.pop()
        with span("train/loss_sync", seconds="train/loss_sync_seconds",
                  step=step):
            val = float(loss_arr)
        with span("train/log_line", seconds="train/log_line_seconds",
                  step=step):
            self.log_line(step, epoch, val, eps)

    # -- what a barrier or a save needs of the state ------------------

    def note_preempted(self, epoch: int, doing: str, signals=None) -> None:
        """A drained preemption flag: stop after this boundary, and say
        so in the log and as the distinct health event fmstat reports
        as PREEMPTED instead of conflating a clean preemption exit
        with a crash (obs/attribution health_verdict)."""
        self.stopping = True
        self.s.logger.info("preemption signalled; %s", doing)
        if self.s.tel is not None:
            event = {"status": "preempted", "step": self.global_step,
                     "epoch": epoch}
            if signals is not None:
                event["signals"] = signals
            self.s.tel.sink.emit("health", event)

    def vocab_reset(self, rows) -> None:
        """The eviction hook."""
        self.table, self.acc = _reset_rows(self.s, self.table, self.acc,
                                           rows)

    def vocab_barrier(self, where: str) -> None:
        s = self.s
        if s.vocab is None:
            return
        st = s.vocab.barrier(self.vocab_reset)
        s.logger.info(
            "vocab barrier (%s): +%d admitted, -%d evicted, %d/%d "
            "live rows", where, st["admitted"], st["evicted"],
            st["live"], s.cfg.vocabulary_size - 1)

    def stream_state(self):
        """The watermark payload a save should carry right now: merged
        across workers at this lockstep point (a collective when
        multi-process — callers must invoke it at step-deterministic
        points only)."""
        s = self.s
        if not s.stream_mode:
            return None
        from fast_tffm_tpu.data.stream import exchange_watermarks
        wm = self.stream_watermark or {"format": 1, "files": []}
        return (exchange_watermarks(wm, s.num_shards)
                if s.multi_process else wm)

    def save(self, epoch: int, wait: bool, force: bool = False,
             rewrite_stale_metadata: bool = False) -> None:
        """Checkpoint the state as it stands at ``global_step``, with
        the watermark and admission sidecars that describe the same
        prefix. Device arrays save async unless ``wait``: the snapshot
        to the host is taken here (orbax's of a mesh's shards, or
        ``ckpt_state``'s), the write runs in the background."""
        s = self.s
        s.ckpt.settle()
        state = (s.lk.state() if s.offload else
                 ckpt_state(s.cfg, self.table, self.acc, into=s.snapshot))
        s.ckpt.save(self.global_step, *state,
                    vocabulary_size=s.cfg.vocabulary_size, force=force,
                    wait=wait, epoch=epoch,
                    rewrite_stale_metadata=rewrite_stale_metadata,
                    stream_state=self.stream_state(),
                    vocab_state=(s.vocab.state_payload()
                                 if s.vocab is not None else None))
        self.last_periodic_save = (self.global_step, epoch)


def _publish_decision(s: _Session, loop: StepLoop) -> Optional[dict]:
    """Quality sweep + gate decision for the publish about to happen;
    None when no quality loop is configured (publish unconditionally).
    Rides the publish settle point the caller already synchronized at;
    multi-host safe: the sweep merge is collective and the chief's
    decision is broadcast (obs/quality.PublishGate docstring), so all
    workers skip or run the save/publish that follows together."""
    if not s.quality_on:
        return None
    from fast_tffm_tpu.obs.quality import (QualityStats, emit_gate_held,
                                           emit_quality)
    cfg, tel, logger, gate = s.cfg, s.tel, s.logger, s.gate
    global_step = loop.global_step
    stats = QualityStats(cfg.loss_type)
    # Chief-only counter, like emit_quality below: per-worker shard
    # counters merge by SUM in fmstat.
    with span("quality/eval", leaf=False, step=global_step,
              seconds=("quality/eval_seconds"
                       if jax.process_index() == 0 else None)):
        # preempt: a SIGTERM mid-sweep stops ALL workers at the same
        # window boundary instead of finishing the full validation
        # pass inside the kill grace window.
        # phases=False: this sweep lies inside the publish's own leaf
        # (checkpoint/publish, or the exit publish outside the loop).
        auc, n = s.validate(loop.table, collect=stats,
                            preempt=lambda: bool(s.preempted),
                            phases=False)
    if jax.process_index() == 0:
        # Chief-only: n and the merged stats are already job-global,
        # and per-worker shard counters merge by SUM in fmstat — every
        # worker emitting would inflate quality/evals and
        # quality/examples by P.
        emit_quality(tel, global_step, float(auc), stats, n)
    if tel is not None:
        tel.heartbeat()  # a long sweep is progress, not a stall
    if jax.process_index() == 0:
        logger.info(
            "publish quality eval at step %d: AUC %.6f, loss "
            "%s, calibration %s over %d examples",
            global_step, auc,
            "-" if stats.loss is None else f"{stats.loss:.6f}",
            "-" if stats.calibration is None
            else f"{stats.calibration:.4f}", n)
    if gate is None:
        return {"held": False, "auc": float(auc), "examples": int(n)}
    # Chief decides, broadcast: identity single-process; every worker
    # applies the byte-identical decision.
    from fast_tffm_tpu.data.stream import broadcast_blob
    decision = broadcast_blob(gate.decide(float(auc), global_step),
                              "quality/gate_decision")
    # n is already job-global (the sweep merge), so adding it after
    # the broadcast stays identical on every worker.
    decision["examples"] = int(n)
    if decision["held"]:
        if jax.process_index() == 0:
            # Chief-only, like emit_quality: one hold must count once,
            # not once per worker shard.
            emit_gate_held(tel, decision)
        logger.warning(
            "publish GATE HELD at step %d: %s — the published "
            "pointer stays on the last passing step",
            global_step, "; ".join(decision["reasons"]))
    return decision


def _agreed_batch(s: _Session, loop: StepLoop, batch, epoch: int):
    """What the epoch loop trains on next, or None when the epoch is
    over for this process and — in multi-process mode — for every
    other one too.

    Lockstep: line-index sharding can give processes batch counts
    differing by one; every step is a collective program, so a process
    that stepped alone would hang the cluster. Agree on exhaustion/
    preemption each step (tiny host allgather) and feed all-padding
    filler batches (zero weight -> zero loss/grad) until everyone is
    done. The deadline guard bounds the wait: a dead peer raises
    WorkerLostError naming it instead of parking the survivors here
    forever (parallel/liveness.py)."""
    if not s.multi_process:
        with span("train/batch_checks",
                  seconds="train/batch_checks_seconds",
                  step=loop.global_step + 1):
            if s.preempted:
                # fmlint: disable=R001 -- preempted holds host signal
                # numbers from the handler, never device arrays
                loop.note_preempted(epoch, "saving and exiting",
                                    signals=[int(x) for x in s.preempted])
                return None
            return batch
    from jax.experimental import multihost_utils
    from fast_tffm_tpu.parallel.liveness import guarded_collective
    # The epoch loop's rank barrier (anatomy flags-wait phase; span step
    # id = cross-rank join key). On CPU+gloo this wait also absorbs the
    # PREVIOUS step's still-executing program — allgather blocks behind
    # queued device work — which is exactly what the anatomy report names.
    with span("train/step_flags", seconds="train/step_flags_seconds",
              step=loop.global_step + 1):
        flags = guarded_collective(
            multihost_utils.process_allgather,
            np.asarray([batch is None, bool(s.preempted)]),
            label="train/step_flags")
    if bool(flags[..., 1].any()):
        loop.note_preempted(epoch, "saving and exiting")
        return None
    if bool(flags[..., 0].all()):
        return None
    if batch is None:
        from fast_tffm_tpu.data.pipeline import empty_batch
        batch = empty_batch(s.cfg, uniq_bucket=s.uniq_bucket,
                            shards=s.row_shards)
    return batch


def _run_epochs(s: _Session, loop: StepLoop) -> None:
    """``run_mode = epochs``: ``epoch_num`` passes over ``train_files``
    from where the restored schedule stands, each ending in the epoch
    barrier (``_epoch_barrier``). ONE feed for the job
    (``pipeline.EpochFeed``): an epoch's end comes in band, as a mark
    behind its last batch, and the next epoch's first batches are built
    and placed while this one's last steps run."""
    cfg = s.cfg
    # Where a barrier can change what the next epoch's batches are, the
    # feed waits at the mark and the loop places: a vocab barrier
    # re-points rows (admit), the processes agree on U and on every step
    # before anything is placed, offload.
    hold = s.vocab is not None or s.multi_process or s.offload
    feed = None
    try:
        for epoch in range(s.start_epoch, cfg.epoch_num):
            if loop.stopping:
                break
            # What the loop does to have an epoch's first next() to
            # call: the job's first epoch opens the feed (generators:
            # its threads, builders and files are the first next()'s),
            # every later one tells it that the barrier is over.
            with span("pipeline/open", seconds="pipeline/open_seconds",
                      epoch=epoch):
                if feed is None:
                    feed = EpochFeed(
                        cfg, cfg.train_files,
                        range(s.start_epoch, cfg.epoch_num),
                        place=None if hold else loop.feed_place,
                        hold=hold, uniq_bucket=lambda: s.uniq_bucket,
                        weight_files=cfg.weight_files,
                        shard_index=s.shard_index,
                        num_shards=s.num_shards,
                        fixed_shape=s.multi_process, raw_ids=s.raw_mode,
                        bad_lines=s.bad_tracker, vocab=s.vocab,
                        row_shards=s.row_shards)
                else:
                    feed.release(epoch - 1)
            # fmlint: disable=R003 -- anchors the per-epoch
            # step-seconds window (always-on aggregate)
            loop.t_prev = time.perf_counter()
            first = True  # an epoch's first batch goes by its own name
            mark = None   # the epoch's end, once the feed has handed it over
            while True:
                batch = None
                if mark is None:  # in lockstep the others may still step
                    # Consumer-side stall: time blocked INSIDE next() only.
                    # Any wider would fold end-of-step bookkeeping (notably
                    # a loss line's deliberate float(loss) device sync) into
                    # the host-bound signal and misdiagnose a device-bound
                    # run (the build cost is timed on the producing threads).
                    with span("pipeline/first_batch" if first
                              else "train/input_wait",
                              seconds="train/input_wait_seconds",
                              step=loop.global_step + 1) as wait:
                        item = next(feed)
                    if s.tel is not None:  # an epoch's first; a feed_stall
                        s.tel.waited(loop.global_step + 1, wait.dur, first)
                    first = False
                    if isinstance(item, EpochMark):
                        mark = item
                    else:
                        batch, loop.placed = item
                batch = _agreed_batch(s, loop, batch, epoch)
                # fmlint: disable=R014 -- _agreed_batch returns None on
                # every process together in multi-process mode (it agrees
                # on exhaustion and preemption through the
                # train/step_flags allgather first); single-process, the
                # loop's collectives are gated on multi_process, so this
                # escape leaves no peer unmatched
                if batch is None:
                    break
                loop.step(batch, epoch, summaries=s.summaries)
                if cfg.save_steps and loop.global_step % cfg.save_steps == 0:
                    with span("train/checkpoint_pause",
                              seconds="train/checkpoint_pause_seconds",
                              step=loop.global_step) as pause:
                        # Host-offload state: wait, the background writer
                        # would race the in-place numpy Adagrad updates.
                        loop.save(loop.completed_epochs, wait=s.offload)
                    if s.tel is not None:  # keep the pause out of the next
                        loop.t_prev += pause.dur  # step's step_seconds
            loop.placed = None  # a preemption's: the batch it did not step
            _epoch_barrier(s, loop, epoch, mark.stats if mark is not None
                           else feed.stats(epoch))
    finally:  # a step that raised, a preemption, the job's end: the
        loop.placed = None  # feeds' threads stop, what they placed is let go
        try:
            if feed is not None:
                feed.close()
        finally:
            s.close_sweeps()


def _epoch_barrier(s: _Session, loop: StepLoop, epoch: int,
                   epoch_stats) -> None:
    """What runs between an epoch's last step and the next epoch's
    first: the owed loss lines, the input's spill report and the
    bucket it adapts, the vocab barrier, validation, the summary and
    telemetry flushes, and the grow barrier."""
    cfg, logger, tel = s.cfg, s.logger, s.tel
    stopping = loop.stopping
    loop.sync_live_line()  # the epoch's last line, ahead of the barrier
    if not stopping:
        # The epoch barrier, an enclosure of leaf phases: from the
        # iterator's exhaustion until the NEXT epoch's first dispatch
        # returns (or the loop's end): what the steady rate leaves out.
        loop.barrier = begin("train/epoch_barrier",
                             seconds="train/epoch_barrier_seconds",
                             epoch=epoch)
        loop.barrier_sweep = 0.0
    with span("train/barrier_reports",
              seconds="train/barrier_reports_seconds", epoch=epoch):
        if s.bad_tracker is not None and s.bad_tracker.bad:
            # Cumulative run-level view: the breaker and quarantine are
            # run-scoped, so the log line is too.
            logger.info("bad-line policy through epoch %d: %s",
                        epoch, s.bad_tracker.describe())
        if epoch_stats.spilled_batches or (s.multi_process
                                           and epoch_stats.batches):
            # Spill visibility (fixed-U mode): a probe-missed dense
            # stretch degrades fill silently otherwise.
            logger.info("epoch %d input: %s", epoch, epoch_stats.describe())
            if epoch_stats.spill_fraction > SPILL_WARN_FRACTION:
                logger.warning(
                    "uniq_bucket %d is undersized for this data: "
                    "%.0f%% of batches closed early on the "
                    "unique-row budget; raise uniq_bucket (or set 0 "
                    "to re-probe) to recover effective batch size",
                    s.uniq_bucket, 100 * epoch_stats.spill_fraction)
        if s.multi_process and not stopping and epoch + 1 < cfg.epoch_num:
            # Adaptive bucket: a probe-missed dense stretch spills every
            # epoch otherwise. The job-wide spill fraction is allgathered
            # (per-process stats see only their own shard — a local
            # decision would desynchronize shapes and deadlock the
            # collective program), and every process applies the same
            # doubling.
            from jax.experimental import multihost_utils
            from fast_tffm_tpu.parallel.liveness import guarded_collective
            tot = guarded_collective(
                multihost_utils.process_allgather,
                np.asarray(
                    [epoch_stats.spilled_batches, epoch_stats.batches,
                     epoch_stats.max_uniq]),
                label="train/spill_stats")
            tot = tot.reshape(-1, 3)
            # fmlint: disable=R001 -- tot is the HOST numpy result
            # of process_allgather; these ints never touch a device
            s.uniq_bucket = adapt_uniq_bucket(
                cfg, s.uniq_bucket, int(tot[:, 0].sum()),
                int(tot[:, 1].sum()), logger,
                max_uniq=int(tot[:, 2].max()), shards=s.row_shards)
        if not stopping:
            # The epoch boundary IS a vocab barrier point: the epoch's
            # observations admit/evict here, so the next epoch (and the
            # validation sweep just below) runs against the refreshed map
            # + reset rows.
            loop.vocab_barrier(f"epoch {epoch}")
    if cfg.validation_files and not stopping:
        # An enclosure of the sweep's leaves (evaluate()'s
        # validation/*; a lockstep sweep is one leaf).
        with span("train/validation", leaf=False,
                  seconds="train/validation_seconds", epoch=epoch) as sweep:
            # A preempted sweep stops on every worker together; the step
            # loop then drains the flag and all workers save together.
            auc, n = s.validate(loop.table,
                                preempt=lambda: bool(s.preempted),
                                of_epoch=True)
        # what this barrier's slow_step leaves out (StepLoop.end_barrier)
        loop.barrier_sweep = sweep.dur if tel is not None else 0.0
        loop.last_val = (auc, n)
        if jax.process_index() == 0:
            logger.info(
                "epoch %d validation AUC %.6f over %d examples",
                epoch, auc, n)
        if s.summaries is not None:
            s.summaries.add("validation/auc", loop.global_step, auc)
        if tel is not None:
            tel.set("validation/auc", auc)
            # fmlint: disable=R001 -- auc is already a host
            # python float from the streamed AUC merge
            tel.add_scalar("validation/auc", loop.global_step,
                           float(auc))
    if s.summaries is not None:  # epoch barrier: bulk-fetch + write
        with span("train/summary_flush",
                  seconds="train/summary_pause_seconds", epoch=epoch):
            s.summaries.flush()
    if tel is not None:
        # Epoch barrier: the one point buffered device scalars
        # are bulk-fetched and the JSONL reaches disk for sure.
        tel.count("train/epochs")
        tel.barrier_flush(loop.global_step)
    if not stopping:  # a preemption-cut epoch is NOT completed
        loop.completed_epochs = epoch + 1
    if (s.grow_ctx is not None and not stopping
            and loop.completed_epochs < cfg.epoch_num):
        # The epoch boundary IS the grow barrier in epochs mode: every
        # worker is synchronized here (the same point the vocab barrier
        # uses), and the chief's admission plan is broadcast so everyone
        # raises together or nobody does. The barrier state is saved
        # durably FIRST (force rewrites a same-step periodic save with
        # the completed epoch count) — it is exactly what the newcomer's
        # verified restore comes up on. The last epoch never grows: the
        # run is about to finish, and a reform would only delay its exit.
        with span("train/barrier_reports",
                  seconds="train/barrier_reports_seconds", epoch=epoch):
            plan = s.grow_ctx.check_barrier()
            if plan is not None:
                loop.save(loop.completed_epochs, wait=True, force=True)
                raise ClusterGrowth(plan)


class _StreamClock:
    """The stream run's publish clock and gate-hold state, beside the
    tracker whose lag its gauges report."""

    def __init__(self, s: _Session, tracker):
        self.tel, self.tracker = s.tel, tracker
        self.publish_every = float(
            getattr(s.cfg, "publish_interval_seconds", 0.0))
        self.last_publish = time.monotonic()
        # The freshness gauge (and the STALE PUBLISH verdict) track
        # the last SUCCESSFUL publish, separately from the attempt
        # clock above: a gate that keeps holding advances the cadence
        # but NOT the pointer — the age must keep growing so a long
        # hold surfaces as STALE PUBLISH, the closed loop's designed
        # failure signal.
        self.last_publish_ok = time.monotonic()
        # Whether the LAST gate decision held. While holding, the
        # retention-pressure publish trigger (_publish_due) is
        # disarmed: a republish attempt cannot succeed (the gate would
        # hold the same regressed state again), so re-arming it would
        # spin a full validation sweep per loop iteration for the
        # whole hold. The interval arm keeps re-evaluating at the
        # publish cadence — the bounded re-check that notices
        # recovery.
        self.gate_holding = False
        # One retention-pause log per hold episode (_stream_step).
        self.risk_pause_logged = False

    def gauges(self) -> None:
        """The stream's freshness gauges, as of now."""
        tel = self.tel
        if tel is None:
            return
        tel.set("stream/watermark_lag_seconds",
                self.tracker.watermark_lag_seconds())
        if self.publish_every > 0:
            tel.set("stream/last_publish_age_seconds",
                    time.monotonic() - self.last_publish_ok)


def _publish_due(s: _Session, clock: _StreamClock) -> bool:
    """Interval elapsed, OR retention pressure: periodic save_steps
    saves must never GC the published step out from under a scorer
    mid-interval — republishing first repoints at fresh state instead
    of letting the pointer dangle. Chief-only in lockstep mode (the
    decision rides the flags allgather)."""
    if clock.publish_every <= 0:
        return False
    if time.monotonic() - clock.last_publish >= clock.publish_every:
        return True
    # Gated runs check one retention slot EARLY (margin=2): the very
    # tick this arm triggers may turn out HELD, and a hold starting at
    # the margin-1 boundary would leave the mandatory final/preemption
    # save to evict the last-good step — the reserve the save pause
    # depends on must exist BEFORE the hold begins.
    return (bool(s.cfg.save_steps) and not clock.gate_holding
            and s.ckpt.published_at_risk(
                margin=2 if s.gate is not None else 1))


def _stream_publish(s: _Session, loop: StepLoop,
                    clock: _StreamClock) -> None:
    """Quality eval + gate, then save + settle the manifest + verify +
    atomically repoint the ``published`` pointer. A HELD decision
    skips the save too: a held tick must not mint a new step —
    retention (max_to_keep) could otherwise use held steps to lap the
    published pointer, deleting the exact "last good triple" the gate
    exists to keep serving. Lockstep-safe: the decision is
    chief-broadcast, so every worker runs the save's commit barrier
    (or skips it) together; only process 0 flips the pointer."""
    with span("checkpoint/publish", leaf=False,
              seconds="train/checkpoint_pause_seconds",
              step=loop.global_step):
        # Publish settle IS a vocab barrier point: the published
        # (table, slot map, step) triple a scorer hot-reloads must be
        # post-admission/eviction coherent — evicted rows reset BEFORE
        # the save, so the published step serves evicted ids from the
        # cold row, never stale embeddings. (It runs before the
        # quality eval, so the sweep measures exactly the state a
        # pass would publish.)
        loop.vocab_barrier(f"publish step {loop.global_step}")
        decision = _publish_decision(s, loop)
        clock.gate_holding = bool(decision and decision.get("held"))
        if not clock.gate_holding:
            clock.risk_pause_logged = False
        if decision is None or not decision.get("held"):
            # force=True: a publish can land on the SAME step as the
            # last periodic save, and the barrier above just moved the
            # in-memory (table, slot map) pair — the benign same-step-
            # collision skip would pair the old arrays with the new
            # sidecar. Forcing rewrites both, so the published triple
            # is coherent.
            loop.save(0, wait=True, force=s.vocab is not None)
            ok = s.ckpt.publish_step(loop.global_step) is not None
            # Non-chief workers assume the chief's verify passed
            # (publish_step is process-0-only; a verify failure is
            # already counted and the decision stream stays
            # chief-broadcast, so a rare divergent baseline here
            # cannot diverge an outcome).
            if ok or jax.process_index() != 0:
                clock.last_publish_ok = time.monotonic()
                _gate_published(s, decision)
        # held: no save, no publish — and once the published step
        # reaches the retention boundary, _stream_step pauses
        # periodic saves too, so GC can never evict the last-good
        # checkpoint mid-hold.
    clock.last_publish = time.monotonic()
    clock.gauges()
    if s.grow_ctx is not None and not clock.gate_holding:
        # The publish settle IS the grow barrier in stream mode (the
        # same sync point the vocab barrier rides): the save above
        # just landed with the merged watermark (wait=True), so a
        # newcomer's verified restore resumes the stream exactly-once
        # from this point. A HELD publish skipped the save — no
        # durable barrier state, no admission; the chief-broadcast
        # hold decision keeps every worker on the same arm.
        plan = s.grow_ctx.check_barrier()
        if plan is not None:
            raise ClusterGrowth(plan)


def _stream_step(s: _Session, loop: StepLoop, clock: _StreamClock,
                 batch) -> None:
    """One stream step and what the stream does around it: adopt the
    batch's position, then the periodic save under the gate's
    retention policy."""
    cfg = s.cfg
    loop.step(batch, 0, gauges=clock.gauges)
    if batch.stream_pos is not None:
        # The durable position advances ONLY with stepped batches
        # (lockstep fillers carry None).
        loop.stream_watermark = batch.stream_pos
    if not (cfg.save_steps and loop.global_step % cfg.save_steps == 0):
        return
    # margin=2: stop one slot shy of the boundary so the mandatory
    # final/preemption save can still land without evicting the
    # last-good step.
    if clock.gate_holding and s.ckpt.published_at_risk(margin=2):
        # Retention pause: while the gate is HOLDING, a periodic save
        # that would push the published (last-good) step past
        # max_to_keep must not run — orbax's newest-N eviction has no
        # pin, so minting the step would delete the exact checkpoint
        # the fleet is serving from (published_at_risk's "the pointer
        # never names a deleted step" contract). Durability pauses for
        # the hold — progress since the last save is re-trained on a
        # crash, exactly once via the watermark — and resumes when the
        # gate passes (the publish repoints at fresh state, clearing
        # the risk).
        if not clock.risk_pause_logged:
            clock.risk_pause_logged = True
            s.logger.warning(
                "publish gate holding with the published step at the "
                "retention boundary: pausing periodic saves so GC "
                "cannot evict the last-good checkpoint; heal the "
                "input stream (or raise max_to_keep) to resume")
        return
    # Gated runs save SYNCHRONOUSLY: the retention math protecting the
    # published step (the margin=2 risk arm + the hold pause above)
    # reasons over COMMITTED step dirs — an async save's invisible
    # in-flight step would let a hold latch with the window already
    # full, and the mandatory final save would then evict the exact
    # last-good checkpoint the gate pinned (caught by the
    # retention-pause e2e test).
    with span("train/checkpoint_pause",
              seconds="train/checkpoint_pause_seconds") as pause:
        loop.save(0, wait=s.offload or s.gate is not None)
    if s.tel is not None:
        loop.t_prev += pause.dur


def _run_stream(s: _Session, loop: StepLoop) -> None:
    """``run_mode = stream``, the indefinitely-surviving online loop:
    poll the stream source, step every arriving batch, save with the
    watermark, and publish a manifest-verified checkpoint every
    ``publish_interval_seconds``. Single-process overlaps build and
    compute through the prefetch thread; multi-worker runs the source
    inline on this thread so its one discovery collective per
    iteration stays aligned with the lockstep flags allgather and the
    step program (collectives from two threads would interleave
    nondeterministically across workers — the deadlock class the
    window protocol exists to prevent)."""
    from fast_tffm_tpu.data import stream as streamlib
    cfg, logger, tel = s.cfg, s.logger, s.tel
    multi_process = s.multi_process
    restored_wm = (s.restored or {}).get("stream")
    # Seed the adopted position from the restored sidecar: a recovered
    # session (elastic shrink/grow, preempt-resume) saves at its
    # restored step BEFORE any new batch steps — publish settles fire
    # on idle ticks — and an empty in-memory watermark there would
    # REWRITE the step's sidecar to empty, wiping the durable position
    # and double-training the whole consumed prefix after the next
    # restore (caught by the kill-then-grow soak).
    loop.stream_watermark = restored_wm
    if s.restored is not None and restored_wm is None:
        logger.warning(
            "restored checkpoint at step %d carries no stream "
            "watermark (an epoch-mode warm start, or a lost "
            "watermark sidecar): streaming starts from the "
            "BEGINNING of %s — any stream bytes this model "
            "already trained on will be trained again",
            loop.global_step, cfg.stream_dir)
    tracker = streamlib.StreamTracker(
        cfg.stream_dir, cfg.stream_poll_seconds,
        cfg.seal_policy, retry=RetryPolicy.from_config(cfg),
        shard_index=s.shard_index, num_shards=s.num_shards,
        bad_lines=s.bad_tracker, watermark=restored_wm,
        lockstep=multi_process)
    u_bucket = 0
    if multi_process:
        u_bucket = (cfg.uniq_bucket
                    or streamlib.probe_stream_uniq_bucket(
                        cfg, tracker, shards=s.row_shards))
        logger.info("fixed unique-row bucket: %d", u_bucket)
    workers = streamlib.stream_workers(cfg, fixed_shape=multi_process)
    if workers > 1:
        logger.info(
            "stream host data plane: %d parallel batch-build "
            "workers (host_threads = %s; sealed line groups "
            "through the bounded ordered ring)",
            workers, cfg.host_threads)
    source = streamlib.StreamSource(
        cfg, tracker,
        stop=(None if multi_process else (lambda: bool(s.preempted))),
        fixed_shape=multi_process, uniq_bucket=u_bucket,
        raw_ids=s.raw_mode, workers=workers,
        bad_lines=s.bad_tracker, vocab=s.vocab,
        row_shards=s.row_shards)
    clock = _StreamClock(s, tracker)
    if tel is not None:
        tel.set("stream/publish_interval_seconds", clock.publish_every)
    # fmlint: disable=R003 -- anchors the stream step-seconds
    # window (always-on aggregate)
    loop.t_prev = time.perf_counter()
    try:
        if multi_process:
            _stream_lockstep(s, loop, clock, source, u_bucket)
        else:
            _stream_single(s, loop, clock, source)
    finally:
        source.close()
    clock.gauges()  # the exit metrics snapshot carries the
    # freshness gauges even when the run never hit a flush step
    loop.sync_live_line()
    if s.bad_tracker is not None and s.bad_tracker.bad:
        logger.info("bad-line policy through the stream run: "
                    "%s", s.bad_tracker.describe())
    if source.stats.batches:
        logger.info("stream input: %s", source.stats.describe())


_STREAM_PREEMPTED = "saving the stream position and exiting"


def _stream_lockstep(s: _Session, loop: StepLoop, clock: _StreamClock,
                     source, u_bucket: int) -> None:
    """The multi-worker stream loop: every iteration agrees, through
    one flags allgather, on whether anyone has a batch, was signalled,
    is done, or (the chief) is due a publish."""
    from jax.experimental import multihost_utils
    from fast_tffm_tpu.data import stream as streamlib
    from fast_tffm_tpu.data.pipeline import empty_batch
    from fast_tffm_tpu.parallel.liveness import guarded_collective
    cfg, tel = s.cfg, s.tel
    while True:
        b = source.next_batch(block=False)
        has = b not in (streamlib.IDLE, streamlib.DONE)
        done = b is streamlib.DONE
        pub_due = _publish_due(s, clock)
        # The flags allgather is the stream loop's rank barrier: time
        # parked here is waiting for the slowest peer (anatomy flags-wait
        # phase; the span's step id is the cross-rank join key).
        with span("stream/step_flags",
                  seconds="train/step_flags_seconds",
                  step=loop.global_step + 1):
            flags = np.asarray(guarded_collective(
                multihost_utils.process_allgather,
                np.asarray([has, bool(s.preempted), done, pub_due]),
                label="stream/step_flags")).reshape(-1, 4)
        if bool(flags[:, 1].any()):
            loop.note_preempted(0, _STREAM_PREEMPTED)
            break
        if bool(flags[:, 2].all()) and not bool(flags[:, 0].any()):
            break
        if bool(flags[:, 0].any()):
            batch = (b if has else empty_batch(cfg, uniq_bucket=u_bucket,
                                               shards=s.row_shards))
            _stream_step(s, loop, clock, batch)
        else:
            if tel is not None:
                tel.heartbeat()
            clock.gauges()
            time.sleep(min(cfg.stream_poll_seconds, 0.5))
        if bool(flags[0, 3]):  # the CHIEF's clock
            _stream_publish(s, loop, clock)


def _stream_single(s: _Session, loop: StepLoop, clock: _StreamClock,
                   source) -> None:
    """The one-process stream loop. StreamPrefetcher, not
    pipeline.prefetch: the driver must keep its publish clock and
    preemption checks ticking while the stream idles — a blocking
    queue get would starve publishing for as long as no batch
    arrives."""
    from fast_tffm_tpu.data import stream as streamlib
    cfg, tel = s.cfg, s.tel
    pf = streamlib.StreamPrefetcher(source, depth=cfg.prefetch_depth)
    if tel is not None:  # read as differences: there from the first flush
        tel.count("stream/gets", 0)
        tel.count("stream/gets_idle", 0)
    try:
        while True:
            if s.preempted:
                loop.note_preempted(0, _STREAM_PREEMPTED)
                break
            # The consumer's stall, as _run_epochs counts it: the time
            # blocked inside the get alone, a leaf of the loop's wall.
            with span("train/input_wait",
                      seconds="train/input_wait_seconds",
                      step=loop.global_step + 1):
                batch = pf.get(timeout=min(cfg.stream_poll_seconds, 0.5))
            if tel is not None:
                tel.count("stream/gets")
                if batch is streamlib.IDLE:
                    tel.count("stream/gets_idle")
            # fmlint: disable=R007 -- single-process loop
            # (_stream_lockstep is the multi-worker path): the step's
            # collectives are themselves gated on multi_process, so no
            # peer exists to diverge from; `batch` reads as
            # rank-tainted only through the tracker's shard_index
            # plumbing
            # fmlint: disable=R014 -- same single-process
            # justification: the loop's collectives are all gated on
            # multi_process, so this escape leaves no peer's sequence
            # unmatched
            if batch is streamlib.DONE:
                if s.preempted:
                    loop.note_preempted(0, _STREAM_PREEMPTED)
                break
            # fmlint: disable=R007 -- same single-process
            # justification as above
            if batch is streamlib.IDLE:
                if tel is not None:
                    tel.heartbeat()
                clock.gauges()
            else:
                _stream_step(s, loop, clock, batch)
            if _publish_due(s, clock):
                _stream_publish(s, loop, clock)
    finally:
        pf.close()


def _finish(s: _Session, loop: StepLoop) -> None:
    """After the last step of either mode: the final save, the exit
    publish, a stream run's one validation pass, and the export."""
    cfg, logger, tel = s.cfg, s.logger, s.tel
    loop.end_barrier()
    loop.sync_live_line()
    if tel is not None:
        tel.loop_stop()  # the final save and the export are no step's
    if loop.loss is not None:
        loop.loss_val = float(loop.loss)
    # The final save IS a barrier point (vocab/table.py's contract):
    # nothing is in flight here — the stream is drained or the epoch
    # iterators exhausted — so the durable (table, slot map) pair
    # admits the last interval's crossers and evicts/resets its cold
    # rows before the bytes land (the exit publish below repoints at
    # exactly this state). MUST run before the save captures the
    # state: the row resets donate (and for the device path reassign)
    # the table/acc buffers.
    loop.vocab_barrier(f"final save step {loop.global_step}")
    # Final/preemption save: barrier until durably written — the
    # process may exit right after.
    # If this step's existing checkpoint carries a stale epoch
    # count — from THIS run's last periodic save, or from the
    # RESTORED checkpoint when a resumed run advanced the schedule
    # without a single global step (every shard's input empty —
    # note a multi-process job with ANY data still advances
    # global_step via lockstep fillers, so that case needs the
    # whole job dry) — tell save() to correct it (an atomic epoch
    # sidecar written by process 0; restore overlays it). Both
    # signals are deterministic (lockstep-consistent state, not
    # disk reads), so every process of a multi-host job agrees the
    # correction exists — restore's process-0-read + broadcast does
    # the rest.
    stale = ((loop.last_periodic_save[0] == loop.global_step
              and loop.last_periodic_save[1] != loop.completed_epochs)
             or (s.restored is not None
                 and loop.global_step == s.restored_step
                 and loop.completed_epochs != s.restored_epoch))
    loop.save(loop.completed_epochs, wait=True, force=True,
              rewrite_stale_metadata=stale)
    if s.stream_mode and getattr(cfg, "publish_interval_seconds",
                                 0.0) > 0:
        _exit_publish(s, loop)
    if (s.stream_mode and not s.multi_process and cfg.validation_files
            and not s.quality_on):
        # Stream mode has no per-epoch sweeps; a configured
        # validation corpus gets one final scored pass here
        # (multi-process streams validate in _chief_finalize below;
        # publishing streams already validated through the exit
        # publish's quality sweep just above) — silently
        # accepting-and-ignoring the knob would be a config trap.
        # phases=False: the loop's wall has stopped (loop_stop above)
        auc, n = s.validate(loop.table, phases=False)
        logger.info("final validation AUC %.6f over %d examples",
                    auc, n)
        if tel is not None:
            tel.set("validation/auc", auc)
            # fmlint: disable=R001 -- auc is already a host float
            # from the streamed AUC merge
            tel.add_scalar("validation/auc", loop.global_step,
                           float(auc))
    if s.multi_process:
        _chief_finalize(cfg, loop.table, logger, s.mesh, s.shard_index,
                        s.num_shards, loop.last_val, s.val_bucket,
                        s.bad_tracker)
    else:
        # Same size gate on EVERY dense-export path: a single-host
        # mesh whose aggregate row-sharded table exceeds host RAM
        # must not OOM assembling the .npz after a successful run.
        nbytes = table_bytes(cfg)
        if nbytes > EXPORT_NPZ_MAX_BYTES:
            logger.info(
                "skipping dense .npz export: table is "
                "%.1f GB > %.1f GB threshold; use the checkpoint at "
                "%s.ckpt", nbytes / 2**30,
                EXPORT_NPZ_MAX_BYTES / 2**30, cfg.model_file)
        else:
            export_npz(s.lk.table if s.offload else loop.table,
                       cfg.model_file + ".npz",
                       vocabulary_size=cfg.vocabulary_size)


def _exit_publish(s: _Session, loop: StepLoop) -> None:
    """The exit publish: a clean STOP drain (or a preemption's durable
    save) is the freshest verified state a scorer can hot-reload; the
    final save already settled the manifest (wait=True). Gated like
    every other publish — a run whose tail regressed quality must exit
    with the pointer still on the last passing step (the final save
    itself always lands: resume durability is not gated). A PREEMPTED
    exit skips the quality sweep: the grace window between SIGTERM and
    the orchestrator's SIGKILL has no budget for a validation pass,
    and a mid-sweep kill would lose the publish entirely — so a
    gate-less run publishes immediately (the historical behavior) and
    a gated run leaves the pointer on the last step the gate actually
    passed rather than publishing unevaluated state."""
    if loop.stopping and s.gate is not None:
        s.logger.info(
            "preempted with a publish gate configured: exit "
            "publish skipped (no quality sweep inside the "
            "grace window); the pointer stays on the last "
            "passing step")
        return
    decision = None if loop.stopping else _publish_decision(s, loop)
    if decision is not None:
        # The exit sweep IS this table's final validation:
        # _chief_finalize (multi-process) must not re-run it.
        loop.last_val = (decision["auc"], decision["examples"])
    if decision is None or not decision.get("held"):
        if s.ckpt.publish_step(loop.global_step) is not None:
            # Persist the exit publish's baseline too — it is exactly
            # what the NEXT run's gate must re-arm from.
            _gate_published(s, decision)
            if s.tel is not None:
                s.tel.set("stream/last_publish_age_seconds", 0.0)


# Above this, the dense .npz convenience export is skipped (the real
# model lives in the sharded checkpoint): a 10^9-row table is ~36 GB
# dense — materializing it on one host is exactly what the sharded
# design exists to avoid.
EXPORT_NPZ_MAX_BYTES = 2 << 30


# Shrink threshold: halve the bucket only when the epoch's DENSEST
# batch used under this fraction of it — the halved bucket then still
# holds that batch with >= 1/(2*0.35) ~ 1.4x headroom, so the shrink
# cannot itself cause next-epoch spills on this data.
SHRINK_FILL_FRACTION = 0.35


def adapt_uniq_bucket(cfg: FmConfig, uniq_bucket: int, spilled: int,
                      batches: int, logger, max_uniq: int = 0,
                      shards=None) -> int:
    """Next epoch's fixed unique-row bucket, given THIS epoch's job-wide
    stats: double (up to the worst-case ladder top) while the spill
    fraction stays above SPILL_WARN_FRACTION; halve (never below 64 or
    the single-example bound) after a spill-free epoch whose densest
    batch (``max_uniq``, job-wide max) filled under SHRINK_FILL_FRACTION
    of the bucket — an overshot startup probe or a dense early file
    otherwise inflates every later step's gather/scatter width for the
    rest of the job (round-4 review). Deterministic in its inputs —
    callers must feed every process the same totals (train() allgathers
    them) so all agree on the new batch shapes without negotiation. An
    explicit ``uniq_bucket`` config is never overridden. On a mesh
    (``shards``) the bounds hold per segment of the bucket and
    ``max_uniq`` follows the fullest one (pipeline._num_uniq).
    """
    if cfg.uniq_bucket or not batches:
        return uniq_bucket
    if spilled / batches > SPILL_WARN_FRACTION:
        top = uniq_bucket_top(cfg, shards=shards)
        if uniq_bucket >= top:
            return uniq_bucket
        new_bucket = min(uniq_bucket * 2, top)
        logger.info(
            "raising uniq_bucket %d -> %d for the next epoch (%.0f%% of "
            "batches spilled on the unique-row budget this epoch)",
            uniq_bucket, new_bucket, 100 * spilled / batches)
        return new_bucket
    half = uniq_bucket // 2
    if (spilled == 0 and max_uniq
            and max_uniq <= uniq_bucket * SHRINK_FILL_FRACTION
            and half >= 64
            # config invariant: the bucket (on a mesh each segment)
            # must exceed the per-example feature cap or one dense
            # example could overflow it outright
            and half // (shards.n if shards else 1)
            > cfg.max_features_per_example):
        logger.info(
            "lowering uniq_bucket %d -> %d for the next epoch (densest "
            "batch used %d unique rows, %.0f%% fill — recovering "
            "gather/scatter width from an oversized probe or an earlier "
            "raise)", uniq_bucket, half, max_uniq,
            100 * max_uniq / uniq_bucket)
        return half
    return uniq_bucket


def _chief_finalize(cfg: FmConfig, table: jax.Array, logger, mesh,
                    shard_index: int, num_shards: int,
                    last_val=None, val_bucket: int = 0,
                    bad_tracker=None) -> None:
    """Multi-process epilogue: final validation AUC via the sharded
    score fn (table stays row-sharded; only binned histograms cross
    hosts), then a size-gated dense export assembled chunk-by-chunk so
    no host ever holds more than the chief's final copy.

    ``last_val`` is the last per-epoch (auc, n): when the final epoch
    already validated this exact table, re-sweeping validation_files
    (every batch a collective) would just recompute it."""
    from jax.experimental import multihost_utils
    from fast_tffm_tpu.parallel.liveness import guarded_collective
    if cfg.validation_files:
        if last_val is None:  # e.g. preemption cut the epoch short
            # Same cap as the per-epoch sweeps: an uncapped fallback
            # here would run a full lockstep validation inside a
            # preemption grace window.
            last_val = evaluate_distributed(
                cfg, table, cfg.validation_files, mesh, shard_index,
                num_shards, uniq_bucket=val_bucket,
                max_batches=cfg.validation_max_batches or None,
                weight_files=cfg.validation_weight_files,
                bad_lines=bad_tracker)
        if jax.process_index() == 0:
            logger.info("final validation AUC %.6f over %d examples",
                        *last_val)
    nbytes = table_bytes(cfg)
    if nbytes > EXPORT_NPZ_MAX_BYTES:
        if jax.process_index() == 0:
            logger.info(
                "skipping dense .npz export: table is %.1f GB > %.1f GB "
                "threshold; use the sharded checkpoint at %s.ckpt",
                nbytes / 2**30, EXPORT_NPZ_MAX_BYTES / 2**30,
                cfg.model_file)
    else:
        # Chunked allgather: every process participates (collective),
        # non-chief hosts drop each chunk immediately, so peak extra
        # host memory is one chunk — not the whole table — everywhere
        # but the chief, which writes chunks straight into the one
        # preallocated dense buffer the .npz needs anyway.
        chunk = max(1, (64 << 20) // (cfg.row_dim * 4))
        chief = jax.process_index() == 0
        out = (np.empty((cfg.num_rows, cfg.row_dim), np.float32)
               if chief else None)
        for a in range(0, cfg.num_rows, chunk):
            b = min(a + chunk, cfg.num_rows)
            piece = guarded_collective(
                multihost_utils.process_allgather, table[a:b],
                tiled=True, label="finalize/export_chunk")
            if chief:
                out[a:b] = np.asarray(piece)
        if chief:
            export_npz(out, cfg.model_file + ".npz",
                       vocabulary_size=cfg.vocabulary_size)
    guarded_collective(multihost_utils.sync_global_devices,
                       "fast_tffm_tpu_finalize", label="finalize/sync")
