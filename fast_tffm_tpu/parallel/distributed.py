"""Multi-process bring-up — the TF1 ``ClusterSpec``/``Server`` equivalent.

The reference builds a gRPC cluster from the config's ``[Cluster]``
``ps_hosts``/``worker_hosts`` and runs async PS training (SURVEY.md §3.2/
§3.3). The TPU-native replacement: every worker is a ``jax.distributed``
process in ONE synchronous SPMD job; XLA collectives over ICI/DCN replace
gRPC parameter traffic; there are no ps roles — the table is row-sharded
across the global mesh (parallel/sharded.py), so the mesh *is* the
parameter server.

CLI surface parity: ``run_tffm.py train cfg dist_train worker <i>``
maps worker i onto jax.distributed process i, with ``worker_hosts[0]``
doubling as the coordinator (the analogue of the reference's chief
worker). ``ps`` roles are accepted and explained away (run_tffm.py):
a job that listed N ps hosts simply doesn't start them.
"""

from __future__ import annotations

import os
import time
from typing import Callable, List, Optional, Sequence, Tuple

from fast_tffm_tpu.config import FmConfig

# Per-attempt cap on the coordinator handshake: the total budget
# (cluster_connect_timeout_seconds) is spent in bounded slices with a
# short breather between them, so one wedged TCP connect can't eat the
# whole budget and the worker's log shows it is still trying.
CONNECT_ATTEMPT_CAP_SECONDS = 60.0
CONNECT_RETRY_SLEEP_SECONDS = 2.0


def coordinator_address(cfg: FmConfig, generation: int = 0,
                        hosts: Optional[Sequence[str]] = None) -> str:
    """worker_hosts[0] with its port shifted up by 1000: the reference's
    worker port serves TF gRPC; the jax.distributed coordinator needs its
    own listening port, derived deterministically so every process
    computes the same address from the shared config.

    ``generation`` (elastic recovery) bumps the port once per cluster
    reform: the previous generation's coordinator socket may still sit
    in TIME_WAIT — or belong to the dead worker — and every survivor
    derives the same bumped address without a side channel. ``hosts``
    overrides the config's worker list (the reform passes the
    SURVIVING hosts; the new chief is the first of them)."""
    host = (hosts if hosts is not None else cfg.worker_hosts)[0]
    if ":" in host:
        name, port = host.rsplit(":", 1)
        return f"{name}:{int(port) + 1000 + int(generation)}"
    return f"{host}:{8476 + int(generation)}"


def _emit_bringup_failed(address: str, process_id: int, attempts: int,
                         timeout_seconds: float,
                         last_error: Exception) -> None:
    """``health: cluster_bringup_failed`` on the active telemetry
    stream, flushed before the caller raises: the exception alone is
    invisible to fmstat post-mortems — an operator reading the stream
    of a job that never formed must see WHICH process gave up on WHICH
    coordinator. No-op without an active run."""
    from fast_tffm_tpu.obs.telemetry import active
    tel = active()
    if tel is None:
        return
    try:
        tel.count("cluster/bringup_failures")
        tel.sink.emit("health", {
            "status": "cluster_bringup_failed",
            "coordinator": address,
            "process_index": int(process_id),
            "attempts": int(attempts),
            "timeout_seconds": float(timeout_seconds),
            "error": f"{type(last_error).__name__}: "
                     f"{str(last_error)[:300]}",
        })
        tel.sink.flush()
    except Exception:  # noqa: BLE001 - forensics must never mask the
        # actionable bring-up error about to be raised
        pass


def initialize_with_retry(initialize: Callable[..., None], address: str,
                          num_processes: int, process_id: int,
                          timeout_seconds: float,
                          sleep: Callable[[float], None] = time.sleep,
                          clock: Callable[[], float] = time.monotonic
                          ) -> int:
    """Drive ``initialize`` (jax.distributed.initialize-shaped) in a
    bounded retry loop until it succeeds or ``timeout_seconds`` of
    total budget is spent, then raise naming the coordinator address
    and which process failed to join — the un-hardened call hangs
    workers forever on a coordinator that is still booting (the common
    staggered bring-up) or never coming (the failure an operator must
    see, not infer from silence). Each attempt gets jax's own
    ``initialization_timeout`` capped at CONNECT_ATTEMPT_CAP_SECONDS
    and at the remaining budget. ``sleep``/``clock`` are injectable so
    tests pin the budget math without real waits. Returns the number
    of attempts made (for logging/tests)."""
    deadline = clock() + timeout_seconds
    attempts = 0
    last_error: Exception = None  # type: ignore[assignment]
    while True:
        remaining = deadline - clock()
        if remaining <= 0:
            _emit_bringup_failed(address, process_id, attempts,
                                 timeout_seconds, last_error)
            raise RuntimeError(
                f"process {process_id} failed to join the "
                f"jax.distributed cluster: coordinator {address} did "
                f"not accept the connection within "
                f"cluster_connect_timeout_seconds={timeout_seconds:g}s "
                f"({attempts} attempt(s)). Is the coordinator process "
                "(worker 0) up, and its port (worker_hosts[0] port + "
                f"1000) reachable from this host? Last error: "
                f"{last_error}") from last_error
        attempts += 1
        try:
            initialize(coordinator_address=address,
                       num_processes=num_processes,
                       process_id=process_id,
                       initialization_timeout=max(1, int(min(
                           remaining, CONNECT_ATTEMPT_CAP_SECONDS))))
            return attempts
        except Exception as e:  # jax surfaces an unreachable
            # coordinator as RuntimeError (grpc DEADLINE_EXCEEDED /
            # UNAVAILABLE) — class varies by jax version, so retry on
            # any failure while budget remains; a genuinely fatal
            # misconfiguration exhausts the budget and raises with the
            # last underlying error attached.
            last_error = e
            if clock() + CONNECT_RETRY_SLEEP_SECONDS >= deadline:
                # No room for another attempt: fall through to the
                # deadline raise on the next loop iteration.
                sleep(max(0.0, deadline - clock()))
            else:
                sleep(CONNECT_RETRY_SLEEP_SECONDS)


def init_from_cluster(cfg: FmConfig, job_name: str,
                      task_index: int) -> Tuple[int, int]:
    """Join the SPMD job as process ``task_index`` of the cluster in the
    config. Returns (data_shard_index, num_shards) for the input
    pipeline (each worker reads a disjoint line shard, the analogue of
    the reference's per-worker file shards; SURVEY §3.2)."""
    if job_name != "worker":
        raise ValueError(f"unsupported job_name {job_name!r}; only "
                         "'worker' exists in the TPU rebuild (ps roles "
                         "are handled at the CLI)")
    hosts = cfg.worker_hosts
    # Validate BEFORE the single-host early return: a launcher started
    # with an out-of-range index against a 1-host config would
    # otherwise be silently accepted as shard 0 of 1 and race the real
    # worker's checkpoint writes instead of erroring like any
    # multi-host config does.
    if not 0 <= task_index < max(len(hosts), 1):
        raise ValueError(f"task_index {task_index} out of range for "
                         f"{len(hosts)} worker_hosts")
    if len(hosts) <= 1:
        return 0, 1
    _join_cluster(cfg, address=coordinator_address(cfg),
                  num_processes=len(hosts), process_id=task_index)
    return task_index, len(hosts)


def _liveness_owns_death_detection(cfg: FmConfig) -> bool:
    """jax's own death detection (abort every survivor ~100s after any
    task death) is replaced ONLY when the heartbeat-lease layer is on
    to do the job instead — with ``heartbeat_seconds = 0`` there is no
    monitor thread to enforce the collective deadline, and disabling
    both layers would make a dead peer an UNBOUNDED hang (strictly
    worse than the historical abort)."""
    return getattr(cfg, "heartbeat_seconds", 0) > 0


def _join_cluster(cfg: FmConfig, address: str, num_processes: int,
                  process_id: int) -> None:
    """Clear any pre-existing backends, assert the platform/collectives
    config, and join the jax.distributed job at ``address`` as process
    ``process_id`` of ``num_processes`` — shared by the initial
    bring-up and the elastic reform (which must rebuild the exact same
    client state against a different membership)."""
    import os

    import jax
    import jax.extend.backend
    # A backend already exists by now: train() runs the capacity
    # pre-flight and stamps run_meta (both touch jax.devices()) before
    # the join. Distributed state and collectives config only apply at
    # client creation, so the client is cleared and re-created here.
    # VERIFIED: on the CPU backend (the gloo multi-process tests, the
    # elastic reform). NOT verified: destroying and re-creating a TPU
    # client in-process on real libtpu — no multi-host TPU job has run
    # this path; if it fails there, the fix is to join before anything
    # touches the backend.
    jax.extend.backend.clear_backends()
    # Re-assert the operator's platform choice after the clear.
    if os.environ.get("JAX_PLATFORMS"):
        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    # CPU processes need an explicit collectives backend to federate into
    # one device namespace (TPU slices federate natively over ICI/DCN;
    # this setting only affects the CPU client, e.g. the localhost
    # smoke-cluster test, SURVEY §4).
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    def _initialize(**kw):
        try:
            if _liveness_owns_death_detection(cfg):
                _initialize_resilient(**kw)
            else:
                jax.distributed.initialize(**kw)
        except Exception:
            # A failed connect leaves the half-built client in
            # jax.distributed's global state (the client is registered
            # BEFORE connect()), and a bare re-initialize would then
            # raise 'should only be called once' instead of retrying.
            # Tear the partial state down so the next attempt is clean.
            try:
                jax.distributed.shutdown()
            except Exception:
                pass
            raise

    initialize_with_retry(
        _initialize,
        address=address,
        num_processes=num_processes,
        process_id=process_id,
        timeout_seconds=getattr(cfg, "cluster_connect_timeout_seconds",
                                300.0))
    if jax.process_count() != num_processes:
        raise RuntimeError(
            "jax.distributed did not federate the cluster: expected "
            f"{num_processes} processes, got {jax.process_count()}")


# jax's own death detection is DISABLED at bring-up (heartbeat budget
# pushed out ~3 years): its only response to a dead task is a
# LOG(FATAL) that ABORTS every surviving process ~100s after the loss
# — the exact opposite of this module's job. The liveness layer
# (parallel/liveness.py: sub-10s lease staleness, named diagnosis,
# elastic recovery) replaces it; transport-level failures still
# surface organically as collective errors, which the deadline guard
# converts.
_DISABLED_HEARTBEAT_KWARGS = dict(
    service_heartbeat_interval_seconds=100_000_000,
    service_max_missing_heartbeats=1_000,
    client_heartbeat_interval_seconds=100_000_000,
    client_max_missing_heartbeats=1_000,
)


def _initialize_resilient(coordinator_address: str, num_processes: int,
                          process_id: int,
                          initialization_timeout: int = 300) -> None:
    """jax.distributed.initialize with survivable failure semantics:
    identical global-state wiring (the public function forwards to
    this same ``global_state.initialize``), but with the runtime's
    die-with-the-first-casualty heartbeat detection pushed out of the
    picture (see ``_DISABLED_HEARTBEAT_KWARGS``). Falls back to the
    plain public call on signature drift — the cluster still works
    there, only the abort-on-peer-death default returns."""
    import jax
    from jax._src import distributed as _dist
    try:
        _dist.global_state.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id,
            initialization_timeout=initialization_timeout,
            **_DISABLED_HEARTBEAT_KWARGS)
    except TypeError:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id,
            initialization_timeout=initialization_timeout)


# Strong references to retired runtime clients/services: their gRPC
# threads may still be parked on a dead peer, and a destructor-driven
# shutdown from GC could block or abort mid-recovery. One entry per
# lost-worker incident — a deliberate, bounded leak.
_RETIRED: List[Tuple] = []


def has_retired_clients() -> bool:
    """True when this process retired a dead cluster's runtime client
    (elastic recovery or fail-fast). The CLI checks this to exit via
    ``os._exit`` after sinks close: interpreter teardown would destroy
    the retired service, whose call cancellation trips the retired
    client's error-poll handler — a LOG(FATAL) abort AFTER a perfectly
    clean run. All durable state (checkpoint, metrics, logs, exports)
    is closed by then; skipping C++ teardown of already-dead cluster
    plumbing is the correct exit."""
    return bool(_RETIRED)


def retire_distributed_client() -> None:
    """Drop the jax.distributed client/service WITHOUT the shutdown
    handshake. A clean ``shutdown()`` runs the coordination service's
    Shutdown barrier, which by definition cannot complete while a
    registered peer is dead — it stalls for its full timeout and then
    (with jaxlib's default callback) aborts the process. After a
    WorkerLostError the old cluster is unrecoverable anyway: keep the
    objects alive (no destructor side effects), reset the global
    state so a reform (or a lone-survivor fallback to single-process)
    can rebuild from scratch, and restore the local-backend config."""
    import jax
    import jax.extend.backend
    from jax._src import distributed as _dist
    state = _dist.global_state
    _RETIRED.append((state.client, state.service,
                     getattr(state, "preemption_sync_manager", None)))
    _dist.global_state = type(state)()
    # The gloo CPU-collectives setting outlives the client it needs: a
    # lone survivor rebuilding its LOCAL backend would fail inside
    # make_gloo_tcp_collectives(distributed_client=None). Reset to the
    # default; _join_cluster re-asserts gloo when a shrunken
    # multi-process cluster actually reforms.
    try:
        jax.config.update("jax_cpu_collectives_implementation", "none")
    except Exception:
        pass
    try:
        jax.extend.backend.clear_backends()
    except Exception:
        pass


def reform_shrunken_cluster(cfg: FmConfig, lease, generation: int,
                            logger=None) -> Tuple[int, int, List[int]]:
    """Rebuild the SPMD job from the surviving membership after a
    WorkerLostError (elastic = shrink):

    1. retire the old distributed client (no shutdown handshake — see
       ``retire_distributed_client``);
    2. announce readiness for cluster generation ``generation`` in the
       heartbeat rendezvous dir and wait until every LIVE lease holder
       has announced and the set holds still for a settle window —
       survivors' guard deadlines expire at slightly different times,
       so membership is only committed once it stops changing;
    3. re-rank: survivors sorted by ORIGINAL process index; the first
       survivor's host becomes the new coordinator at a
       generation-bumped port; ``initialize_with_retry`` forms the
       shrunken job (a lone survivor skips jax.distributed entirely
       and simply continues single-process).

    Returns ``(new_shard_index, num_shards, members)`` — the members
    list holds the survivors' original indices, which is also the new
    input-shard order, so the lost worker's byte ranges redistribute
    across everyone at the next epoch pass. The lease's expected
    membership is shrunk in place so departed workers stop being
    reported lost forever after."""
    from fast_tffm_tpu.parallel.liveness import REFORM_SETTLE_SECONDS
    log = logger or _silent_logger()
    retire_distributed_client()
    lease.announce_reform(generation)
    budget = getattr(cfg, "cluster_connect_timeout_seconds", 300.0)
    deadline = time.monotonic() + budget
    members: List[int] = []
    stable_since: Optional[float] = None
    while True:
        live = set(lease.live_members())
        announced = set(lease.reform_members(generation))
        agreed = sorted(live & announced)
        now = time.monotonic()
        if agreed and live <= announced:
            if agreed != members:
                members, stable_since = agreed, now
            elif (stable_since is not None
                  and now - stable_since >= REFORM_SETTLE_SECONDS):
                break
        else:
            members, stable_since = agreed, None
        if now >= deadline:
            raise RuntimeError(
                f"elastic reform generation {generation} did not "
                f"converge within cluster_connect_timeout_seconds="
                f"{budget:g}s: live={sorted(live)} "
                f"announced={sorted(announced)}")
        time.sleep(min(0.1, max(lease.heartbeat_seconds / 4, 0.02)))
    if lease.process_index not in members:
        raise RuntimeError(
            f"elastic reform generation {generation}: this process "
            f"({lease.process_index}) lost its own lease; members="
            f"{members}")
    lease.members = tuple(members)
    rank = members.index(lease.process_index)
    log.info("elastic reform generation %d: survivors %s, this process "
             "re-ranks %d -> %d of %d", generation, members,
             lease.process_index, rank, len(members))
    if len(members) > 1:
        hosts = [cfg.worker_hosts[m] for m in members]
        _join_cluster(cfg,
                      address=coordinator_address(cfg, generation,
                                                  hosts=hosts),
                      num_processes=len(members), process_id=rank)
    if rank == 0:
        # Reform-completion litter sweep (chief only): superseded
        # generations' announce/plan/commit files and departed
        # members' leases must not accumulate over a long elastic
        # stream's reforms.
        from fast_tffm_tpu.parallel.liveness import sweep_lease_dir
        sweep_lease_dir(lease.directory, generation, members,
                        join_stale_after=lease.stale_after)
    return rank, len(members), members


def reform_grown_cluster(cfg: FmConfig, lease, generation: int,
                         plan: dict, logger=None
                         ) -> Tuple[int, int, List[int], int]:
    """Rebuild the SPMD job with replacement worker(s) admitted
    (``elastic = grow``) — the inverse of ``reform_shrunken_cluster``,
    through the same per-generation rendezvous files:

    1. retire the (healthy) distributed client when one exists — the
       reformed job needs a fresh client against the bumped
       generation's coordinator either way, and retire is the one
       teardown that can never stall on a handshake;
    2. announce readiness for ``generation`` and poll
       ``grow_rendezvous_step``: incumbents are mandatory, planned
       joiners optional — a joiner whose worker lease never turns up
       fresh inside ``join_settle_seconds`` died mid-rendezvous and
       the reform proceeds WITHOUT it (never wedging the incumbents);
       announcers the plan never assigned are refused loudly;
    3. the chief commits the final membership (``commit-<g>.json``);
       every party adopts it verbatim, so nobody can disagree about
       ``num_processes``; then form the job at the generation-bumped
       coordinator port.

    A joiner that dies AFTER the commit but before its connect lands
    surfaces as the bring-up retry exhausting its budget; the
    incumbents then fall back to a shrink-style reform at the NEXT
    generation, which the now-stale joiner drops out of — a bounded
    detour, not a wedge. Returns ``(rank, num_shards, members,
    generation)`` — the FINAL generation, which the fallback bumps
    past the plan's: the caller must adopt it, or the next reform
    would reuse an already-consumed generation (and its coordinator
    port, still held by the retired service)."""
    from fast_tffm_tpu.parallel import liveness as lv
    log = logger or _silent_logger()
    import jax
    if jax.process_count() > 1:
        retire_distributed_client()
    lease.announce_reform(generation)
    budget = getattr(cfg, "cluster_connect_timeout_seconds", 300.0)
    settle = getattr(cfg, "join_settle_seconds", 5.0)
    deadline = time.monotonic() + budget
    join_deadline = time.monotonic() + max(
        settle, lease.stale_after + lease.heartbeat_seconds)
    incumbents = [int(i) for i in plan["incumbents"]]
    chief = lease.process_index == min(incumbents)
    refused: set = set()
    while True:
        now = time.monotonic()
        members = lv.read_commit(lease.directory, generation)
        if members is not None:
            break
        for slot in lv.unexpected_announcers(lease, plan):
            if slot not in refused:
                refused.add(slot)
                log.warning(
                    "grow generation %d: refusing announce from slot "
                    "%d — not in the admission plan (stale generation "
                    "or slot collision)", generation, slot)
                if chief:
                    # Chief-only like the other job-global health
                    # events: every incumbent sees the same announce
                    # file, and per-worker shard counters merge by
                    # SUM — one turned-away process must count once,
                    # not once per incumbent.
                    lv.emit_join_refused(generation, slot,
                                         "announced a generation it "
                                         "was never planned into")
        if chief:
            members = lv.grow_rendezvous_step(lease, plan, now,
                                              join_deadline)
            if members is not None:
                dropped = sorted(
                    set(int(s) for s in plan["joiners"].values())
                    - set(members))
                if dropped:
                    log.warning(
                        "grow generation %d: planned joiner slot(s) "
                        "%s never rendezvoused inside the settle "
                        "window (died mid-rendezvous?); reforming "
                        "without them", generation, dropped)
                lv.write_commit(lease.directory, generation, members)
                break
        if now >= deadline:
            raise RuntimeError(
                f"elastic grow generation {generation} did not "
                f"converge within cluster_connect_timeout_seconds="
                f"{budget:g}s: announced="
                f"{lease.reform_members(generation)} plan={plan}")
        time.sleep(min(0.1, max(lease.heartbeat_seconds / 4, 0.02)))
    if lease.process_index not in members:
        raise RuntimeError(
            f"elastic grow generation {generation}: this incumbent "
            f"({lease.process_index}) is missing from the committed "
            f"membership {members}")
    lease.members = tuple(members)
    rank = members.index(lease.process_index)
    joined = sorted(set(members) - set(incumbents))
    log.info("elastic grow generation %d: members %s (admitted %s), "
             "this process re-ranks %d -> %d of %d", generation,
             members, joined or "nobody", lease.process_index, rank,
             len(members))
    if len(members) > 1:
        hosts = [cfg.worker_hosts[m] for m in members]
        try:
            _join_cluster(cfg,
                          address=coordinator_address(cfg, generation,
                                                      hosts=hosts),
                          num_processes=len(members), process_id=rank)
        except RuntimeError:
            stale_joiners = [s for s in joined if not lease.fresh(s)]
            if not stale_joiners:
                raise
            # The committed joiner died between commit and connect:
            # fall back to a shrink-style reform at the next
            # generation — live-lease filtering drops it there.
            log.warning(
                "grow generation %d bring-up failed with committed "
                "joiner(s) %s now stale; falling back to a shrink "
                "reform at generation %d", generation, stale_joiners,
                generation + 1)
            rank, n, members = reform_shrunken_cluster(
                cfg, lease, generation + 1, logger)
            return rank, n, members, generation + 1
    if rank == 0:
        from fast_tffm_tpu.parallel.liveness import sweep_lease_dir
        sweep_lease_dir(lease.directory, generation, members,
                        join_stale_after=lease.stale_after)
    return rank, len(members), members, generation


def join_rendezvous(cfg: FmConfig, logger=None
                    ) -> Tuple[object, int, int, List[int], int, int]:
    """The replacement process's half of elastic GROW
    (``run_tffm.py train <cfg> --join``): publish a join ticket in the
    rendezvous dir, wait for a running cluster's admission plan, then
    come up through the SAME generation-bumped rendezvous the
    incumbents use. Returns ``(lease, rank, num_shards, members,
    generation, slot)`` — from there the elastic driver treats this
    process exactly like any other member (verified checkpoint
    restore, chief-broadcast watermark/vocab, shard re-balance all
    happen in the session it enters).

    Bounded: ``join_timeout_seconds`` (default: the cluster-connect
    budget) caps the wait for an offer; a commit that EXCLUDES this
    joiner (it lost a slot race, or announced too late) is refused
    loudly and the wait resumes for the next opening until the budget
    runs out."""
    from fast_tffm_tpu.parallel import liveness as lv
    log = logger or _silent_logger()
    directory = lv.lease_dir(cfg)
    os.makedirs(directory, exist_ok=True)
    hb = getattr(cfg, "heartbeat_seconds", 5.0)
    ticket = lv.JoinTicket(directory, heartbeat_seconds=hb).start()
    budget = (getattr(cfg, "join_timeout_seconds", 0.0)
              or getattr(cfg, "cluster_connect_timeout_seconds", 300.0))
    deadline = time.monotonic() + budget
    poll = min(1.0, max(hb / 4, 0.05))
    min_generation = 0
    lease = None
    log.info("join: ticket %s published in %s; waiting for a running "
             "cluster's admission plan (budget %gs)", ticket.name,
             directory, budget)
    try:
        while True:
            plan = lv.grow_plan_for(directory, ticket.name,
                                    min_generation=min_generation)
            if plan is not None:
                g = int(plan["generation"])
                slot = int(plan["joiners"][ticket.name])
                committed = lv.read_commit(directory, g)
                if committed is not None and slot not in committed:
                    # Stale plan: that generation already closed
                    # without us. Refuse it loudly and only consider
                    # NEWER offers from here on.
                    log.warning(
                        "join: generation %d committed without this "
                        "joiner (stale plan); waiting for a fresh "
                        "offer", g)
                    min_generation = g + 1
                    plan = None
            if plan is not None:
                hint = sorted({int(i) for i in plan["incumbents"]}
                              | {int(s)
                                 for s in plan["joiners"].values()})
                lease = lv.HeartbeatLease(
                    directory, process_index=slot, members=hint,
                    heartbeat_seconds=hb).start()
                lease.announce_reform(g)
                log.info("join: announced for cluster generation %d "
                         "as worker slot %d", g, slot)
                while True:
                    committed = lv.read_commit(directory, g)
                    if committed is not None:
                        break
                    if time.monotonic() >= deadline:
                        raise RuntimeError(
                            f"join: generation {g} never committed "
                            f"within join budget {budget:g}s (did the "
                            "incumbents die mid-rendezvous?)")
                    time.sleep(poll)
                if slot not in committed:
                    log.warning(
                        "join: commit for generation %d excludes this "
                        "joiner (slot race lost / announce too late); "
                        "re-queueing for the next opening", g)
                    lv.emit_join_refused(g, slot,
                                         "commit excluded this joiner")
                    lease.stop()
                    lease = None
                    min_generation = g + 1
                    continue
                members = committed
                lease.members = tuple(members)
                rank = members.index(slot)
                if len(members) > 1:
                    hosts = [cfg.worker_hosts[m] for m in members]
                    _join_cluster(
                        cfg,
                        address=coordinator_address(cfg, g,
                                                    hosts=hosts),
                        num_processes=len(members), process_id=rank)
                log.info("join: admitted into generation %d as rank "
                         "%d of %d (worker slot %d)", g, rank,
                         len(members), slot)
                return lease, rank, len(members), members, g, slot
            if time.monotonic() >= deadline:
                raise RuntimeError(
                    f"join: no running cluster admitted this process "
                    f"within {budget:g}s — is a trainer with elastic "
                    f"= grow running against "
                    f"{getattr(cfg, 'model_file', '?')}, with a free "
                    "worker slot, and reaching its next safe barrier "
                    "(epoch boundary / publish settle)?")
            time.sleep(poll)
    except BaseException:
        if lease is not None:
            try:
                lease.stop()
            except Exception:
                pass
        raise
    finally:
        ticket.stop(remove=True)


def _silent_logger():
    from fast_tffm_tpu.utils.logging import get_logger
    return get_logger()
