"""Predict driver — the ``py/fm_predict.py`` equivalent (SURVEY.md §3.4).

Restores the latest checkpoint at the config's ``model_file``, streams
the predict files through parser + scorer, and writes one score per
input line, order-preserving — sigmoid-transformed for logistic loss,
raw for mse. ``score_path`` is treated as a directory; each input file
``f`` produces ``<score_path>/<basename(f)>.score``.

Both drivers run ONE continuous batch stream across ALL predict files
(fast_tffm_tpu/scoring.py): file N's disk write, file N+1's D2H, file
N+2's scoring, and file N+3's parse all overlap — no per-file fetcher
drain, no per-file warmup, no per-file telemetry barrier (README
"Predict path"; the pre-refactor per-file loop was most of a 15x
predict-vs-train gap on an earlier device — not re-measured on the
v5e, ROADMAP S4).
"""

from __future__ import annotations

import os
import time
from typing import List, Optional

import jax
import numpy as np

from fast_tffm_tpu.checkpoint import (CheckpointState,
                                      check_restored_vocab,
                                      checkpoint_template, device_rows)
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import expand_files
from fast_tffm_tpu.metrics import sigmoid
from fast_tffm_tpu.obs.telemetry import (active, make_telemetry,
                                         pop_active, push_active)
from fast_tffm_tpu.obs.trace import begin, span
from fast_tffm_tpu.scoring import ScoreWriter, score_sweep
from fast_tffm_tpu.utils.logging import get_logger


def load_table(cfg: FmConfig, mesh=None,
               step: Optional[int] = None,
               with_step: bool = False):
    """Restore the table from the latest checkpoint — or, with an
    explicit ``step``, those exact verified bytes (the serving
    process's hot-reload load, and the soak's per-step parity control;
    restore() verifies an explicit step and raises instead of walking
    past it).

    With a mesh: restored ROW-SHARDED in the [ckpt_rows, D] checkpoint
    layout — the full table never materializes on one device or host
    (BASELINE config #5 scale: 10^9 rows ~ 36 GB dense). Without: the
    logical [num_rows, D] table on the default device.

    ``with_step=True`` returns ``(table, step)`` — callers that must
    pair the table with its step's sidecars (the admit-mode vocab slot
    map) need to know which step the walk-back actually restored."""
    from fast_tffm_tpu.utils.retry import RetryPolicy
    ckpt = CheckpointState(cfg.model_file,
                           retry=RetryPolicy.from_config(cfg),
                           verify=getattr(cfg, "ckpt_verify", "size"))
    restored = ckpt.restore(
        step=step, template=checkpoint_template(cfg, mesh,
                                                host=mesh is None))
    ckpt.close()
    if restored is None:
        raise FileNotFoundError(
            f"no checkpoint found under {cfg.model_file}.ckpt "
            "(run training first)")
    check_restored_vocab(cfg, restored)
    loaded_step = int(restored["step"])
    if mesh is not None:
        table = restored["table"]
    else:
        # Checkpoints store the 4096-aligned [ckpt_rows, D] layout;
        # the single-device scorer wants the logical table. The
        # restore landed on the host: only those rows reach the device.
        table = device_rows(restored["table"], cfg.num_rows)
    return (table, loaded_step) if with_step else table


def predict_scores(cfg: FmConfig, table: jax.Array, files,
                   mesh=None, backend=None, vocab=None) -> np.ndarray:
    """Raw scores for every example in ``files``, in input order. With a
    mesh, the batch is data-sharded and scored against the row-sharded
    table in place (table shape [ckpt_rows, D]). With a lookup
    ``backend`` (lookup.HostOffloadLookup), rows are gathered host-side
    and only [U, D] blocks reach the device (``table`` is unused).

    A thin collector over scoring.score_sweep — the same continuous
    cross-file stream predict() writes files from, concatenated."""
    out: List[np.ndarray] = []
    score_sweep(cfg, table, files,
                on_file=lambda _path, vals: out.append(vals),
                mesh=mesh, backend=backend, vocab=vocab)
    return (np.concatenate(out) if out
            else np.zeros(0, dtype=np.float32))


def predict(cfg: FmConfig, table: Optional[jax.Array] = None,
            job_name: Optional[str] = None,
            task_index: Optional[int] = None) -> List[str]:
    """Run batch prediction; returns the list of score files written.

    Multi-device hosts score through the mesh (row-sharded table +
    data-sharded batches — SURVEY.md §3.4's single restore+score stack,
    scaled the same way training is); a lone device gets the plain
    jitted scorer. ``dist_train worker <i>`` argv (mirroring the train
    CLI) joins a jax.distributed job: input is byte-range-sharded by
    process, scored in lockstep through the global mesh, and the chief
    merges per-process part files into the ordered score file (a shared
    ``score_path`` filesystem is assumed, as for checkpoints)."""
    logger = get_logger(log_file=cfg.log_file or None)
    if job_name is not None:
        from fast_tffm_tpu.parallel.distributed import init_from_cluster
        init_from_cluster(cfg, job_name, task_index or 0)
    # Run telemetry (obs/): created after cluster init so the process
    # index in the run metadata (and the per-worker shard suffix) is
    # real. The try/finally below is the sink's lifecycle guarantee —
    # a crash mid-sweep still flushes everything buffered.
    tel = make_telemetry(cfg, "predict")
    tel_prev = push_active(tel)
    # Compute-plane liveness (parallel/liveness.py): multi-process
    # predict is the same lockstep collective protocol as distributed
    # validation — a dead peer must raise a named WorkerLostError, not
    # park the survivors in the window allgather forever. No elastic
    # recovery here (predict is cheap to rerun); fail fast with the
    # diagnosis.
    lease = None
    guard_prev = None
    guard_installed = False
    if jax.process_count() > 1:
        from fast_tffm_tpu.parallel.liveness import (HeartbeatLease,
                                                     install_guard,
                                                     lease_dir)
        if cfg.heartbeat_seconds > 0:
            lease = HeartbeatLease(
                lease_dir(cfg), process_index=jax.process_index(),
                members=range(jax.process_count()),
                heartbeat_seconds=cfg.heartbeat_seconds).start()
            if tel is not None:
                tel.lease = lease
        guard_prev = install_guard(lease, cfg.collective_timeout_seconds)
        guard_installed = True
    # Entry until the sweep's first dispatch: restore or adopt the
    # table, build the scorer and the input pipeline. It is paid once
    # a call, so it weighs on short sweeps (obs/trace.begin).
    setup = begin("predict/setup", seconds="predict/setup_seconds")
    try:
        written = _predict_body(cfg, table, logger, setup)
        return written
    except BaseException as e:
        # Crash forensics (obs/health.py): traceback + recent-event
        # ring as the stream's last substantive event; the finally
        # still closes the sink so run_end terminates the stream.
        from fast_tffm_tpu.parallel.liveness import WorkerLostError
        if isinstance(e, WorkerLostError):
            # Fail fast with the diagnosis: drop buffered device
            # scalars (their producing collectives will never
            # complete) and retire the dead cluster's client so
            # interpreter exit isn't stalled by a shutdown barrier
            # that cannot succeed.
            if tel is not None:
                tel.sink.discard_scalars()
            from fast_tffm_tpu.parallel.distributed import (
                retire_distributed_client)
            retire_distributed_client()
        if tel is not None:
            try:
                tel.record_crash(e)
            except Exception:
                logger.exception("crash event emission failed")
        raise
    finally:
        setup.end()
        if lease is not None:
            try:
                lease.stop()
            except Exception:
                logger.exception("heartbeat lease stop failed")
        if guard_installed:
            from fast_tffm_tpu.parallel.liveness import restore_guard
            restore_guard(guard_prev)
        if tel is not None:
            try:
                tel.close()
            except Exception:
                logger.exception("metrics sink close failed")
        pop_active(tel_prev)


def _score_out_path(cfg: FmConfig, path: str) -> str:
    return os.path.join(cfg.score_path,
                        os.path.basename(path) + ".score")


def _predict_body(cfg: FmConfig, table, logger, setup) -> List[str]:
    tel = active()
    if jax.process_count() > 1:
        if cfg.lookup == "host":
            raise ValueError("lookup = host predict is single-process")
        if getattr(cfg, "vocab_mode", "fixed") == "admit":
            raise ValueError(
                "vocab_mode = admit predict is single-process (the "
                "slot map is host state; see the train-side "
                "restriction)")
        return _predict_multiprocess(cfg, table, logger, setup)
    mesh = None
    backend = None
    vocab = None
    admit = getattr(cfg, "vocab_mode", "fixed") == "admit"
    if admit and table is not None:
        raise ValueError(
            "vocab_mode = admit predict restores the (table, slot "
            "map, step) triple from the checkpoint together — a "
            "caller-held table has no slot map to pair with; pass "
            "table=None")
    if cfg.lookup == "host":
        # Offload predict (lookup.py seam): restore (or wrap a
        # caller-supplied table) into the best offload backend — pinned
        # accelerator-host memory where supported, local numpy else; the
        # device only ever sees per-batch [U, D] row blocks. Routing a
        # provided table to the device paths here would materialize the
        # offload-scale table in HBM — the exact OOM this mode avoids.
        from fast_tffm_tpu.lookup import make_score_backend
        backend = make_score_backend(cfg, table)
        table = None
        logger.info("offload predict [%s]: table [%d, %d] outside HBM",
                    type(backend).__name__, *backend.table.shape)
    elif jax.device_count() > 1:
        from fast_tffm_tpu.parallel.sharded import make_mesh, place_table
        try:
            mesh = make_mesh()
        except ValueError as e:
            # e.g. a non-power-of-two device count: score on one device
            # rather than refusing (the table must then fit it).
            logger.warning("mesh predict unavailable (%s); scoring on a "
                           "single device", e)
        if mesh is not None and cfg.batch_size % mesh.shape["data"]:
            logger.warning(
                "batch_size %d not divisible by the mesh data axis %d; "
                "scoring on a single device", cfg.batch_size,
                mesh.shape["data"])
            mesh = None
        if mesh is not None:
            logger.info("mesh predict: %s over %d devices",
                        dict(mesh.shape), jax.device_count())
            if table is not None and int(table.shape[0]) != cfg.ckpt_rows:
                table = place_table(cfg, mesh, table)
    vstep = None
    if backend is not None:
        vstep = int(getattr(backend, "step", -1))
    elif table is None:
        table, vstep = load_table(cfg, mesh, with_step=True)
    if table is not None:
        # Ledger (obs/memory.py): the sweep's resident table — .nbytes
        # is host metadata, no fetch. Upserted per sweep; the process-
        # global ledger carries it for the mem/* gauges and any OOM's
        # owner breakdown.
        from fast_tffm_tpu.obs.memory import LEDGER
        LEDGER.register("table", int(table.nbytes))
    if not admit:
        # The inverse loud-failure of the admit-without-sidecar raise
        # below: an admit-trained table scored through modulo ids
        # would gather arbitrary rows with zero errors.
        from fast_tffm_tpu.checkpoint import refuse_fixed_mode_admit_step
        refuse_fixed_mode_admit_step(
            cfg, os.path.abspath(cfg.model_file) + ".ckpt", vstep)
    if admit:
        # Pair the restored table with ITS step's slot map — the
        # sidecar rides checkpoints exactly like the watermark, so the
        # walk-back can never split the (table, slot map) pair.
        from fast_tffm_tpu.checkpoint import load_vocab_map
        vocab = load_vocab_map(
            cfg, os.path.abspath(cfg.model_file) + ".ckpt", vstep)
        logger.info("vocab admission map: %d live rows at step %d",
                    vocab.live_rows, vstep)
    os.makedirs(cfg.score_path, exist_ok=True)
    files = expand_files(cfg.predict_files)
    written: List[str] = []
    # Writer thread (see scoring.ScoreWriter): file N's disk write
    # overlaps file N+1's parse/score/D2H. The inner close() surfaces
    # deferred write errors on the clean path; the finally's close is
    # the idempotent no-mask flush for the error path.
    writer = ScoreWriter(logger)
    # fmlint: disable=R003 -- the clock of the sweep's running rate
    # (the log line, the gauge and each predict_file event); the
    # predict/seconds counter is the predict/run span's below
    t0 = time.perf_counter()
    emitted = [0]  # cumulative examples cut so far (single-writer:
    # on_file runs on one thread at a time — score_sweep's contract)

    def on_file(path: str, raw: np.ndarray) -> None:
        # Runs on the fetch worker thread mid-sweep (score_sweep's
        # contract): the transform is vectorized numpy, the submit is
        # a bounded queue put, the telemetry emit is thread-safe —
        # nothing here stalls the device loop beyond backpressure.
        vals = sigmoid(raw) if cfg.loss_type == "logistic" else raw
        out_path = _score_out_path(cfg, path)
        writer.submit(out_path, vals)
        written.append(out_path)
        emitted[0] += len(raw)
        if tel is not None:
            # Per-file wall time no longer exists (files overlap — that
            # is the point), so seconds/rate report the sweep so far
            # at this file's cut.
            # fmlint: disable=R003 -- closes the sweep-rate sample
            dt = time.perf_counter() - t0
            tel.sink.emit("predict_file",
                          {"path": path, "examples": len(raw),
                           "seconds": dt,
                           "examples_per_sec":
                               emitted[0] / dt if dt > 0 else 0.0})

    try:
        with span("predict/run", seconds="predict/seconds", leaf=False):
            n = score_sweep(cfg, table, files, on_file=on_file,
                            mesh=mesh, backend=backend, vocab=vocab,
                            before_first_dispatch=setup.end)
            with span("predict/write_wait"):
                writer.close()  # the writer thread's last files
    finally:
        writer.close(raise_error=False)
        from fast_tffm_tpu.obs.memory import LEDGER
        LEDGER.release("table")
    # fmlint: disable=R003 -- closes the rate's clock
    dt = time.perf_counter() - t0
    rate = n / dt if dt > 0 else 0.0
    if tel is not None:
        tel.set("predict/examples_per_sec", rate)
        # One barrier for the sweep (scores are host-side; the flush
        # is pure file I/O) — the per-file barriers the old loop paid
        # serialized the stream once per file.
        tel.barrier_flush(step=len(written))
    logger.info("predict sweep: %d files, %d examples, %.0f examples/s",
                len(written), n, rate)
    return written


def _predict_multiprocess(cfg: FmConfig, table, logger,
                          setup) -> List[str]:
    """Sharded predict, one continuous stream: every process scores its
    byte-range shard of ALL files through the global-mesh score fn in
    lockstep (each call is a collective program — the filler-batch
    protocol from distributed validation keeps uneven shards from
    deadlocking), demuxes its ordered local scores into per-file part
    files through the bounded writer thread, and the CHIEF's background
    merge thread concatenates parts in process order as each file's
    markers land (byte ranges are contiguous: process i's lines all
    precede process i+1's) — so the merge of file N overlaps the
    scoring of file N+1. Three sweep-level barriers (stale-part scrub,
    parts done, merge done) replace the old two barriers per file."""
    from jax.experimental import multihost_utils
    from fast_tffm_tpu.data.pipeline import (FileMarks,
                                             batch_iterator,
                                             probe_uniq_bucket,
                                             require_bounded_examples)
    from fast_tffm_tpu.models.fm import ModelSpec
    from fast_tffm_tpu.parallel.liveness import guarded_collective
    from fast_tffm_tpu.parallel.sharded import (lockstep_score_batches,
                                                make_mesh,
                                                make_sharded_score_fn)
    from fast_tffm_tpu.scoring import (PartMerger, ScoreDemux,
                                       scrub_stale_parts)
    require_bounded_examples(cfg, "multi-process predict")
    mesh = make_mesh()
    if cfg.batch_size % mesh.shape["data"]:
        raise ValueError(
            f"batch_size {cfg.batch_size} must be divisible by the mesh "
            f"data axis {mesh.shape['data']} for multi-process predict")
    logger.info("multi-process predict: %s over %d devices, %d processes",
                dict(mesh.shape), jax.device_count(), jax.process_count())
    if table is None:
        table, vstep = load_table(cfg, mesh, with_step=True)
        # Same admit-trained-under-fixed loud failure as the
        # single-process path (admit itself is rejected before this
        # branch): the existence probe is deterministic on the shared
        # checkpoint dir, so every process raises uniformly — no
        # collective divergence.
        from fast_tffm_tpu.checkpoint import refuse_fixed_mode_admit_step
        refuse_fixed_mode_admit_step(
            cfg, os.path.abspath(cfg.model_file) + ".ckpt", vstep)
    spec = ModelSpec.from_config(cfg)
    score_fn = make_sharded_score_fn(spec, mesh)
    p, P = jax.process_index(), jax.process_count()
    os.makedirs(cfg.score_path, exist_ok=True)
    tel = active()
    files = expand_files(cfg.predict_files)
    if not files:
        # Only an empty predict_files tuple reaches here (a non-matching
        # glob stays a literal path and fails loudly at the probe's
        # open). expand_files is deterministic, so every process returns
        # uniformly — no collective divergence. The sweep-level probe
        # below would otherwise IndexError; the old per-file loop just
        # never entered.
        logger.warning("predict_files is empty; nothing to score")
        return []
    out_paths = [_score_out_path(cfg, f) for f in files]
    # ONE uniq-bucket decision per sweep (probe_uniq_bucket samples the
    # first/last/largest file — deterministic bytes, so every process
    # agrees without a collective). The old per-file probe re-read
    # every file's head/mid/tail before scoring it AND recompiled
    # nothing it couldn't have shared — the "double read" half of the
    # predict gap.
    ub = cfg.uniq_bucket or probe_uniq_bucket(cfg, files)
    marks = FileMarks()
    it = batch_iterator(cfg, files, training=False, epochs=1,
                        keep_empty=True, shard_index=p, num_shards=P,
                        fixed_shape=True, uniq_bucket=ub,
                        file_marks=marks)
    # Parts/markers left by a CRASHED prior sweep into the same
    # score_path would satisfy the merger's marker polls instantly and
    # merge the old run's scores as if fresh — the chief scrubs them
    # (any part index, markers included), and the barrier keeps every
    # worker's first fresh part behind the scrub.
    if p == 0:
        stale = scrub_stale_parts(out_paths)
        if stale:
            logger.warning(
                "removed %d stale part file(s) from a prior predict "
                "sweep into %s (first: %s)", len(stale),
                cfg.score_path, stale[0])
    guarded_collective(multihost_utils.sync_global_devices,
                       "predict_parts_clean",
                       label="predict/clean_barrier")
    writer = ScoreWriter(logger)
    merger = PartMerger(out_paths, P, logger) if p == 0 else None
    # fmlint: disable=R003 -- the clock of this worker's rate gauge;
    # the predict/seconds counter is the predict/run span's below
    t0 = time.perf_counter()
    n_local = 0

    def on_file(path: str, raw: np.ndarray) -> None:
        vals = sigmoid(raw) if cfg.loss_type == "logistic" else raw
        out_path = _score_out_path(cfg, path)
        part = f"{out_path}.part{p}"
        # The marker is created only after the part file is durably
        # written+closed — the chief's merge thread keys on it.
        writer.submit(part, vals, marker=f"{part}.done")
        if tel is not None:
            tel.count("predict/examples", len(raw))
            tel.sink.emit("predict_file",
                          {"path": path, "examples": len(raw),
                           "process_index": p})

    demux = ScoreDemux(marks, on_file)
    setup.end()  # the lockstep windows dispatch from here on
    try:
        with span("predict/run", seconds="predict/seconds", leaf=False):
            with span("predict/sweep", leaf=False, files=len(files)):
                for batch, local in lockstep_score_batches(
                        cfg, it, mesh, score_fn, table, ub):
                    demux.consume(local[:batch.num_real])
                    n_local += batch.num_real
                    if tel is not None:
                        tel.heartbeat()  # lockstep progress feeds the
                        # watchdog; a hung peer stalls the whole cluster
            demux.finalize()
            writer.close()  # every part + marker of this worker is on
            # disk
            guarded_collective(multihost_utils.sync_global_devices,
                               "predict_parts_done",
                               label="predict/parts_barrier")
            if merger is not None:
                # All markers are durable past the barrier: the merge
                # thread finishes its remaining files promptly (bounded
                # per-marker grace; a missing marker raises by name).
                merger.finish()
            # Chief finished reading (and deleting) every part before
            # anyone returns and could rewrite/reuse the score dir.
            guarded_collective(multihost_utils.sync_global_devices,
                               "predict_merged",
                               label="predict/merge_barrier")
    finally:
        writer.close(raise_error=False)
        if merger is not None:
            merger.stop()
    if tel is not None:
        # Per-WORKER rate for this worker's shard; the merged view
        # (fmstat over all .p<i> shards) sums examples and seconds
        # across processes, keyed by process index in the metadata.
        # fmlint: disable=R003 -- closes the rate's clock
        dt = time.perf_counter() - t0
        tel.set("predict/examples_per_sec",
                n_local / dt if dt > 0 else 0.0)
        tel.barrier_flush(step=len(out_paths))
    return out_paths
