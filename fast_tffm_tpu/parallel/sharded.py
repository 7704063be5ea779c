"""Mesh-sharded training — the parameter-server replacement (SURVEY.md §7.5).

The reference scales by splitting the embedding table into
``vocabulary_block_num`` row blocks round-robined across TF1 parameter
servers, with workers gathering active rows and pushing sparse Adagrad
updates over gRPC, asynchronously (SURVEY §2 "Distributed backend", §3.2).

The TPU-native design here replaces all of that with SPMD over a
``jax.sharding.Mesh``:

- axes ``("data", "model")``: the batch is sharded over ``data``
  (data parallelism); the table and its Adagrad accumulator are
  **row-sharded over every device** (``P(("data", "model"))``) — the mesh
  *is* the parameter server, and FSDP-style row sharding means the table's
  memory scales with the slice, exactly like adding PS tasks.
- the train step's lookup is told which rows it holds: the host data
  plane orders each batch's unique rows by owning shard
  (data/pipeline.segment_plan), so shard s finds its rows' slots in
  segment s of ``uniq_ids``, gathers them from its own block, and the
  ``[U / n, D]`` pieces are all-gathered; backward, the slot gradients
  are reduce-scattered and each shard applies sparse Adagrad to its own
  block over ``U / n`` slots (``sharded_train_step_body``). A shard
  that walked all U slots masked, as GSPMD partitions the one-device
  step, paid for every slot what a row it holds costs (PERF.md
  section 6, PR 33).
- everything between the lookup's two halves (expand, interaction,
  loss, their backward) and the scoring path are the one-device
  bodies under ``jax.jit`` with shardings: GSPMD partitions them over
  the batch and inserts the collectives over ICI — no hand-written
  transport there, per the scaling-book recipe.
- updates are **synchronous**: every step sees every gradient. This is a
  deliberate semantics upgrade over the reference's lock-free async
  (hogwild) PS updates — a documented divergence (SURVEY §7 hard part #2).

Tensor/pipeline/sequence/expert parallelism are structurally N/A for FMs
(no big dense ops, 2-layer-deep model, unordered feature bags, no MoE —
SURVEY §2 parallelism inventory); the two axes that exist for this model
family, batch-DP and table row sharding (model parallelism for an
embedding model), are both first-class here.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import UNIQ_LADDER_MIN
from fast_tffm_tpu.models.fm import ModelSpec, score_body

# Table rows are sharded across *all* mesh devices — both axes — so table
# memory per chip shrinks linearly with slice size (the PS-scaling analogue).
ROW_SPEC = P(("data", "model"), None)
# The unique-row slots of a mesh train step's feed, one segment per row
# shard (data/pipeline.segment_plan).
SLOT_SPEC = P(("data", "model"))


def make_mesh(devices: Optional[Sequence[jax.Device]] = None,
              model_axis: int = 1) -> Mesh:
    """Build a ("data", "model") mesh over ``devices`` (default: all).

    ``model_axis`` splits devices between the two axes; with the default 1
    the mesh is pure data-parallel (table still row-sharded over all
    devices). Single device -> trivial 1x1 mesh, same code path.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if model_axis <= 0 or n % model_axis:
        raise ValueError(f"model_axis {model_axis} must divide {n} devices")
    # Power-of-two total so the 4096-aligned checkpoint row layout
    # (FmConfig.ckpt_rows) shards evenly; TPU slices are powers of two.
    if n & (n - 1) or n > 4096:
        raise ValueError(
            f"device count {n} must be a power of two <= 4096 so the "
            "4096-aligned table rows (FmConfig.ckpt_rows) shard evenly")
    n_data = n // model_axis
    # A batch's unique rows ship as one segment per ROW shard (data x
    # model; data/pipeline.segment_plan), and every rung of their
    # ladder is a multiple of its smallest, so that one has to cut
    # into that many segments.
    if n > UNIQ_LADDER_MIN:
        raise ValueError(
            f"{n} row shards (data axis {n_data} x model axis "
            f"{model_axis}) do not divide the unique-row ladder's "
            f"smallest rung, {UNIQ_LADDER_MIN} slots: a batch's unique "
            "rows are cut into one segment per row shard")
    # Multi-process: each data-axis row must stay within one process —
    # global_batch concatenates PER-PROCESS local batches along the data
    # axis (make_array_from_process_local_data), so a data row spanning
    # processes would pair different processes' data with one replicated
    # chunk and offset_local_idx into out-of-range unique slots:
    # silently corrupted gathers, not an error.
    if jax.process_count() > 1 and n_data % jax.process_count():
        raise ValueError(
            f"data axis size {n_data} must be a multiple of the process "
            f"count {jax.process_count()}: global_batch assembles one "
            "data-axis block per process")
    grid = np.asarray(devices).reshape(n_data, model_axis)
    return Mesh(grid, ("data", "model"))


def _require_host_dedup(spec: ModelSpec) -> None:
    """Mesh steps consume the host-side unique contract (uniq_ids with
    fixed buckets; global_batch offsets local_idx into the concatenated
    unique axis) — a raw-ids spec here would feed garbage indices.

    Design position, not a gap: the mesh train step's lookup rests on
    the host knowing the batch's distinct rows. It orders them by
    owning shard (data/pipeline.segment_plan), so each shard gathers
    and scatters the U / n slots of rows it holds and the collectives
    carry [U, D] once each way; raw ids would leave every shard B*L
    slots to unique, mask and walk. The fixed-U lockstep protocol
    (multi-process global_batch) needs the static unique budget
    anyway."""
    if spec.dedup == "device":
        raise ValueError(
            "dedup = device is for the plain single-device jit only; "
            "mesh steps require dedup = host. The shipped drivers only "
            "build a mesh when more than one device exists, where "
            "dedup = auto already resolves to host; when driving the "
            "mesh API directly on a one-device environment (where auto "
            "picks device), rebuild the spec with "
            "dataclasses.replace(spec, dedup='host')")


# kernel='pallas' on a mesh: GSPMD has no partitioning rule for a
# pallas_call custom call, so the step bodies wrap the kernel in
# shard_map over the data axis when given the mesh (models/fm._scores,
# ops/pallas_fm.fm_batch_scores_pallas) — each device runs the fused
# kernel on its batch shard, GSPMD keeps owning the gather/scatter
# collectives around it. The mesh is bound into the partial below.


def _layout(mesh: Mesh):
    """The one encoding of the sharding layout: (row, vec, mat, repl) =
    (table rows, per-example vectors, per-example matrices, replicated)."""
    return (NamedSharding(mesh, ROW_SPEC),
            NamedSharding(mesh, P("data")),
            NamedSharding(mesh, P("data", None)),
            NamedSharding(mesh, P()))


def _shardings(mesh: Mesh, with_fields: bool):
    row, vec, mat, repl = _layout(mesh)
    in_sh = [row, row, vec, vec, vec, mat, mat]
    if with_fields:
        in_sh.append(mat)
    out_sh = (row, row, repl, vec)
    return tuple(in_sh), out_sh


def _block_index(mesh: Mesh, block, ids):
    """``ids`` as indices into this shard's ``block`` of table rows
    (inside a shard_map over every mesh axis). A slot whose row another
    shard holds gets the index one past the block: a gather told to
    fill reads zeros there and a scatter drops its update. A segmented
    feed has such slots only as padding: ``pad_id`` names one dead row,
    which one shard holds."""
    rows = block.shape[0]
    local = ids - jax.lax.axis_index(mesh.axis_names) * rows
    return jnp.where((local >= 0) & (local < rows), local, rows)


def _regroup(slots, outer: int, inner: int):
    """``[U, ...]`` slots lying as ``outer`` groups of ``inner`` pieces
    put as ``inner`` groups of ``outer`` pieces. With several feeds
    side by side (multi-process ``global_batch``), each cut into one
    segment per row shard, ``_regroup(x, feeds, shards)`` is
    shard-major, so that ``SLOT_SPEC`` hands shard s its segment of
    every feed, and ``_regroup(x, shards, feeds)`` is back in the order
    ``local_idx`` indexes; the identity for one feed."""
    if outer == 1 or inner == 1:
        return slots
    cut = slots.reshape(outer, inner, -1, *slots.shape[1:])
    return jnp.swapaxes(cut, 0, 1).reshape(slots.shape)


def sharded_train_step_body(spec: ModelSpec, mesh: Mesh, blocks: int,
                            table, acc, labels, weights, uniq_ids,
                            local_idx, vals, fields=None):
    """models.fm.train_step_body on a mesh whose feed is SEGMENTED
    (data/pipeline.segment_plan): ``uniq_ids`` is one segment of
    ``U / n`` slots per row shard, segment s naming rows that shard s
    holds (and ``pad_id`` in its spare slots), so each shard walks its
    own slots and nobody walks all U.

    - ``gather``: every shard gathers its segment from its own block of
      the table (``shard_map``; no mask over U) and the ``[U / n, D]``
      pieces are all-gathered into the ``[U, D]`` that ``expand`` needs
      on every shard.
    - the middle is ``grad_body`` as on one device, the batch cut over
      ``data`` by GSPMD (which all-reduces the slot gradients).
    - ``adagrad``: every shard takes the gradients of its segment and
      runs ``sparse_adagrad_apply`` on its own blocks of table and
      accumulator, over ``U / n`` slots (a gather and one two-operand
      scatter a shard).

    A feed in another order is NOT an error the step can see: a real
    row in a segment whose shard does not hold it reads as zeros and
    its update is dropped. ``DeviceBatch.row_shards`` says how a batch
    was cut, and the train loop checks it (train.StepLoop.place).
    ``blocks`` > 1 (multi-process: ``global_batch`` lays the processes'
    feeds side by side, each segmented) costs two local transposes of
    ``[U, D]``."""
    from fast_tffm_tpu.models.fm import grad_body, sparse_adagrad_apply
    n = int(mesh.devices.size)
    ids = _regroup(uniq_ids, blocks, n)

    def gather(block, ids):
        with jax.named_scope("gather"):
            mine = block.at[_block_index(mesh, block, ids)].get(
                mode="fill", fill_value=0.0)
            return jax.lax.all_gather(mine, mesh.axis_names, tiled=True)

    # check_vma=False: an all_gather's result is typed as varying over
    # its axes, though every shard holds the same [U, D].
    gathered = jax.shard_map(
        gather, mesh=mesh, in_specs=(ROW_SPEC, SLOT_SPEC),
        out_specs=P(), check_vma=False)(table, ids)
    loss, scores, grad = grad_body(
        spec, _regroup(gathered, n, blocks), labels, weights,
        uniq_ids, local_idx, vals, fields, mesh=mesh)

    def apply(block, acc_block, ids, grad):
        # The one scatter over block and accumulator drops a slot
        # indexed past the block (XLA's scatter semantics), and the
        # accumulator's gather in front of it clamps that index to a
        # row whose update is then dropped.
        return sparse_adagrad_apply(
            block, acc_block, _block_index(mesh, block, ids), grad,
            spec.learning_rate)

    table, acc = jax.shard_map(
        apply, mesh=mesh,
        in_specs=(ROW_SPEC, ROW_SPEC, SLOT_SPEC, ROW_SPEC),
        out_specs=(ROW_SPEC, ROW_SPEC))(
            table, acc, ids, _regroup(grad, blocks, n))
    return table, acc, loss, scores


@functools.lru_cache(maxsize=None)
def make_sharded_train_step(spec: ModelSpec, mesh: Mesh,
                            with_fields: Optional[bool] = None,
                            blocks: int = 1):
    """The train step on a mesh (``sharded_train_step_body``): batch
    over ``data``, table rows over the whole mesh, loss replicated;
    the feed is segmented by row shard, ``blocks`` feeds side by side.
    Cached per (spec, mesh, blocks)."""
    if with_fields is None:
        with_fields = spec.model_type == "ffm"
    _require_host_dedup(spec)
    in_sh, out_sh = _shardings(mesh, with_fields)
    fn = functools.partial(sharded_train_step_body, spec, mesh, blocks)
    fn.__name__ = "fm_sharded_train_step"  # module/trace name (fm._bind)
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(0, 1))

    # pjit rejects kwargs when in_shardings is set; keep the kwargs-friendly
    # surface of make_train_step via a thin positional adapter.
    def step(table, acc, labels, weights, uniq_ids, local_idx, vals,
             fields=None):
        args = (table, acc, labels, weights, uniq_ids, local_idx, vals)
        if with_fields:
            args += (fields,)
        return jitted(*args)

    return step


@functools.lru_cache(maxsize=None)
def make_sharded_score_fn(spec: ModelSpec, mesh: Mesh,
                          with_fields: Optional[bool] = None):
    """Sharded inference: row-sharded table in, batch-sharded scores out."""
    if with_fields is None:
        with_fields = spec.model_type == "ffm"
    _require_host_dedup(spec)
    row, vec, mat, _ = _layout(mesh)
    in_sh = [row, vec, mat, mat] + ([mat] if with_fields else [])

    fn = functools.partial(score_body, spec, mesh=mesh)
    fn.__name__ = "fm_sharded_score"
    jitted = jax.jit(fn, in_shardings=tuple(in_sh), out_shardings=vec)

    def score(table, uniq_ids, local_idx, vals, fields=None):
        args = (table, uniq_ids, local_idx, vals)
        if with_fields:
            args += (fields,)
        return jitted(*args)

    return score


def padded_num_rows(cfg: FmConfig, mesh: Mesh) -> int:
    """Table rows on the mesh == the checkpoint row layout
    (``cfg.ckpt_rows``, a fixed 4096 multiple): one shape for runtime,
    save, and restore means checkpoints round-trip row-sharded on any
    topology. The extra rows sit past ``pad_id`` so no id can ever
    gather or update them; exports slice them off via
    ``export_npz(..., vocabulary_size=...)``."""
    n = int(mesh.devices.size)
    rows = cfg.ckpt_rows
    assert rows % n == 0, (rows, n)  # make_mesh enforces pow2 <= 4096
    return rows


def init_sharded_state(cfg: FmConfig, mesh: Mesh, seed: int = 0
                       ) -> Tuple[jax.Array, jax.Array]:
    """Initialise (table, accumulator) directly sharded: jit with
    out_shardings makes every device materialise only its own row shard —
    a 10^9-row table never exists on one host (SURVEY §7 hard part #3).

    Row values match init_table() exactly for the first ``cfg.num_rows``
    rows (same key, same distribution; the pad tail is appended, not
    interleaved), so single-device and sharded runs are comparable.
    """
    row = NamedSharding(mesh, ROW_SPEC)
    n_rows = padded_num_rows(cfg, mesh)
    shape = (cfg.num_rows, cfg.row_dim)

    def init(key):
        t = jax.random.uniform(key, shape, dtype=jnp.float32,
                               minval=-cfg.init_value_range,
                               maxval=cfg.init_value_range)
        t = t.at[cfg.num_rows - 1:].set(0.0)
        pad = jnp.zeros((n_rows - cfg.num_rows, cfg.row_dim), jnp.float32)
        a = jnp.full((n_rows, cfg.row_dim), cfg.adagrad_init, jnp.float32)
        return jnp.concatenate([t, pad], axis=0), a

    return jax.jit(init, out_shardings=(row, row))(jax.random.PRNGKey(seed))


def place_table(cfg: FmConfig, mesh: Mesh, table) -> jax.Array:
    """Lift a host/logical table onto the mesh row-sharded, appending
    the dead pad tail up to the [ckpt_rows, D] runtime layout. The
    restore path doesn't need this (checkpoints restore sharded
    directly); it serves callers holding a dense table (tests, external
    .npz imports)."""
    row = NamedSharding(mesh, ROW_SPEC)
    n_pad = padded_num_rows(cfg, mesh) - int(np.shape(table)[0])

    def lift(t):
        pad = jnp.zeros((n_pad, cfg.row_dim), jnp.float32)
        return jnp.concatenate([t.astype(jnp.float32), pad], axis=0)

    if not isinstance(table, jax.Array):
        table = jnp.asarray(np.asarray(table), jnp.float32)
    return jax.jit(lift, out_shardings=row)(table)


def global_batch(mesh: Mesh, local_uniq_size: int, **arrays) -> dict:
    """Assemble per-process local batch arrays into global sharded arrays
    for multi-process SPMD training.

    Every process calls this with its own (identically-shaped, see
    pipeline ``fixed_shape``) local batch; the result is one global
    array per input whose global shape concatenates the process-local
    batches along dim 0, placed per the mesh's data-axis sharding.

    ``local_idx`` needs care: each process's values index its *local*
    unique-id block, so they are offset by ``process_index *
    local_uniq_size`` to index the concatenated global unique axis (each
    process's pad slot lands inside its own block, which still holds
    ``pad_id``, so padding semantics survive concatenation).

    Semantic note vs single-process: an id occurring on several
    processes occupies one unique slot per process, so its Adagrad
    accumulator gains sum-of-squared per-process grads (not the square
    of the summed grad) and its L2 reg is counted once per process.
    This matches per-row-touch semantics of the reference's PS (each
    worker pushed its own IndexedSlices update; SURVEY §3.2) and is the
    documented multi-host divergence, far smaller than the reference's
    async staleness.
    """
    p = jax.process_index()
    _, vec, mat, _ = _layout(mesh)
    out = {}
    for name, arr in arrays.items():
        if arr is None:
            continue
        arr = np.asarray(arr)
        if name == "local_idx":
            arr = offset_local_idx(arr, p, local_uniq_size)
        sh = vec if arr.ndim == 1 else mat
        out[name] = jax.make_array_from_process_local_data(sh, arr)
    return out


def offset_local_idx(local_idx: np.ndarray, process_index: int,
                     local_uniq_size: int) -> np.ndarray:
    """The multi-process unique-axis index math, factored out of
    global_batch so the driver's dryrun can simulate P logical processes'
    assembly through the REAL function (this offset is where the
    index bugs would live): process p's local_idx values index its own
    unique block, shifted into the concatenated global unique axis."""
    return np.asarray(local_idx) + np.int32(process_index
                                            * local_uniq_size)


def local_rows(global_arr: jax.Array) -> np.ndarray:
    """This process's rows of a ``P('data')``-sharded global dim-0 array
    (the output side of ``global_batch``): addressable shards ordered by
    index range and deduplicated — with ``model_axis > 1`` the vector is
    replicated along the model axis, so a process can hold several
    shards covering the SAME range; keeping one per range is required or
    the concat doubles the slice. Used by distributed validation and
    multi-process predict to recover the local batch's slice."""
    seen = set()
    pieces = []
    for s in sorted(global_arr.addressable_shards,
                    key=lambda s: s.index[0].start or 0):
        rng_key = (s.index[0].start, s.index[0].stop)
        if rng_key in seen:
            continue
        seen.add(rng_key)
        pieces.append(np.asarray(s.data))
    return np.concatenate(pieces)


# Batches agreed on per lockstep round: one flag allgather (a
# synchronizing host collective) covers this many score programs, and
# their device->host score fetches defer to the round's end so fetch i
# overlaps programs i+1.. still in flight. Device cost per round is
# WINDOW batches' args + [B_global] score vectors in flight (a few MB).
LOCKSTEP_WINDOW = 8


def lockstep_score_batches(cfg: FmConfig, it, mesh: Mesh, score_fn,
                           table, uniq_bucket: int,
                           max_batches: Optional[int] = None,
                           preempt=None):
    """Drive a per-process batch iterator through a mesh score fn in
    LOCKSTEP: every score call is a collective program, so a process
    whose shard ran dry (or hit ``max_batches`` real batches) feeds
    all-padding filler until every process is done. Yields
    ``(batch, local_scores)`` per local iterator batch — the single
    implementation of the deadlock-sensitive protocol shared by
    distributed validation and multi-process predict (a diverging copy
    here hangs a cluster, not a test).

    Round-5 windowing: processes agree once per LOCKSTEP_WINDOW batches
    (an allgather of per-process window fill) instead of once per batch
    — every round each process runs max(fills) collective programs,
    padding its own tail with fillers, so programs stay matched while
    the per-batch host-sync collective and the per-batch blocking score
    fetch both amortize across the window.

    ``preempt`` (zero-arg callable, may be None): a per-process
    preemption flag piggybacked on the fill allgather. A SIGTERM lands
    on ONE worker; without this the signalled worker alone would stop
    feeding collectives mid-sweep and desync the lockstep group — with
    it, every process sees the flag in the SAME gathered result and
    all stop together at the window boundary, before dispatching any
    of that window's programs (the sweep ends early; train()'s
    step-boundary save path then runs on every worker)."""
    import time as _time
    from jax.experimental import multihost_utils
    from fast_tffm_tpu.data.pipeline import empty_batch
    from fast_tffm_tpu.models.fm import batch_args
    from fast_tffm_tpu.obs.memory import LEDGER
    from fast_tffm_tpu.obs.telemetry import active
    from fast_tffm_tpu.obs.trace import anatomy_on, span
    from fast_tffm_tpu.parallel.liveness import guarded_collective
    tel = active()  # per-worker lockstep telemetry (obs/): each
    # process counts its own rounds/fillers/examples into its own
    # sink shard; fmstat merges the streams keyed by process index
    anat = anatomy_on()  # stamp window ids as span join keys
    wid = -1  # lockstep window id: every rank increments it in the
    # same barrier'd order (the window allgather IS the barrier), so
    # the same wid names the same window on every rank — the join key
    # fmtrace --anatomy aligns per-rank clocks on (obs/anatomy.py)
    wid_prev = -1  # the window whose deferred scores _drain fetches
    n_real = 0
    filler = None
    filler_gargs = None  # device assembly of the all-padding batch is
    # identical every filler step — ship it once, not once per step
    # (H2D bytes are the cost every step pays)
    pending_prev: list = []  # previous window's dispatched scores,
    # fetched AFTER the next window is dispatched (see _drain below)

    def _drain(pending, fetch_wid=-1):
        """Window-deferred bulk fetch: every queued score vector of a
        PREVIOUS window materializes host-side here, after the next
        window's programs were already dispatched — so the D2H drain
        overlaps that window's device compute AND the following fill's
        host parse, instead of serializing between them (the cross-file
        predict sweep feeds one continuous stream through this loop;
        without the deferral every window boundary stalled on the
        fetch). One span for the whole drain. Guarded: fetching a
        score whose producing program can never complete (dead peer
        mid-window) blocks exactly like the dispatch would."""
        if not pending:
            return []
        ids = {"wid": fetch_wid} if (anat and fetch_wid >= 0) else {}
        t_fetch = _time.perf_counter()
        with span("lockstep/score_fetch", batches=len(pending), **ids):
            # collective=False: this is a LOCAL device wait (it runs
            # only when this rank's pending window is non-empty, a
            # per-rank count) — it rides the guard for the deadline,
            # not the protocol trace.
            out = guarded_collective(
                lambda: [(batch, local_rows(score))
                         for batch, score in pending],
                label="lockstep/score_fetch", collective=False)
        if tel is not None:
            tel.count("lockstep/fetch_seconds",
                      _time.perf_counter() - t_fetch)
        return out

    while True:
        window = []
        wid += 1
        ids = {"wid": wid} if anat else {}
        t_fill = _time.perf_counter()
        with span("lockstep/window_fill", **ids):
            while len(window) < LOCKSTEP_WINDOW:
                if max_batches and n_real + len(window) >= max_batches:
                    break
                b = next(it, None)
                if b is None:
                    break
                window.append(b)
        # The silent multi-worker wait: a peer still filling (or hung)
        # parks everyone here. The span makes the wait VISIBLE on the
        # timeline; the deadline guard (parallel/liveness.py) bounds
        # the wait — a dead peer raises WorkerLostError naming it
        # instead of parking the cluster forever.
        t_ag = _time.perf_counter()
        with span("lockstep/allgather", window=len(window), **ids):
            flags = guarded_collective(
                multihost_utils.process_allgather,
                np.asarray([len(window),
                            1 if (preempt is not None and preempt())
                            else 0]),
                label="lockstep/window_fill")
        flags = np.asarray(flags).reshape(-1, 2)
        if tel is not None:
            tel.count("lockstep/allgather_seconds",
                      _time.perf_counter() - t_ag)
        if flags[:, 1].any():
            # Coordinated preemption: every process computed the SAME
            # gathered flags, so all return here together — no program
            # of this window was dispatched, collectives stay matched.
            # The previous window's deferred scores drain first (local
            # device_get, no collective): they completed, so they are
            # yielded, not re-done after resume.
            for batch, local in _drain(pending_prev, wid_prev):
                yield batch, local
            if tel is not None:
                tel.count("lockstep/preempted_windows")
            LEDGER.release("lockstep_window")
            return
        rounds = int(flags[:, 0].max())
        if tel is not None:
            tel.heartbeat()  # a completed collective is progress
        if tel is not None and rounds:
            tel.count("lockstep/windows")
            # Collective programs this round == the window max across
            # workers; real + filler always sums to it, so the three
            # counters cross-check.
            tel.count("lockstep/programs", rounds)
            tel.count("lockstep/real_batches", len(window))
            # Filler programs this worker runs because a PEER's shard
            # is longer — the load-imbalance signal per worker.
            tel.count("lockstep/filler_batches", rounds - len(window))
            tel.count("lockstep/window_fill_seconds",
                      _time.perf_counter() - t_fill)
        if rounds == 0:
            # Every process ran dry in the same round: drain the last
            # deferred window and end the sweep.
            for batch, local in _drain(pending_prev, wid_prev):
                yield batch, local
            LEDGER.release("lockstep_window")
            return
        pending = []
        t_disp = _time.perf_counter()
        with span("lockstep/score_dispatch", batches=rounds, **ids):
            for i in range(rounds):
                if i < len(window):
                    batch = window[i]
                    args = batch_args(batch)
                    args.pop("labels"), args.pop("weights")
                    gargs = global_batch(mesh, len(batch.uniq_ids),
                                         **args)
                else:
                    if filler_gargs is None:
                        filler = empty_batch(cfg,
                                             uniq_bucket=uniq_bucket)
                        args = batch_args(filler)
                        args.pop("labels"), args.pop("weights")
                        filler_gargs = global_batch(
                            mesh, len(filler.uniq_ids), **args)
                    gargs = filler_gargs
                # Collective program dispatch under the deadline
                # guard: a dead peer parks the dispatch inside the
                # program's own collectives, out of reach of the
                # host-allgather guard above.
                score = guarded_collective(
                    score_fn, table,
                    label="lockstep/score_dispatch", **gargs)
                if i < len(window):
                    pending.append((batch, score))
        if tel is not None:
            tel.count("lockstep/dispatch_seconds",
                      _time.perf_counter() - t_disp)
        n_real += len(window)
        if tel is not None:
            tel.count("lockstep/examples",
                      sum(b.num_real for b in window))
        # Drain the PREVIOUS window (this window's programs are already
        # in flight, so its compute overlaps this D2H); this window's
        # scores stay queued on device until the next round — at most
        # one extra window of [B_global] f32 vectors held in HBM.
        fetched = _drain(pending_prev, wid_prev)
        pending_prev = pending
        wid_prev = wid
        # Ledger (obs/memory.py): the deferred window's [B_global]
        # score vectors held in HBM until the next round's drain —
        # .nbytes is host metadata, upserted once per window.
        LEDGER.register("lockstep_window",
                        sum(s.nbytes for _, s in pending))
        for batch, local in fetched:
            # This process's rows of the global [B_global] score vector
            # are exactly its local batch (global_batch concatenates
            # local batches in process order over process-contiguous
            # data-axis devices); local_rows dedups model-axis replicas.
            assert len(local) == len(batch.labels), (
                f"local score slice {len(local)} != local batch "
                f"{len(batch.labels)}")
            yield batch, local


def evaluate_distributed(cfg: FmConfig, table: jax.Array, files, mesh,
                         shard_index: int, num_shards: int,
                         uniq_bucket: int = 0,
                         max_batches: Optional[int] = None,
                         weight_files=(),
                         bad_lines=None,
                         preempt=None, collect=None) -> Tuple[float, int]:
    """Multi-process sharded AUC: every process scores its own input
    shard through the mesh score fn in lockstep (the shared
    lockstep_score_batches protocol), then the per-process binned-AUC
    histograms are allgathered and merged — no table or score set ever
    materializes on one host. Returns the same (auc, n_examples) on
    every process. ``max_batches`` caps real batches per input shard.

    ``uniq_bucket``: pass the caller's once-probed value; 0 re-probes
    (deterministic — same bytes on every process, so all agree without
    a collective). ``preempt`` rides the lockstep fill allgather
    (parallel/sharded.py): a SIGTERM on one worker stops the sweep on
    EVERY worker at the same window boundary — the partial histograms
    still merge below (everyone exits the loop together, so the final
    allgather stays matched). ``collect`` (obs/quality.QualityStats):
    fed the per-batch local scores like the AUC update, and its four
    sums ride INSIDE the existing histogram-merge allgather payload —
    the quality loop adds no collective and no device fetch; after the
    merge the collector holds the job-wide totals. Its presence is
    config-deterministic, so every process ships the same payload
    width."""
    from jax.experimental import multihost_utils
    from fast_tffm_tpu.data.pipeline import (VALIDATION_PLANE,
                                             batch_iterator,
                                             probe_uniq_bucket)
    from fast_tffm_tpu.metrics import StreamingAUC
    from fast_tffm_tpu.parallel.liveness import guarded_collective
    spec = ModelSpec.from_config(cfg)
    score_fn = make_sharded_score_fn(spec, mesh)
    auc = StreamingAUC()
    n = 0
    ub = uniq_bucket or cfg.uniq_bucket or probe_uniq_bucket(cfg, files)
    it = batch_iterator(cfg, files, training=False, epochs=1,
                        weight_files=weight_files,
                        shard_index=shard_index, num_shards=num_shards,
                        fixed_shape=True, uniq_bucket=ub,
                        bad_lines=bad_lines, counters=VALIDATION_PLANE)
    for batch, local in lockstep_score_batches(cfg, it, mesh, score_fn,
                                               table, ub,
                                               max_batches=max_batches,
                                               preempt=preempt):
        nr = batch.num_real
        auc.update(local[:nr], batch.labels[:nr], batch.weights[:nr])
        if collect is not None:
            collect.update(local[:nr], batch.labels[:nr],
                           batch.weights[:nr])
        n += batch.num_real
    # process_allgather device_puts its payload and this runtime never
    # enables x64, so float64 histograms (and int64 counts) silently
    # downcast to 32 bits in transit — bins past 2^24 examples lose
    # integer precision and a per-process n past 2^31 wraps, both real
    # at the Criteo-1TB north star. Ship every f64 value as a (hi, lo)
    # float32 pair (lo = v - f64(f32(v))): hi + lo recovers ~48 bits
    # exactly, enough for any count this side of 10^14.
    bins = auc.num_bins
    # The quality collector's four sums ride the same payload (its
    # presence is config-driven, so every process agrees on the
    # width) — the publish-gate quality loop adds zero collectives.
    extra = (collect.sums() if collect is not None
             else np.zeros(0, np.float64))
    payload = np.concatenate([auc.pos, auc.neg,
                              np.asarray([n], np.float64), extra])
    width = 2 * bins + 1 + extra.shape[0]
    hi = payload.astype(np.float32)
    lo = (payload - hi.astype(np.float64)).astype(np.float32)
    gathered = guarded_collective(
        multihost_utils.process_allgather,
        np.stack([hi, lo]),
        label="validation/auc_merge")          # [P, 2, width] f32
    gathered = gathered.reshape(-1, 2, width)
    vals = (gathered[:, 0, :].astype(np.float64)
            + gathered[:, 1, :].astype(np.float64)).sum(axis=0)
    merged = StreamingAUC(num_bins=bins)
    merged.pos[:] = vals[:bins]
    merged.neg[:] = vals[bins:2 * bins]
    n_total = int(round(vals[2 * bins]))
    if collect is not None:
        collect.load_sums(vals[2 * bins + 1:])
    return merged.result(), n_total


def shard_batch(mesh: Mesh, **arrays) -> dict:
    """Place host batch arrays with their mesh shardings (keeps per-step
    host->device transfers going straight to the right shards)."""
    _, vec, mat, _ = _layout(mesh)
    n_data = mesh.shape["data"]
    out = {}
    for name, arr in arrays.items():
        if arr is None:
            continue
        if np.shape(arr)[0] % n_data:
            raise ValueError(
                f"batch array {name!r} dim 0 ({np.shape(arr)[0]}) must be "
                f"divisible by the mesh data axis ({n_data}); pick a "
                f"batch_size that is a multiple of it")
        sh = vec if np.ndim(arr) == 1 else mat
        out[name] = jax.device_put(arr, sh)
    return out
