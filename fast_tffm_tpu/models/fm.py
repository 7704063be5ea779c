"""Model assembly: table init, scoring, loss, and the jitted train step.

This is the analogue of the reference's in-driver graph build (SURVEY.md
§3.1): gather unique rows -> scorer -> loss + reg -> Adagrad sparse apply.
The whole step is one ``jax.jit`` so, like the reference's single
``sess.run`` per step, Python touches nothing per-step but the loop.

Differences from the reference, by design (SURVEY §7):
- updates are synchronous (no async PS staleness),
- batches are fixed-shape/bucketed, deduplicated on the host,
- the optimizer is a hand-rolled *sparse* Adagrad: full-size accumulator
  (row-sharded like the table in parallel/), but per-step work touches
  only the batch's unique rows — the equivalent of TF's
  ``sparse_apply_adagrad`` on IndexedSlices (SURVEY §3.1).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Format, Layout

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import DeviceBatch
from fast_tffm_tpu.ops.interaction import (batch_reg, ffm_batch_scores,
                                           fm_batch_scores, gather_rows)
from fast_tffm_tpu.ops.pair_scatter import pair_scatter_add
from fast_tffm_tpu.compile_cache import uncached
from fast_tffm_tpu.obs.telemetry import active
from fast_tffm_tpu.utils.logging import get_logger


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """The static (hashable) subset of FmConfig the jitted step closes
    over; one compiled executable per (spec, batch shape bucket)."""
    model_type: str
    order: int
    factor_num: int
    field_num: int
    vocabulary_size: int
    loss_type: str
    factor_lambda: float
    bias_lambda: float
    learning_rate: float
    kernel: str = "xla"
    # "host": the pipeline dedups ids and ships (uniq_ids[U],
    # local_idx), U fitted to the batch's distinct rows
    # (data/pipeline._uniq_ladder). "device": the pipeline ships raw
    # ids [B, L]; a scorer gathers them directly, a train step runs
    # jnp.unique on device over U = B*L + 1 slots. Only the
    # single-device jit paths support "device" (mesh/offload/
    # multi-process need the host-side unique contract). Resolution of
    # "auto": from_config.
    dedup: str = "host"

    @classmethod
    def from_config(cls, cfg: FmConfig) -> "ModelSpec":
        kernel = cfg.kernel
        if kernel == "pallas" and (cfg.model_type == "ffm"
                                   or cfg.order != 2):
            # The fused Pallas kernel covers 2nd-order FM only; an
            # explicit `kernel = pallas` on FFM/order>2 would otherwise
            # silently run XLA (the same silent-config-betrayal pattern
            # as the old mesh coercion). Warn and make the spec honest.
            import warnings
            warnings.warn(
                f"kernel = pallas is only implemented for 2nd-order FM; "
                f"model_type={cfg.model_type!r} order={cfg.order} runs "
                "the XLA scorer instead")
            kernel = "xla"
        if kernel == "auto":
            # Where the fused Pallas kernel applies (2nd-order FM on a
            # native-TPU backend), 'auto' SURVIVES into the spec and
            # _scores resolves it per bucket width at trace time from
            # the (L, dedup) matrix (ops/kernel_choice.py). Interpret
            # mode off-TPU is a correctness path for tests, not a fast
            # path, so auto resolves to XLA there; regime_line puts
            # the outcome in every run's log.
            if not (cfg.model_type == "fm" and cfg.order == 2
                    and jax.default_backend() == "tpu"):
                kernel = "xla"
        dedup = cfg.dedup
        if dedup == "auto":
            # The host unique, whatever the programs are for. Mesh,
            # offload and multi-process rely on its contract. On the
            # plain single-device jit both uses pay for every slot
            # they walk, pad slot or real row (a gather 29 ns, adagrad's
            # scatter 160 ns on the v5e; PERF.md section 5), and the
            # host unique's U is the ladder rung of the batch's
            # distinct rows, not B*L raw cells (a scorer) or the device
            # unique's B*L + 1 (a train step). The one caller that
            # ships raw ids, serve, says so itself
            # (scoring.CompiledScorer(dedup="device")).
            dedup = "host"
        return cls(model_type=cfg.model_type, order=cfg.order,
                   factor_num=cfg.factor_num, field_num=cfg.field_num,
                   vocabulary_size=cfg.vocabulary_size,
                   loss_type=cfg.loss_type, factor_lambda=cfg.factor_lambda,
                   bias_lambda=cfg.bias_lambda,
                   learning_rate=cfg.learning_rate, kernel=kernel,
                   dedup=dedup)

    @property
    def row_dim(self) -> int:
        if self.model_type == "ffm":
            return self.factor_num * self.field_num + 1
        return self.factor_num + 1


def init_table(cfg: FmConfig, seed: int = 0) -> jax.Array:
    """[vocab+1, D] uniform(-init_value_range, +init_value_range) — the
    reference's init (SURVEY §2 "Model parameters") — with the final
    padding row zeroed (it must stay dead)."""
    key = jax.random.PRNGKey(seed)
    t = jax.random.uniform(
        key, (cfg.num_rows, cfg.row_dim), dtype=jnp.float32,
        minval=-cfg.init_value_range, maxval=cfg.init_value_range)
    return t.at[-1].set(0.0)


def init_accumulator(cfg: FmConfig) -> jax.Array:
    """Adagrad accumulator, full table size, constant-initialised (TF
    Adagrad's initial_accumulator_value; cfg.adagrad_init)."""
    return jnp.full((cfg.num_rows, cfg.row_dim), cfg.adagrad_init,
                    dtype=jnp.float32)


def resolved_kernel(spec: ModelSpec, L: int) -> str:
    """The kernel a (spec, bucket-width-L) executable actually runs —
    the ONE resolution of ``kernel = auto`` (trace-time, per bucket:
    the bucketed pipeline compiles one executable per (spec, L), so
    each bucket independently gets the kernel the measured matrix says
    wins at its width; ops/kernel_choice.py). Shared by _scores and by
    the start-up log's regime line so the line can't drift from the
    dispatch."""
    if spec.model_type == "ffm":
        return "xla"  # field-bucketed XLA scorer; no Pallas FFM kernel
    kernel = spec.kernel
    if kernel == "auto":
        from fast_tffm_tpu.ops.kernel_choice import auto_kernel
        kernel = auto_kernel(spec.dedup, L)
    if kernel == "pallas" and spec.order != 2:
        kernel = "xla"  # from_config warns; direct specs stay honest
    return kernel


def regime_line(spec: ModelSpec, cfg: FmConfig) -> str:
    """The resolved (backend, dedup, kernel per bucket) of one spec —
    train, predict and serve each log it once at start-up, so an
    ``auto`` that resolved away from the chip's path (XLA off-TPU, host
    dedup on a mesh) is on record and never silent."""
    kernels = ",".join(f"L{L}:{resolved_kernel(spec, L)}"
                       for L in cfg.bucket_ladder)
    return (f"backend={jax.default_backend()} "
            f"devices={jax.device_count()} dedup={spec.dedup} "
            f"kernel={kernels} (configured: kernel = {cfg.kernel}, "
            f"dedup = {cfg.dedup})")


def _scores(spec: ModelSpec, gathered: jax.Array, local_idx: jax.Array,
            vals: jax.Array, fields: Optional[jax.Array],
            mesh=None) -> jax.Array:
    """``mesh`` (sharded paths only) lets the Pallas kernel run under
    shard_map over the data axis — GSPMD cannot partition a pallas_call
    itself (parallel/sharded.py binds it; None = single-device jit)."""
    if spec.model_type == "ffm":
        return ffm_batch_scores(gathered, spec.field_num, local_idx,
                                fields, vals)
    if resolved_kernel(spec, vals.shape[-1]) == "pallas":
        from fast_tffm_tpu.ops.pallas_fm import fm_batch_scores_pallas
        return fm_batch_scores_pallas(gathered, local_idx, vals, mesh=mesh)
    return fm_batch_scores(gathered, local_idx, vals, order=spec.order)


def _per_example_loss(spec: ModelSpec, scores: jax.Array,
                      labels: jax.Array) -> jax.Array:
    if spec.loss_type == "logistic":
        # Stable sigmoid cross-entropy with {0,1} labels (the reference's
        # classification loss; SURVEY §2 "Loss + optimizer").
        return (jnp.maximum(scores, 0.0) - scores * labels
                + jnp.log1p(jnp.exp(-jnp.abs(scores))))
    return jnp.square(scores - labels)


def loss_and_scores(spec: ModelSpec, gathered: jax.Array,
                    labels: jax.Array, weights: jax.Array,
                    uniq_ids: jax.Array, local_idx: jax.Array,
                    vals: jax.Array, fields: Optional[jax.Array],
                    mesh=None) -> Tuple[jax.Array, jax.Array]:
    """Weighted-mean data loss + batch-active L2 reg. Zero-weight padding
    examples drop out of both value and gradient."""
    scores = _scores(spec, gathered, local_idx, vals, fields, mesh=mesh)
    with jax.named_scope("loss"):
        return _loss_of_scores(spec, scores, gathered, labels, weights,
                               uniq_ids), scores


def _loss_of_scores(spec: ModelSpec, scores, gathered, labels, weights,
                    uniq_ids) -> jax.Array:
    per = _per_example_loss(spec, scores, labels)
    # Exact-zero guard ONLY for the all-padding filler batch (sum(w)=0,
    # numerator 0 — the distributed lockstep's zero-weight filler). Any
    # nonzero total weight — however tiny (fractional weight_files) —
    # divides exactly, preserving the weighted-mean contract. DOUBLE
    # where, not a subnormal floor: TPUs flush f32 subnormals to zero,
    # so max(0, 1e-38) would still divide 0/0 and divide's VJP would
    # inject NaN into the table gradient even though the forward value
    # is masked (and CPU tests can't see it — CPUs keep subnormals).
    wsum = weights.sum()
    nonzero = wsum > 0.0
    den = jnp.where(nonzero, wsum, 1.0)
    data_loss = jnp.where(nonzero, (per * weights).sum() / den, 0.0)
    reg = batch_reg(gathered, uniq_ids, spec.vocabulary_size,
                    spec.factor_lambda, spec.bias_lambda)
    return data_loss + reg


def _device_dedup(spec: ModelSpec, raw_idx: jax.Array):
    """On-device unique for dedup='device' batches: ``raw_idx`` holds
    RAW feature ids [B, L] (pad cells = pad_id). U = B*L + 1 is static
    and >= any possible unique count + the pad slot, so jnp.unique's
    size-truncation can never drop an id. pad_id is the largest value
    (ids < vocab) so it sorts into the tail next to the fill slots —
    the same "padding slots hold pad_id" invariant the host path keeps.
    """
    with jax.named_scope("dedup"):
        flat = raw_idx.ravel()
        uniq, inv = jnp.unique(flat, size=flat.shape[0] + 1,
                               fill_value=spec.vocabulary_size,
                               return_inverse=True)
        return (uniq.astype(jnp.int32),
                inv.reshape(raw_idx.shape).astype(jnp.int32))


def sparse_adagrad_apply(table: jax.Array, acc: jax.Array,
                         uniq_ids: jax.Array, grad_rows: jax.Array,
                         lr: float) -> Tuple[jax.Array, jax.Array]:
    """acc[rows] += g²; table[rows] -= lr * g / sqrt(acc[rows]).

    Two visits of the U slots where there were three (PR 38): the
    accumulator's rows are gathered, the float32 maths is the same
    three operations in the same order (``a + g²``, ``rsqrt``,
    ``-lr * g * ...``), and ONE two-operand scatter
    (``ops/pair_scatter.py``) adds the update to the table's rows and
    ``g²`` to the accumulator's in the same walk over the slots.

    ``uniq_ids`` are unique except padding slots, which all name the
    dead row with gradient rows already masked to zero: their adds are
    ``w + (-lr * 0 * rsqrt(a))`` and ``a + 0``, identities in whatever
    order the scatter applies them, so the dense-Adagrad semantics on
    touched rows are exact. An index past the block (the mesh's slots
    whose row another shard holds, ``parallel/sharded._block_index``)
    is dropped by the scatter; the gather in front clamps it to a row
    whose update is then dropped with it.
    """
    with jax.named_scope("adagrad"):
        sq = jnp.square(grad_rows)
        upd = -lr * grad_rows * lax.rsqrt(acc[uniq_ids] + sq)
        return pair_scatter_add(table, acc, uniq_ids, upd, sq)


def grad_body(spec: ModelSpec, gathered, labels, weights, uniq_ids,
              local_idx, vals, fields=None, *, mesh=None):
    """The device-side compute between a lookup backend's ``gather`` and
    ``apply_grad`` (lookup.py): loss/scores plus gradients w.r.t. the
    gathered ``[U, D]`` rows, padding rows masked to zero.

    This is the seam the reference gets from TF autodiff stopping at the
    embedding_lookup boundary (SURVEY §3.2: workers compute IndexedSlices
    row gradients; where the rows *live* — PS task, device shard, host
    RAM — is the backend's business). ``train_step_body`` composes it
    with the in-jit device backend; HostOffloadLookup composes it with a
    host-RAM store.
    """
    def loss_fn(g):
        return loss_and_scores(spec, g, labels, weights, uniq_ids,
                               local_idx, vals, fields, mesh=mesh)

    (loss, scores), grad = jax.value_and_grad(
        loss_fn, has_aux=True)(gathered)
    live = (uniq_ids < spec.vocabulary_size).astype(grad.dtype)[:, None]
    return loss, scores, grad * live


def _bind(body, spec: ModelSpec, name: str):
    """``functools.partial(body, spec)`` under a stable name: jax names
    the compiled module, its IR dump and its profiler events after the
    function, and a bare partial is ``jit__unknown`` in all three."""
    fn = functools.partial(body, spec)
    fn.__name__ = name
    return fn


@functools.lru_cache(maxsize=None)
def make_grad_fn(spec: ModelSpec):
    """Jitted grad_body: (gathered, labels, weights, uniq_ids, local_idx,
    vals[, fields]) -> (loss, scores, grad_rows). The offload train path:
    only [U, D] rows and their gradients ever cross the host boundary."""
    return jax.jit(_bind(grad_body, spec, "fm_grad"))


def train_step_body(spec: ModelSpec, table, acc, labels, weights, uniq_ids,
                    local_idx, vals, fields=None, *, mesh=None):
    """One full training step (gather -> loss -> grad -> sparse Adagrad).

    Pure function of arrays; jitted directly by make_train_step and jitted
    with mesh shardings by parallel/sharded.py — single source of truth for
    the step semantics either way. The gather + apply pair here IS the
    device lookup backend, fused into the jit (lookup.py documents the
    seam; grad_body is the shared middle).

    With ``spec.dedup == 'device'`` (an explicit ``dedup = device``;
    ``auto`` never resolves a train step to it) the caller ships RAW
    ids in ``local_idx`` and ``uniq_ids=None``; the unique pass runs
    here on device (_device_dedup) instead of on the host.
    """
    if spec.dedup == "device":
        if uniq_ids is not None:  # trace-time: batches must be raw-ids
            raise ValueError(
                "dedup=device step got a host-deduped batch (uniq_ids is "
                "set); build batches with raw_ids=True — slot indices "
                "read as feature ids would silently corrupt training")
        uniq_ids, local_idx = _device_dedup(spec, local_idx)
    gathered = gather_rows(table, uniq_ids)
    loss, scores, grad = grad_body(spec, gathered, labels, weights,
                                   uniq_ids, local_idx, vals, fields,
                                   mesh=mesh)
    table, acc = sparse_adagrad_apply(table, acc, uniq_ids, grad,
                                      spec.learning_rate)
    return table, acc, loss, scores


class TrainStep:
    """The one-device train step, compiled per batch-shape bucket, with
    the table and the accumulator held in the layout the step's own
    gather and scatters work in.

    The runtime's default layout of a long, narrow array and the one a
    gather or a scatter-add takes its operand in can differ (on the
    v5e: FFM's [2^23+1, 89] arrives rows-minor, the step works
    row-contiguous), and a step compiled for the default then copies
    the whole table and accumulator in and out, every step. So the
    FIRST compile leaves the layout of the two donated arguments and
    of the two state results to the compiler (``Layout.AUTO``) and
    reads back what it chose; every later bucket is compiled with that
    layout pinned on both sides. A state that arrives in another
    layout is re-laid once (counter ``train/state_relayouts``); the
    step's results come back in the layout and feed the next call as
    they are. Where the compiler keeps the default (FM's 17 columns on
    the v5e, any shape on the CPU) nothing is re-laid and the program
    is the one a plain ``jax.jit`` builds. Other readers of the state
    (scorers, gathers, checkpoint saves) need nothing: jax compiles a
    function that names no layout for the layout its committed
    argument has (``_Relabel`` keeps that true)."""

    def __init__(self, spec: ModelSpec):
        self._body = _bind(train_step_body, spec, "fm_train_step")
        self._layout = None     # the compiler's choice, once made
        self._relabel = None    # set where that is not the default
        self._programs = {}     # argument shapes -> compiled program
        self._last = None       # the program the last call ran

    def __call__(self, table, acc, labels, weights, uniq_ids, local_idx,
                 vals, fields=None):
        batch = (labels, weights, uniq_ids, local_idx, vals, fields)
        if isinstance(table, jax.core.Tracer):
            # Inside someone else's trace there is no buffer to lay
            # out: the layouts are the enclosing program's to choose.
            return jax.jit(self._body)(table, acc, *batch)
        table, acc = (x if isinstance(x, jax.Array) else jnp.asarray(x)
                      for x in (table, acc))
        args = (table, acc) + batch
        key = tuple(None if x is None else (x.shape, x.dtype) for x in args)
        program = self._programs.get(key)
        if program is None:
            program = self._programs[key] = self.compile(*args)
            _count("train/step_programs")
        if program is not self._last:
            # A job whose batches ship at more than one shape (lines of
            # unequal length: the width's rung follows a batch's widest)
            # holds a program a shape and moves between them.
            if self._last is not None:
                _count("train/program_switches")
            self._last = program
        if self._relabel is None:
            return program(table, acc, *batch)
        table, acc, loss, scores = program(*self._laid(table, acc), *batch)
        return (*self._relabel(table, acc), loss, scores)

    def compile(self, table, acc, *batch):
        """The program of one bucket, from arrays or their
        ``ShapeDtypeStruct``s (the state's carry the device). The
        first one compiled decides the layout."""
        first = self._layout is None
        state = Format(Layout.AUTO if first else self._layout,
                       table.sharding)
        shapes = [None if x is None else jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None))
            for x in (table, acc) + batch]
        program = jax.jit(
            self._body, donate_argnums=(0, 1),
            in_shardings=(state, state) + (None,) * len(batch),
            out_shardings=(state, state, None, None),
        ).lower(*shapes).compile()
        if first:
            chosen = {"table": program.input_formats[0][0].layout,
                      "acc": program.input_formats[0][1].layout,
                      "table result": program.output_formats[0].layout,
                      "acc result": program.output_formats[1].layout}
            if len(set(chosen.values())) != 1:
                raise RuntimeError(
                    "the compiler chose different layouts for the train "
                    f"step's state and its results ({chosen}): the step "
                    "would copy a whole array every call")
            self._layout = chosen["table"]
        default = self._layout == _default_layout(shapes[0])
        if first:
            get_logger().info(
                "train state layout: %s%s%s, the compiler's choice for "
                "this step's gather and scatters, %s", table.dtype,
                list(table.shape), _xla_text(self._layout),
                "as the runtime lays it out anyway" if default else
                "not the runtime's default: the state is re-laid once")
        if self._relabel is None and not default:
            self._relabel = _Relabel(self._layout, *shapes[:2])
        return program

    def _laid(self, *state):
        """The state in the step's layout: re-laid where it arrives in
        another one, as it is where it does not."""
        if all(x.format.layout == self._layout for x in state):
            return state
        return self._relabel(*(self._relay(x) for x in state))

    def _relay(self, x):
        if x.format.layout == self._layout:
            return x
        _count("train/state_relayouts")
        laid = jax.device_put(x, Format(self._layout, x.sharding))
        # The old buffer goes once the copy has run, by hand and waited
        # for: a buffer of another size is no use to the copy as a
        # donation and would live on with the caller's reference, and
        # the step program's load reserves its temporaries at dispatch
        # beside whatever is still allocated (on the v5e FFM's two old
        # arrays and two new ones left 1.74 GiB for a 3.01 GiB program).
        jax.block_until_ready(laid)
        x.delete()
        return laid


def _count(name: str) -> None:
    tel = active()
    if tel is not None:
        tel.count(name)


class _Relabel:
    """Hands a train step's table and accumulator back under the layout
    they have.

    jaxlib 0.9.0 labels every result of an executable it DESERIALIZED
    from the persistent compile cache with the runtime's default layout,
    whatever layout the buffer has. ``x.format`` then lies, jax refuses
    the array to the next step ("compiled for input layouts that
    disagree"), and any other program it compiles for that array reads
    it wrongly (CPU: other values, silently) or is refused by the
    runtime (TPU: "expected parameter 0 of size ..."). An executable
    compiled in the process labels its results as they are. So the
    results of the step, and of the re-lay, pass through this identity,
    compiled here in the process with both arrays donated and aliased:
    no operation on the device, one launch, and arrays whose label is
    true. It is run at the executable's own entry, below jax's check of
    argument layouts, which would read the false label. The step's own
    programs keep coming from the cache. Take this class out when a
    jaxlib labels them rightly: the warm-cache run in
    tests/test_state_layout.py fails without it until then."""

    def __init__(self, layout: Layout, table, acc):
        formats = (Format(layout, table.sharding),
                   Format(layout, acc.sharding))
        with uncached():
            self._identity = jax.jit(
                lambda table, acc: (table, acc), donate_argnums=(0, 1),
                in_shardings=formats, out_shardings=formats,
            ).lower(table, acc).compile().runtime_executable()

    def __call__(self, table, acc):
        results = self._identity.execute_sharded([table, acc])
        table, acc = (per_device[0] for per_device in
                      results.disassemble_into_single_device_arrays())
        return table, acc


def _xla_text(layout: Layout) -> str:
    """A layout as XLA writes it, minor dimension first:
    ``{1,0:T(8,128)}``."""
    tiles = "".join(":T(%s)" % ",".join(map(str, t))
                    for t in layout.tiling or ())
    return "{%s%s}" % (",".join(map(str, reversed(layout.major_to_minor))),
                       tiles)


def _default_layout(x) -> Layout:
    """What the runtime gives an array of x's shape on x's device when
    nobody asks for a layout."""
    device = next(iter(x.sharding.device_set))
    return Layout.from_pjrt_layout(device.client.get_default_layout(
        jnp.dtype(x.dtype), x.shape, device))


@functools.lru_cache(maxsize=None)
def make_train_step(spec: ModelSpec) -> TrainStep:
    """Build the train step. Signature:
    (table, acc, labels, weights, uniq_ids, local_idx, vals, fields)
      -> (table, acc, loss, scores)
    Buffers are donated; one executable per batch-shape bucket. Cached per
    spec so repeated train()/evaluate() calls reuse compiled code."""
    return TrainStep(spec)


def _unpack_wire(spec: ModelSpec, L: int, uniq_ids, lengths, flat_idx,
                 flat_vals, flat_fields=None):
    """Device-side wire unpack shared by the packed step/score bodies:
    rebuild the [B, L] rectangles (wire.unpack_rectangles) with the
    padding sentinel this batch shape uses — the uniq table's last slot
    in host-dedup mode, the model pad id (vocabulary_size) in raw-ids
    mode. Narrow-mode f16 values upcast to f32 here, BEFORE any model
    math."""
    from fast_tffm_tpu.wire import unpack_rectangles
    pad = (spec.vocabulary_size if uniq_ids is None
           else uniq_ids.shape[0] - 1)
    return unpack_rectangles(L, pad, lengths, flat_idx, flat_vals,
                             flat_fields)


def packed_train_step_body(spec: ModelSpec, L: int, table, acc, labels,
                           weights, uniq_ids, lengths, flat_idx,
                           flat_vals, flat_fields=None, *, mesh=None):
    """One training step from the PACKED wire format (wire.py): unpack
    the flat CSR back into the padded rectangles on-device, then run
    the exact train_step_body — same compute graph, ~padding-waste
    fewer bytes across the wall. ``L`` is static (one executable per
    (spec, B, L, flat rung, U))."""
    local_idx, vals, fields = _unpack_wire(spec, L, uniq_ids, lengths,
                                           flat_idx, flat_vals,
                                           flat_fields)
    labels = labels.astype(jnp.float32)
    weights = weights.astype(jnp.float32)
    return train_step_body(spec, table, acc, labels, weights, uniq_ids,
                           local_idx, vals, fields, mesh=mesh)


@functools.lru_cache(maxsize=None)
def make_packed_train_step(spec: ModelSpec):
    """Jitted packed train step. Signature:
    (L, table, acc, labels, weights, uniq_ids, lengths, flat_idx,
     flat_vals[, flat_fields]) -> (table, acc, loss, scores)
    ``L`` static, table/acc donated (call them positionally)."""
    return jax.jit(_bind(packed_train_step_body, spec,
                         "fm_packed_train_step"),
                   static_argnums=(0,), donate_argnums=(1, 2))


def packed_score_body(spec: ModelSpec, L: int, table, uniq_ids, lengths,
                      flat_idx, flat_vals, flat_fields=None, *,
                      mesh=None):
    """Inference forward from the packed wire format: unpack, then the
    exact score_body dispatch (raw gather for dedup=device, uniq gather
    otherwise) — BIT-identical scores to the padded wire in wide
    mode."""
    local_idx, vals, fields = _unpack_wire(spec, L, uniq_ids, lengths,
                                           flat_idx, flat_vals,
                                           flat_fields)
    return score_body(spec, table, uniq_ids, local_idx, vals, fields,
                      mesh=mesh)


@functools.lru_cache(maxsize=None)
def make_packed_score_fn(spec: ModelSpec):
    """Jitted packed inference: (L, table, uniq_ids, lengths, flat_idx,
    flat_vals[, flat_fields]) -> raw scores [B]. ``L`` static."""
    return jax.jit(_bind(packed_score_body, spec, "fm_packed_score"),
                   static_argnums=(0,))


def packed_rows_score_body(spec: ModelSpec, L: int, gathered, lengths,
                           flat_idx, flat_vals, flat_fields=None, *,
                           mesh=None):
    """Offload-score half of the packed wire (lookup.py's seam): the
    backend gathered ``[U, D]`` rows on the HOST from the withheld
    uniq_ids (WireBatch.host_uniq); only those rows plus the flat CSR
    cross the wall. Padding indexes the gathered block's last row —
    the same pad-slot contract rows_score_body inherits from the
    padded wire."""
    from fast_tffm_tpu.wire import unpack_rectangles
    local_idx, vals, fields = unpack_rectangles(
        L, gathered.shape[0] - 1, lengths, flat_idx, flat_vals,
        flat_fields)
    return rows_score_body(spec, gathered, local_idx, vals, fields,
                           mesh=mesh)


@functools.lru_cache(maxsize=None)
def make_packed_rows_score_fn(spec: ModelSpec):
    """Jitted packed offload inference: (L, gathered, lengths, flat_idx,
    flat_vals[, flat_fields]) -> raw scores [B]. ``L`` static."""
    return jax.jit(_bind(packed_rows_score_body, spec,
                         "fm_packed_rows_score"),
                   static_argnums=(0,))


def rows_score_body(spec: ModelSpec, gathered, local_idx, vals,
                    fields=None, *, mesh=None):
    """Inference forward from already-gathered rows — the score-side half
    of the lookup seam (offload predict: host gathers, device scores)."""
    return _scores(spec, gathered, local_idx, vals, fields, mesh=mesh)


@functools.lru_cache(maxsize=None)
def make_rows_score_fn(spec: ModelSpec):
    """Jitted rows_score_body: (gathered, local_idx, vals[, fields]) ->
    raw scores [B]."""
    return jax.jit(_bind(rows_score_body, spec, "fm_rows_score"))


def score_body(spec: ModelSpec, table, uniq_ids, local_idx, vals,
               fields=None, *, mesh=None):
    """Inference forward (gather -> scorer). Shared by the single-device
    and mesh-sharded score functions — single source of truth, like
    train_step_body. dedup='host' (what ``auto`` gives every sweep:
    validation, predict, on one device since PR 45): ``uniq_ids[U]``
    and slot indices in ``local_idx``; the gather walks the U slots
    the host unique fitted (20,480 for 19.4k distinct rows of 327,680
    cells at B=8192 x 40), as the train step's does. dedup='device'
    (serve's own choice, or an explicit ``dedup = device``): raw ids in
    ``local_idx``, ``uniq_ids=None`` — and NO device unique: the
    gather walks the batch's B*L raw cells one row after another (28.9
    ns a slot on the v5e whatever the count: 9.46 ms where the fitted
    slots take 0.59; PERF.md section 6, PR 44 and PR 45), and a second
    gather over an iota expands what the first wrote (ROADMAP S16(i):
    it waits for a serve cell). The device unique that would shrink
    the walk costs more than the walk (a sort of B*L ids: 14.7 ms at
    B=8192 x 64, scope ``dedup``, PERF.md section 5, PR 25).
    Either way BIT-identical scores: the same table rows summed in the
    same slot order. A train step needs unique rows for its backward
    scatter (exact sparse Adagrad): ``auto`` gives it the host unique,
    an explicit ``dedup = device`` ``_device_dedup``."""
    if spec.dedup == "device":
        if uniq_ids is not None:
            raise ValueError(
                "dedup=device scorer got a host-deduped batch (uniq_ids "
                "is set); build batches with raw_ids=True")
        B, L = local_idx.shape
        gathered = gather_rows(table, local_idx.ravel())
        idx = jnp.arange(B * L, dtype=jnp.int32).reshape(B, L)
        return rows_score_body(spec, gathered, idx, vals, fields,
                               mesh=mesh)
    gathered = gather_rows(table, uniq_ids)
    return rows_score_body(spec, gathered, local_idx, vals, fields,
                           mesh=mesh)


@functools.lru_cache(maxsize=None)
def make_score_fn(spec: ModelSpec):
    """Jitted inference: (table, uniq_ids, local_idx, vals, fields) ->
    raw scores [B] (the predict driver applies sigmoid for logistic).
    Cached per spec — callers may re-request it per file/epoch."""
    return jax.jit(_bind(score_body, spec, "fm_score"))


def ships_raw_batches(spec: ModelSpec, mesh=None, backend=None) -> bool:
    """Whether an inference path should build raw-ids batches for this
    spec — the one place the policy lives (mesh and offload paths
    require the host-dedup contract regardless of spec.dedup; a drifted
    copy of this condition is exactly how a dedup=device scorer ends up
    fed host-deduped batches)."""
    return spec.dedup == "device" and mesh is None and backend is None


def make_batch_scorer(spec: ModelSpec, mesh=None, backend=None):
    """The one dispatch over the three inference paths — plain jit,
    mesh-sharded, lookup-backend offload (lookup.py) — shared by
    evaluate() and predict_scores() so a new backend wires in exactly
    once. Returns ``score(table, args) -> jax.Array`` (device-resident,
    [B] raw scores) where ``args`` is a batch_args() dict WITHOUT
    labels/weights (consumed destructively: the offload path pops
    uniq_ids).

    Deliberately does NOT materialize to numpy: a per-batch host fetch
    is a full device round-trip that stalls async dispatch (see
    train.py's deferred loss logging). Callers batch their fetches
    with jax.device_get over many scores at once."""
    if backend is not None:
        rows_fn = make_rows_score_fn(spec)

        def score(table, args):
            gathered = backend.gather(args.pop("uniq_ids"))
            return rows_fn(gathered, **args)
    elif mesh is not None:
        from fast_tffm_tpu.parallel.sharded import (make_sharded_score_fn,
                                                    shard_batch)
        fn = make_sharded_score_fn(spec, mesh)

        def score(table, args):
            return fn(table, **shard_batch(mesh, **args))
    else:
        fn = make_score_fn(spec)

        def score(table, args):
            return fn(table, **args)
    return score


def score_args(batch: DeviceBatch) -> Dict[str, np.ndarray]:
    """What a score call takes of a batch: ``batch_args`` without the
    labels and weights, which stay on the host for whoever reads the
    scores."""
    args = batch_args(batch)
    del args["labels"], args["weights"]
    return args


def make_score_placer(mesh=None, backend=None):
    """How a sweep's feed places a batch for ``make_batch_scorer``'s
    call, on its own thread (data/pipeline.py ``place_ahead``):
    ``place(batch) -> (batch, args)`` with ``score_args`` on the device
    (``shard_batch`` on a mesh, whose scorer then finds them laid out).
    None for a lookup backend: its gather is the host's, the call takes
    host arrays."""
    if backend is not None:
        return None
    if mesh is not None:
        from fast_tffm_tpu.parallel.sharded import shard_batch
        return lambda batch: (batch, shard_batch(mesh, **score_args(batch)))
    return lambda batch: (batch, jax.device_put(score_args(batch)))


def batch_args(batch: DeviceBatch) -> Dict[str, np.ndarray]:
    args = dict(labels=batch.labels, weights=batch.weights,
                uniq_ids=batch.uniq_ids, local_idx=batch.local_idx,
                vals=batch.vals)
    if batch.fields is not None:
        args["fields"] = batch.fields
    return args
