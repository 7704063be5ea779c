"""Wire-format layer: how a built batch crosses the host->device wall.

ROADMAP item 2 named the next hard ceiling after the parallel host
plane: ``h2d_only`` sat far under ``device_only`` on an earlier
device (record removed in PR 21; ROADMAP S3/S5 re-measure both on the
v5e), so every end-to-end gain is gated on
bytes-per-example — and the pipeline already *measures* the lever
(``padding-waste``, ``dedup-hit``, ``train/h2d_bytes``) without acting
on it. This module acts on it:

- ``wire_format = padded`` (default): the fixed-shape ``[B, L]``
  rectangles ship exactly as they always have — bit-identical to every
  prior release, pinned by parity tests.
- ``wire_format = packed``: the wire carries the CSR *substance*
  instead of mostly-padding rectangles — flat values + per-example
  lengths (+ the dedup'd uniq table in host-dedup mode), bucketed to a
  quarter-octave flat ladder so jit shapes stay static — and the jitted
  step/score programs rebuild the padded rectangles on-device
  (``unpack_rectangles``; models/fm.py folds it into the compiled
  programs), where the reconstruction is a scatter that costs
  essentially nothing next to the transfer it replaces.
- ``wire_dtypes = narrow`` (packed only): values/weights ship float16
  and upcast to f32 on device before any model math (ids are int32
  end-to-end already; labels stay f32) — half the value bytes for one
  rounding step on the inputs.

The encoder is also where the depth-2 **double-buffered dispatch**
lives: ``WireEncoder.device_put`` issues an explicit async H2D for the
encoded arrays, so while step N executes on the device's compute
stream, the host loop is already encoding and transferring batch N+1
on the copy stream — transfers stop serializing inside the step
dispatch (train.py and scoring.score_sweep both route through it).

One encoder, every surface: train steps, the cross-file predict sweep,
and the serving flush path all go through ``WireEncoder`` — fmlint's
R013 enforces that no train/predict/serve module ships ad-hoc
``jax.device_put`` rectangles around it.

Scope: packed applies to the single-device jit paths (the mesh and
multi-process lockstep paths assemble padded *global* arrays, and the
offload TRAIN step gathers on the host) — ``resolve_wire`` is the one
resolution point and downgrades with a warning, like ``dedup = auto``
resolution. The offload SCORE path does ship packed: only the gathered
rows plus the flat CSR cross the wall.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import numpy as np

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import DeviceBatch
from fast_tffm_tpu.obs.telemetry import batch_payload_bytes

# Narrow-mode wire dtype for values/weights. float16 keeps a 10-bit
# mantissa (libsvm values and example weights are near-unit magnitude);
# everything upcasts to f32 on device BEFORE any model math, so the
# only precision cost is one rounding step on the inputs.
NARROW_VALUE_DTYPE = np.float16

# Smallest flat-ladder rung: tiny serve flushes (one short request)
# must not blow a wide floor past their own rectangle.
FLAT_LADDER_FLOOR = 8


def flat_bucket(nnz: int) -> int:
    """Quarter-octave flat-array bucket covering ``nnz`` feature cells
    — the packed wire's static-shape ladder for the train/predict
    streams (one compiled executable per (batch shape, flat rung), same
    philosophy as the L/U ladders). Rungs are ``m * 2^(k-3)`` for
    ``m in {5, 6, 7, 8}``: four per octave, so the flat array's own
    padding never exceeds 25% (a power-of-two ladder wastes up to 100%,
    which on a dense corpus would hand back most of what packing saved
    — the Criteo-39 shape sits at 80% rectangle fill), while a steady
    stream still touches only the handful of rungs around its density.
    """
    if nnz <= FLAT_LADDER_FLOOR:
        return FLAT_LADDER_FLOOR
    k = (nnz - 1).bit_length()     # 2^(k-1) < nnz <= 2^k
    base = 1 << (k - 3)            # quarter-octave step
    return -(-nnz // base) * base


def rect_fraction_rungs(B: int, L: int):
    """The SERVE flat ladder for one [B, L] compile cell: power-of-two
    fractions of the rectangle (B*L/8 .. B*L) plus the floor — at most
    five rungs, so pre-compiling every (batch rung x width rung x flat
    rung) keeps the server's no-recompile guarantee at ~5x the padded
    warmup matrix instead of the fine ladder's ~50x. Transfer is not
    the serve path's bound (latency is), so the coarser ladder only
    trades some savings for a bounded warmup."""
    cells = B * L
    out = {FLAT_LADDER_FLOOR}
    for j in (3, 2, 1, 0):
        out.add(max(FLAT_LADDER_FLOOR, cells >> j))
    return tuple(sorted(out))


def flat_rungs(B: int, L: int):
    """Alias used by the serve warmup: every flat rung a [B, L] flush
    can encode to under the serve (rect-fraction) ladder."""
    return rect_fraction_rungs(B, L)


@dataclasses.dataclass(frozen=True)
class WireSpec:
    """The resolved (format, dtypes) pair a dispatch path runs under."""
    format: str = "padded"   # "padded" | "packed"
    dtypes: str = "wide"     # "wide" | "narrow"

    @property
    def packed(self) -> bool:
        return self.format == "packed"

    @property
    def narrow(self) -> bool:
        return self.dtypes == "narrow"

    def describe(self) -> str:
        return f"{self.format}-{self.dtypes}"


def resolve_wire(cfg: FmConfig, mesh=None, backend=None,
                 multi_process: Optional[bool] = None,
                 train: bool = False) -> WireSpec:
    """The ONE resolution of the wire knobs for a dispatch path — a
    drifted copy of this condition is exactly how a packed encoder ends
    up feeding a padded-global-array assembler. Mirrors the
    ``dedup = auto`` resolution style: paths that require the padded
    layout (mesh sharding, multi-process lockstep, offload TRAIN — its
    host gather consumes numpy uniq_ids and its step ships gathered
    rows, not batch rectangles) resolve back to padded-wide with a
    warning instead of failing a long job at dispatch time. The offload
    SCORE path supports packed (only flat CSR + gathered rows cross the
    wall), so ``train=False`` keeps it."""
    spec = WireSpec(cfg.wire_format, cfg.wire_dtypes)
    if not spec.packed:
        return spec
    if multi_process is None:
        import jax
        multi_process = jax.process_count() > 1
    blockers = []
    if mesh is not None:
        blockers.append("mesh sharding assembles padded shard arrays")
    if multi_process:
        blockers.append("multi-process lockstep assembles padded "
                        "global arrays")
    if train and backend is not None:
        blockers.append("the offload train step gathers on the host")
    if blockers:
        import warnings
        warnings.warn(
            f"wire_format = packed is unsupported on this path "
            f"({'; '.join(blockers)}); running padded-wide instead")
        return WireSpec()
    return spec


@dataclasses.dataclass
class WireBatch:
    """One encoded batch: the arrays that actually cross the wall plus
    the accounting both h2d counters need. ``batch`` stays attached for
    the step loop's bookkeeping (num_real, stream_pos, vocab_obs)."""
    batch: DeviceBatch
    args: Dict[str, Any]     # exactly the arrays to dispatch
    packed: bool
    L: int                   # static rectangle width (the unpack target)
    wire_bytes: int          # sum of args byte sizes (the real payload)
    logical_bytes: int       # the padded layout's byte size (what the
    # legacy wire would have shipped — the savings denominator)
    host_uniq: Optional[np.ndarray] = None  # offload score path only:
    # uniq_ids stay host-side for the backend gather, never dispatched


class WireEncoder:
    """The one device-bound batch encoder (fmlint R013 anchors here).

    ``pad_id`` is the MODEL's pad id (cfg.pad_id == vocabulary_size) —
    raw-ids batches mark padding cells with it directly; host-dedup
    batches mark padding via the uniq table's last slot, which the
    encoder derives per batch. Admit-mode batches must be remapped to
    physical rows BEFORE encoding (train's ensure_current and serve's
    flush both already order it that way).

    ``host_uniq=True`` (offload score path): uniq_ids are withheld from
    the dispatched args and surfaced on ``WireBatch.host_uniq`` for the
    backend's host-side gather.

    ``rect_fraction=True`` (the serving process): flat arrays bucket to
    the coarse rect-fraction ladder instead of the fine quarter-octave
    one, so the server's pre-compiled shape matrix stays bounded (see
    rect_fraction_rungs)."""

    def __init__(self, wire: WireSpec, pad_id: int,
                 host_uniq: bool = False, rect_fraction: bool = False):
        self.wire = wire
        self.pad_id = int(pad_id)
        self.host_uniq = bool(host_uniq)
        self.rect_fraction = bool(rect_fraction)

    # -- encode ----------------------------------------------------------
    def encode_train(self, batch: DeviceBatch) -> WireBatch:
        return self._encode(batch, train=True)

    def encode_score(self, batch: DeviceBatch) -> WireBatch:
        return self._encode(batch, train=False)

    def _padded_args(self, batch: DeviceBatch,
                     train: bool) -> Dict[str, Any]:
        # Delegate to the canonical layout (models/fm.batch_args) so a
        # DeviceBatch growing a new dispatched array can never leave
        # the padded wire shipping an incomplete dict. Local import:
        # fm.py is a downstream consumer of this module.
        from fast_tffm_tpu.models.fm import batch_args
        args = batch_args(batch)
        if not train:
            args.pop("labels"), args.pop("weights")
        return args

    def _encode(self, batch: DeviceBatch, train: bool) -> WireBatch:
        li = batch.local_idx
        B, L = li.shape
        # The padded layout's size is what the legacy wire would ship:
        # labels/weights ride only on the train wire, matching the
        # score path's historical arg set.
        logical = (li.nbytes + batch.vals.nbytes
                   + (batch.uniq_ids.nbytes
                      if batch.uniq_ids is not None else 0)
                   + (batch.fields.nbytes
                      if batch.fields is not None else 0)
                   + ((batch.labels.nbytes + batch.weights.nbytes)
                      if train else 0))
        if not self.wire.packed:
            args = self._padded_args(batch, train)
            return WireBatch(batch=batch, args=args, packed=False, L=L,
                             wire_bytes=logical, logical_bytes=logical)
        # Padding test: a cell is padding iff its TARGET ROW is the
        # dead pad row (pad_id == vocabulary_size — no real feature id
        # can reach it). Host-dedup batches must be tested through the
        # uniq table, not by slot index: the python builder parks
        # padding at slot U-1 but the C++ fast path parks it at slot 0
        # (both slots hold pad_id — the invariant is about rows, not
        # slot positions, and the on-device rebuild normalizes padding
        # to slot U-1, which is bit-identical math either way: padding
        # contributes exact 0.0 through the zeroed dead row).
        if batch.uniq_ids is None:
            mask = li != self.pad_id
            pad = self.pad_id
        else:
            mask = np.asarray(batch.uniq_ids)[li] != self.pad_id
            pad = len(batch.uniq_ids) - 1
        # Features are front-packed per row (make_device_batch scatters
        # cols 0..len-1), so row-major mask selection IS the per-example
        # contiguous CSR order the device unpack rebuilds from.
        lengths = mask.sum(axis=1).astype(np.int32)
        nnz = int(lengths.sum())
        P = (next(r for r in rect_fraction_rungs(B, L) if r >= nnz)
             if self.rect_fraction else flat_bucket(nnz))
        vdt = (NARROW_VALUE_DTYPE if self.wire.narrow else np.float32)
        flat_idx = np.full(P, pad, dtype=np.int32)
        flat_vals = np.zeros(P, dtype=vdt)
        flat_idx[:nnz] = li[mask]
        flat_vals[:nnz] = batch.vals[mask]
        args = {"lengths": lengths, "flat_idx": flat_idx,
                "flat_vals": flat_vals}
        if batch.fields is not None:
            ff = np.zeros(P, dtype=np.int32)
            ff[:nnz] = batch.fields[mask]
            args["flat_fields"] = ff
        host_uniq = None
        if self.host_uniq:
            # Offload score path: the uniq table stays host-side for
            # the backend gather; the packed rows program has no
            # uniq_ids parameter at all.
            host_uniq = batch.uniq_ids
        else:
            # None in raw-ids mode — the packed programs take it like
            # the padded ones do (an empty pytree leaf).
            args["uniq_ids"] = batch.uniq_ids
        if train:
            args["labels"] = batch.labels
            args["weights"] = (batch.weights.astype(vdt)
                               if self.wire.narrow else batch.weights)
        return WireBatch(batch=batch, args=args, packed=True, L=L,
                         wire_bytes=batch_payload_bytes(args),
                         logical_bytes=logical, host_uniq=host_uniq)

    # -- the depth-2 double buffer ---------------------------------------
    def device_put(self, wb: WireBatch, window: int = 2) -> Dict[str, Any]:
        """Explicit async H2D of the encoded args — the double-buffered
        half of the wire layer. Dispatch is async, so by the time this
        runs for batch N, batch N-1's step is still executing on the
        compute stream; putting N's arrays here moves its transfer onto
        the copy stream CONCURRENT with that compute, instead of
        serializing at the head of N's step execution (the padded-era
        behavior, where the jit call transferred its numpy args
        inline). Single-device paths only — the mesh/lockstep paths
        have their own placement (shard_batch / global_batch).
        ``window``: how many batches' buffers stand on the device at
        once, for the ledger (a feed that places ahead holds more)."""
        import jax
        from fast_tffm_tpu.obs.memory import LEDGER
        # Ledger (obs/memory.py): depth-2 window — this batch's bytes
        # on the copy stream plus the previous batch's still feeding
        # the executing step. wire_bytes is host metadata; an upsert
        # per put, no device interaction.
        LEDGER.register("wire_buffers", window * wb.wire_bytes)
        return jax.device_put(wb.args)


def unpack_rectangles(L: int, pad: int, lengths, flat_idx, flat_vals,
                      flat_fields=None):
    """Device-side inverse of the packed encoding: rebuild the
    ``[B, L]`` (local_idx, vals[, fields]) rectangles from flat CSR —
    BIT-identical to the host-built padded arrays (padding cells
    restored to exactly ``pad`` / 0.0 / 0). Runs inside the jitted
    step/score programs (models/fm.py), where the scatter is noise next
    to the transfer it replaced. All shapes static: B from ``lengths``,
    P from ``flat_idx``, ``L`` and ``pad`` are trace-time ints."""
    import jax.numpy as jnp
    lengths = lengths.astype(jnp.int32)
    B = lengths.shape[0]
    P = flat_idx.shape[0]
    ends = jnp.cumsum(lengths)
    starts = ends - lengths
    total = ends[-1]
    pos = jnp.arange(P, dtype=jnp.int32)
    # Row of each flat cell: count of example ends at or before it.
    row = jnp.searchsorted(ends, pos, side="right").astype(jnp.int32)
    valid = pos < total
    rowc = jnp.clip(row, 0, B - 1)
    col = jnp.clip(pos - starts[rowc], 0, L - 1)
    # Invalid (flat-padding) cells scatter to row B -> dropped; real
    # cells land exactly where make_device_batch put them.
    r = jnp.where(valid, rowc, B)
    li = jnp.full((B, L), pad, dtype=jnp.int32)
    li = li.at[r, col].set(flat_idx.astype(jnp.int32), mode="drop")
    vv = jnp.zeros((B, L), dtype=jnp.float32)
    vv = vv.at[r, col].set(flat_vals.astype(jnp.float32), mode="drop")
    ff = None
    if flat_fields is not None:
        ff = jnp.zeros((B, L), dtype=jnp.int32)
        ff = ff.at[r, col].set(flat_fields.astype(jnp.int32),
                               mode="drop")
    return li, vv, ff
