"""Host input pipeline: text files -> fixed-shape device batches.

Replaces the reference's TF queue-runner pipeline (filename queue ->
TextLineReader.read_up_to -> shuffle queue; SURVEY.md §2 "Input pipeline",
§3.1) with an epoch-aware Python iterator that emits **static-shape**
batches XLA can compile once per bucket:

- per-example feature counts are padded to a bucket ladder (``L``),
- the batch's **unique** feature ids are computed on the host (the
  reference does ``tf.unique`` in-graph; SURVEY §3.1) and padded to their
  own ladder (``U``: quarter-octave rungs on one device, doubling ones
  for a mesh train step; ``_uniq_ladder``), so the device gathers ``U``
  table rows instead of ``B*L`` and gradient scatter-adds are already
  deduplicated,
- short final batches are padded with zero-weight dummy examples.

Padding invariants (relied on by ops/ and tests):
- ``uniq_ids`` padding slots hold ``pad_id == vocabulary_size`` (a dead
  extra table row); the last slot is always padding.
- ``local_idx`` padding points at that last slot and ``vals`` padding is
  0.0, so padded positions contribute exactly zero to scores and grads.
- dummy examples have weight 0.0 and no features.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import glob as globlib
import itertools
import os
import random
import re
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.badlines import BadLineTracker
from fast_tffm_tpu.data.parser import (WHITESPACE, ParsedBlock,
                                       ParseError)
from fast_tffm_tpu.utils.retry import (RetryPolicy, open_with_retry,
                                       retry_io)


class UniqOverflow(ValueError):
    """A batch's unique-id count exceeds the fixed unique bucket; the
    caller must spill (emit a prefix of the batch and requeue the rest)."""


@dataclasses.dataclass
class SpillStats:
    """Spill observability for fixed-U (multi-process) input: when a
    batch's unique ids exceed ``uniq_bucket`` it closes early with fewer
    real examples — correct but throughput-degrading, and invisible
    without these counters (a dense tail the startup probe missed would
    otherwise silently collapse effective batch size). Pass one to
    batch_iterator and read it after the epoch; train() logs it.
    """
    batches: int = 0            # batches emitted
    spilled_batches: int = 0    # closed early on the unique-row budget
    real_examples: int = 0      # non-padding examples emitted
    capacity: int = 0           # batches * batch_size
    max_uniq: int = 0           # densest batch's unique-row count — the
    # shrink branch of train.adapt_uniq_bucket halves an oversized
    # bucket only when the whole epoch's densest batch fits the halved
    # budget with headroom (a mean would hide the one batch that spills)

    def count(self, num_real: int, batch_size: int,
              spilled: bool, num_uniq: int = 0) -> None:
        self.batches += 1
        self.spilled_batches += int(spilled)
        self.real_examples += num_real
        self.capacity += batch_size
        self.max_uniq = max(self.max_uniq, num_uniq)
        if spilled:
            # Spill visibility also reaches the run's metrics stream
            # (obs/): this is the single counting point for fixed-U
            # spills, so the JSONL and the epoch log line can't drift.
            from fast_tffm_tpu.obs.telemetry import active
            tel = active()
            if tel is not None:
                tel.count("pipeline/spilled_batches")

    @property
    def spill_fraction(self) -> float:
        return self.spilled_batches / self.batches if self.batches else 0.0

    @property
    def fill_fraction(self) -> float:
        return (self.real_examples / self.capacity if self.capacity
                else 1.0)

    def describe(self) -> str:
        return (f"{self.batches} batches, {self.real_examples} examples "
                f"(fill {self.fill_fraction:.1%}), "
                f"{self.spilled_batches} spilled "
                f"({self.spill_fraction:.1%})")


# Above this spilled-batch fraction the pipeline is visibly degraded by
# an undersized uniq_bucket and train() warns with the fix.
SPILL_WARN_FRACTION = 0.1

# The prefix a plane's counters and gauges carry in the run's telemetry
# (batches, examples, feature_nnz, feature_slots, worker_build_seconds,
# ...; ``batch_iterator(counters=)``). A validation sweep inside a
# training run opens a plane of its own every epoch: its batches count
# under their own names, so that what reads ``pipeline/*`` (fmstat's
# fill and build rows, the benchmark's cell_fill and
# host_build_s_per_batch) reads the training plane alone.
TRAIN_PLANE = "pipeline"
VALIDATION_PLANE = "validation_plane"


def require_bounded_examples(cfg: FmConfig, context: str) -> None:
    """Fixed-shape (multi-process) modes cap L at the ladder top; an
    over-long example caught lazily mid-run would kill one worker
    between collectives and hang its peers, so refuse up front.
    max_features_per_example = 0 means "unlimited", which can never be
    honored under a fixed L."""
    if not (0 < cfg.max_features_per_example <= cfg.bucket_ladder[-1]):
        raise ValueError(
            f"{context} needs 0 < max_features_per_example "
            f"({cfg.max_features_per_example}) <= bucket_ladder max "
            f"({cfg.bucket_ladder[-1]}) so over-long examples are "
            "truncated up front instead of faulting one worker mid-run")


def effective_L_cap(cfg: FmConfig) -> int:
    """The fixed-shape per-example feature bucket: the ladder value (a
    power of two extended past the top if needed) covering
    max_features_per_example. One definition shared by the fast-path
    builder and probe_uniq_bucket — the two MUST agree or multi-process
    shapes desynchronize across the probe/build boundary."""
    return _ladder_fit(
        max(cfg.bucket_ladder[-1], cfg.max_features_per_example),
        cfg.bucket_ladder)


@dataclasses.dataclass
class DeviceBatch:
    """One fixed-shape batch. Shapes: B examples, L feature slots per
    example, U unique-row slots.

    Raw-ids mode (``dedup = device``): ``uniq_ids`` is None and
    ``local_idx`` holds RAW feature ids (pad cells = pad_id); the jitted
    step runs the unique pass on device (models/fm._device_dedup)."""
    labels: np.ndarray       # f32 [B]
    weights: np.ndarray      # f32 [B]; 0.0 marks padded dummy examples
    uniq_ids: Optional[np.ndarray]  # i32 [U]; pad_id padding; None = raw
    local_idx: np.ndarray    # i32 [B, L]; indexes uniq_ids (or raw ids)
    vals: np.ndarray         # f32 [B, L]; 0.0 padding
    fields: Optional[np.ndarray] = None  # i32 [B, L]; 0 padding (FFM)
    num_real: int = 0        # examples that are not padding
    # Segments of ``uniq_ids``, one per row shard of the mesh the batch
    # was built for (segment_plan); 1 = one list in first-seen order.
    row_shards: int = 1
    # Real feature cells (padding not counted), where the C++ builder
    # counted them as it parsed; None: a reader counts them itself.
    nnz: Optional[int] = None
    # Feature cells the lines had and the batch has not: what the
    # parser skipped past max_features_per_example.
    truncated: int = 0
    # Streaming run mode only (data/stream.py): the durable stream
    # position AFTER this batch's lines — a watermark payload dict the
    # train loop adopts once the batch has actually been stepped, so
    # checkpoints record exactly what was trained (prefetched-but-
    # unstepped batches must not advance the stream position). None
    # everywhere outside stream mode.
    stream_pos: Optional[dict] = None
    # vocab_mode = admit only (vocab/table.py): the batch's distinct
    # HASHED ids, attached by the remap seam — the train loop feeds
    # them to the admission sketch only once the batch is STEPPED
    # (the stream_pos adopt-on-step rule, applied to admission state
    # so it round-trips checkpoints exactly-once). None otherwise.
    vocab_obs: Optional[np.ndarray] = None
    # Admit mode only: the slot-map generation the remap ran under and
    # the retained hash-space originals (references, not copies) — the
    # train loop's ensure_current redoes a remap whose map a barrier
    # moved while the batch sat prefetched (vocab/table.py).
    vocab_gen: Optional[int] = None
    vocab_src: Optional[tuple] = None

    @property
    def shape_key(self) -> Tuple[int, int, int, bool]:
        return (len(self.labels), self.local_idx.shape[1],
                len(self.uniq_ids) if self.uniq_ids is not None else 0,
                self.fields is not None)


class FileMarks:
    """Per-file example-offset ledger for a single-pass keep_empty sweep
    — the cross-file streaming scorer's demux map (scoring.py).

    The pipeline appends ``(path, examples_before)`` as each file STARTS
    feeding; under ``keep_empty`` every line is exactly one example, so
    file i's examples span ``[starts[i], starts[i+1])`` of the emitted
    example stream (the last file ends at the sweep total). The
    load-bearing ordering invariant, kept by every pipeline path: a
    file's entry is appended BEFORE any batch containing that file's
    first example is yielded — so by the time the consumer holds enough
    ordered scores to cut file i, entry i+1 (if any) already exists.
    The scanner-ahead parallel plane appends entries EARLIER than the
    serial path would; earlier is always safe, later never happens.

    Thread-safe: the producing side runs on the prefetch/scanner
    thread, the reading side on the fetch worker — both under one
    lock. Requires ``keep_empty`` (blank lines are examples), a single
    epoch, and no shuffle; batch_iterator enforces all three."""

    def __init__(self):
        self._lock = threading.Lock()
        self._starts: List[Tuple[str, int]] = []

    def start_file(self, path: str, examples_before: int) -> None:
        with self._lock:
            self._starts.append((path, int(examples_before)))

    def snapshot(self) -> List[Tuple[str, int]]:
        with self._lock:
            return list(self._starts)


def expand_files(patterns: Sequence[str]) -> List[str]:
    """File list with glob expansion, order-stable (reference configs list
    globs/comma lists; SURVEY Appendix A)."""
    out: List[str] = []
    for p in patterns:
        hits = sorted(globlib.glob(p))
        if hits:
            out.extend(hits)
        else:
            out.append(p)  # let open() raise -> loud failure on missing file
    return out


def expand_paired_files(patterns: Sequence[str],
                        sidecar_patterns: Sequence[str]
                        ) -> Tuple[List[str], List[str]]:
    """Expand a data-file pattern list and its line-parallel sidecar
    pattern list TOGETHER, one pattern pair at a time.

    A purely positional zip of the two fully-expanded lists can pair
    sidecars to the WRONG files while passing a total-length check —
    e.g. two data patterns against one sidecar pattern whose hit count
    happens to match (ADVICE round 5). Pairing per pattern (both sides
    sort within a pattern, as expand_files does) makes parallel naming
    schemes like ``day*.txt`` / ``day*.weights`` line up by
    construction, and any per-pattern count mismatch fails loudly with
    the offending pair named."""
    if len(sidecar_patterns) != len(patterns):
        raise ValueError(
            f"sidecar pattern list must pair 1:1 with its data pattern "
            f"list ({len(sidecar_patterns)} sidecar patterns vs "
            f"{len(patterns)} data patterns); write one sidecar "
            "pattern per data pattern")
    files: List[str] = []
    sidecars: List[str] = []
    for dp, sp in zip(patterns, sidecar_patterns):
        d = expand_files([dp])
        s = expand_files([sp])
        if len(d) != len(s):
            raise ValueError(
                f"sidecar pattern pair expands to mismatched counts: "
                f"{dp!r} -> {len(d)} data files but {sp!r} -> {len(s)} "
                "sidecars; every data file needs exactly one sidecar")
        files.extend(d)
        sidecars.extend(s)
    return files, sidecars


def _ladder_fit(n: int, ladder: Sequence[int]) -> int:
    for b in ladder:
        if n <= b:
            return b
    # beyond the configured ladder: next power of two, so arbitrarily long
    # examples still get a (rarely recompiled) static bucket
    b = ladder[-1]
    while b < n:
        b *= 2
    return b


# The unique-row ladder's smallest rung, and the least distance between
# two rungs: every rung is a multiple of it. A mesh cuts a batch's
# slots into one segment per row shard (segment_plan) or along its data
# axis (a mesh scorer's feed), so it may have no more row shards than
# this (parallel/sharded.make_mesh checks).
UNIQ_LADDER_MIN = 64


def _uniq_ladder(batch_size: int, max_l: int,
                 doubling: bool = False) -> List[int]:
    """The rungs of the unique-row bucket U: a quarter octave apart
    (2^k x {1, 1.25, 1.5, 1.75}: 256, 320, 384, 448, 512, 640, ...) and
    never closer than ``UNIQ_LADDER_MIN`` (64, 128, 192, 256), so from
    256 slots up a batch is padded by less than a quarter of what it
    needs. The step's gather and its three Adagrad passes pay for a
    pad slot what they pay for a row (PERF.md section 5). ``doubling``
    keeps the powers of two alone: the mesh's ladder (_fit_slots says
    why). Either way the top rung is the first power of two > B*L, so
    a padding slot exists even when every id is distinct."""
    cap = batch_size * max_l + 1
    out, b = [], UNIQ_LADDER_MIN
    while b < cap:
        out.extend(range(b, 2 * b, b if doubling
                         else max(b // 4, UNIQ_LADDER_MIN)))
        b *= 2
    out.append(b)
    return out


@dataclasses.dataclass(frozen=True)
class RowShards:
    """How a mesh cuts the table's rows (parallel/sharded.ROW_SPEC):
    ``n`` equal blocks of ``rows`` rows, block s on shard s."""
    n: int
    rows: int
    pad_id: int

    @classmethod
    def of(cls, cfg: FmConfig, n: int) -> Optional["RowShards"]:
        """``n`` shards of ``cfg``'s table, or None where one device
        holds every row and the slots stay in first-seen order."""
        return cls(n, cfg.ckpt_rows // n, cfg.pad_id) if n > 1 else None


def segment_plan(uniq: np.ndarray, shards: RowShards,
                 fit) -> Tuple[np.ndarray, np.ndarray]:
    """Order a batch's unique rows by the shard that holds them.

    ``uniq`` is the batch's slot list (distinct rows in first-seen
    order, ``pad_id`` in any slot that holds none). Returns
    ``(uniq_ids[U], slot)``: ``uniq_ids`` is ``shards.n`` segments of
    ``U / n`` slots, segment s holding the rows of ``[s * shards.rows,
    (s + 1) * shards.rows)`` in first-seen order, then ``pad_id``; and
    ``slot[j]`` is where ``uniq[j]`` went, so an index ``i`` into
    ``uniq`` becomes ``slot[i]`` into ``uniq_ids`` and names the same
    row. ``uniq_ids`` ships ``P("data")``, so on the mesh segment s
    lands on the shard that holds its rows, and that shard's gather
    and Adagrad passes walk ``U / n`` slots instead of all U
    (parallel/sharded.sharded_train_step_body).

    ``fit(need) -> U`` is the caller's rule for the slot count (the
    ladder's rung, or the fixed bucket); ``need`` is ``n`` times one
    more than the FULLEST shard's rows: every segment keeps a pad slot,
    so the last slot is padding (the pipeline's invariant; a pad slot
    of ``uniq`` goes there) and U follows the fullest shard, never the
    total. A pad slot names ``pad_id`` in EVERY segment: one shard
    holds that dead row and gathers zeros from it, the others find it
    outside their block (they gather with fill and scatter with drop),
    and its gradient is masked to exactly zero either way, so the
    masks of ``grad_body`` and ``batch_reg`` stay as they are."""
    n = shards.n
    # Pad slots sort behind every shard's rows; uint16 makes the stable
    # argsort a radix sort.
    owner = np.where(uniq != shards.pad_id, uniq // shards.rows,
                     n).astype(np.uint16)
    order = np.argsort(owner, kind="stable")
    counts = np.bincount(owner, minlength=n + 1)[:n]
    n_real = int(counts.sum())
    U = fit(n * (int(counts.max()) + 1))
    seg, rem = divmod(U, n)
    if rem:
        raise ValueError(f"{U} unique-row slots do not cut into {n} "
                         "equal segments, one per row shard of the mesh")
    # Slot j of the sorted run belongs to shard s's segment at offset
    # j - (start of s's run).
    dest = np.arange(n_real) + np.repeat(
        np.arange(n) * seg - (np.cumsum(counts) - counts), counts)
    rows = order[:n_real]
    uniq_ids = np.full(U, shards.pad_id, np.int32)
    uniq_ids[dest] = uniq[rows]
    slot = np.full(len(uniq), U - 1, np.int32)
    slot[rows] = dest
    return uniq_ids, slot


def segment_slots(uniq: np.ndarray, idx: np.ndarray, shards: RowShards,
                  fit) -> Tuple[np.ndarray, np.ndarray]:
    """``segment_plan`` applied: ``(uniq_ids[U], idx')`` with
    ``uniq_ids[idx']`` equal to ``uniq[idx]`` cell by cell."""
    uniq_ids, slot = segment_plan(uniq, shards, fit)
    return uniq_ids, np.take(slot, idx)


def _fit_slots(need: int, B: int, L: int, fixed_shape: bool,
               uniq_bucket: int, mesh: bool = False) -> int:
    """U for a batch that needs ``need`` unique-row slots, its pad slot
    counted: the ladder's rung, or under ``fixed_shape`` the pinned
    bucket (UniqOverflow where the batch does not fit it).

    ``mesh`` (a mesh train step's feed: ``need`` is the row shards
    times one more than the fullest shard's rows) keeps the doubling
    rungs. On a mesh U follows the fullest shard, which moves from
    batch to batch across a quarter-octave rung where one device's
    total does not (Criteo at a global batch of 32,768 over four
    shards: 12,232 to 12,603 slots around the rung at 12,288, 2 to 6%
    of batches under it), and a mesh program is the dearer one to make
    ready in the middle of an epoch (ROADMAP.md S4)."""
    if not fixed_shape:
        return _ladder_fit(need, _uniq_ladder(B, L, doubling=mesh))
    U = uniq_bucket or _uniq_ladder(B, L)[-1]
    if need > U:
        raise UniqOverflow(
            f"the batch needs {need} unique-row slots (on a mesh: the "
            "row shards times one more than the fullest shard's rows) "
            f"and the fixed unique bucket holds {U}; raise uniq_bucket")
    return U


def make_device_batch(block: ParsedBlock, cfg: FmConfig,
                      weights: Optional[np.ndarray] = None,
                      batch_size: Optional[int] = None,
                      fixed_shape: bool = False,
                      uniq_bucket: int = 0,
                      raw_ids: bool = False,
                      shards: Optional[RowShards] = None) -> DeviceBatch:
    """CSR block -> fixed-shape DeviceBatch (pad + host-side unique).

    ``fixed_shape`` pins L and U instead of fitting this batch —
    required in multi-process SPMD, where every process must assemble
    identically-shaped global arrays every step (a process whose local
    batch picked a smaller bucket would deadlock the collective
    program). ``uniq_bucket`` (fixed_shape only) pins U to a measured
    density bound instead of the worst-case ladder top — raising
    UniqOverflow when the block genuinely exceeds it (spill protocol).

    ``raw_ids`` (dedup=device mode, incompatible with fixed_shape):
    skip the host unique pass entirely — local_idx holds raw ids,
    uniq_ids is None, the device runs the unique.

    ``shards`` (a mesh step's feed): the unique rows are ordered by
    owning row shard (segment_plan) and U follows the fullest shard.
    """
    B = batch_size or cfg.batch_size
    n_real = block.batch_size
    if n_real > B:
        raise ValueError(f"block of {n_real} examples exceeds batch_size {B}")
    _refuse_raw_fixed(raw_ids, fixed_shape)
    sizes = block.sizes
    max_l = int(sizes.max()) if n_real else 1
    ladder = cfg.bucket_ladder
    L = ladder[-1] if fixed_shape else _ladder_fit(max(max_l, 1), ladder)
    if max_l > L:
        raise ValueError(f"example with {max_l} features exceeds the fixed "
                         f"bucket {L}; raise bucket_ladder or "
                         "max_features_per_example")

    if raw_ids:
        uniq_ids, inverse, pad_slot = None, block.ids, cfg.pad_id
    else:
        # Host-side unique (replaces the reference's in-graph tf.unique).
        try:
            from fast_tffm_tpu.data.cparser import dedup_ids_fast
            uniq, inverse = dedup_ids_fast(block.ids)
        except RuntimeError:  # C++ extension unavailable
            uniq, inverse = np.unique(block.ids, return_inverse=True)
        fit = functools.partial(_fit_slots, B=B, L=L,
                                fixed_shape=fixed_shape,
                                uniq_bucket=uniq_bucket,
                                mesh=shards is not None)
        if shards is not None:
            uniq_ids, inverse = segment_slots(uniq, inverse, shards, fit)
        else:
            uniq_ids = np.full(fit(len(uniq) + 1), cfg.pad_id,
                               dtype=np.int32)
            uniq_ids[:len(uniq)] = uniq
        pad_slot = len(uniq_ids) - 1  # a pad_id slot by construction

    local_idx = np.full((B, L), pad_slot, dtype=np.int32)
    vals = np.zeros((B, L), dtype=np.float32)
    fields = (np.zeros((B, L), dtype=np.int32)
              if block.fields is not None else None)
    if n_real:
        # Vectorized CSR -> padded scatter (this runs per step on the hot
        # host path; a per-example Python loop here dominates step time).
        ex_sizes = np.diff(block.poses[:n_real + 1])
        rows = np.repeat(np.arange(n_real), ex_sizes)
        cols = np.arange(len(rows)) - np.repeat(block.poses[:n_real],
                                                ex_sizes)
        local_idx[rows, cols] = inverse
        vals[rows, cols] = block.vals
        if fields is not None:
            fields[rows, cols] = block.fields

    labels = np.zeros(B, dtype=np.float32)
    labels[:n_real] = block.labels
    w = np.zeros(B, dtype=np.float32)
    if weights is not None:
        w[:n_real] = np.asarray(weights, dtype=np.float32)[:n_real]
    else:
        w[:n_real] = 1.0
    return DeviceBatch(labels=labels, weights=w, uniq_ids=uniq_ids,
                       local_idx=local_idx, vals=vals, fields=fields,
                       num_real=n_real,
                       row_shards=shards.n if shards else 1,
                       truncated=block.truncated)


def epoch_file_order(files: List[str], shuffle: bool, seed: int,
                     epoch: int) -> List[str]:
    """Per-epoch file visit order: shuffled when shuffling is on (the
    reference's filename queue shuffles file order each epoch — SURVEY
    §2 "Input pipeline"; the bounded line/batch shuffle alone never
    mixes ACROSS files, so time-ordered multi-file datasets would feed
    whole files in sequence forever).

    Drawn from a DEDICATED per-(seed, epoch) Random — never the stream
    rng:
    that rng advances at a shard-data-dependent rate (shuffle window
    draws per emitted batch), so sharing it would give different
    processes different file orders by epoch 2 and break multi-process
    lockstep."""
    if not shuffle or len(files) < 2:
        return files
    out = list(files)
    random.Random(f"{seed}/{epoch}").shuffle(out)
    return out


def shard_byte_range(path: str, shard_index: int,
                     num_shards: int) -> Tuple[int, int]:
    """This shard's byte range of ``path``: worker i owns every line
    whose FIRST byte falls in [size*i/N, size*(i+1)/N). Each worker
    reads only ~1/N of every file (the reference sharded whole files
    across workers; byte ranges additionally balance one big file)."""
    size = os.path.getsize(path)
    return (size * shard_index // num_shards,
            size * (shard_index + 1) // num_shards)


def _iter_owned_chunks(path: str, start: int, end: int,
                       chunk_bytes: int = 4 << 20,
                       retry: Optional[RetryPolicy] = None
                       ) -> Iterator[bytes]:
    """Yield byte chunks that together contain exactly the lines owned
    by byte range [start, end) of ``path``.

    Ownership is by line start (the Hadoop-split convention): the line
    straddling ``start`` belongs to the previous range (skipped by
    scanning from start-1 to the first newline — adjacent ranges agree
    on that newline, so every line is owned exactly once); the line
    straddling ``end`` is read to completion. Only the final chunk at
    EOF may lack a trailing newline.

    ``retry`` wraps the open and each chunk read in the transient-IO
    retry loop (utils/retry.py) — a flaky networked filesystem costs
    backoff, not the run. Retry is at CHUNK granularity, and every
    attempt seeks back to the chunk's start offset first: a partial
    buffered read ADVANCES the underlying position before raising, so
    a naive in-place retry would silently resume past the lost bytes
    (truncated/merged lines — wrong training data, the worst failure
    mode this module exists to prevent).
    """
    fh = (open(path, "rb") if retry is None else
          open_with_retry(path, "rb", policy=retry, op="data_open"))

    def read(n: int) -> bytes:
        if retry is None:
            return fh.read(n)
        pos0 = fh.tell()

        def attempt() -> bytes:
            fh.seek(pos0)
            return fh.read(n)
        return retry_io(attempt, policy=retry, op="data_read")

    with fh:
        pos = start
        if start > 0:
            fh.seek(start - 1)
            while True:  # skip to the byte after the first newline
                b = read(chunk_bytes)
                if not b:
                    return  # EOF before any owned line
                i = b.find(b"\n")
                if i >= 0:
                    pos = fh.tell() - len(b) + i + 1
                    fh.seek(pos)
                    break
        if pos >= end:
            return  # first owned line starts past the range
        while True:
            b = read(chunk_bytes)
            if not b:
                return
            if pos + len(b) >= end:
                # The ownership boundary falls in this chunk: emit
                # through the first newline at absolute offset >= end-1
                # (the last owned line's terminator) and stop.
                cut = b.find(b"\n", max(end - 1 - pos, 0))
                if cut >= 0:
                    yield b[:cut + 1]
                    return
                # straddling line continues past this chunk: keep going
            yield b
            pos += len(b)


def _iter_range_lines(path: str, start: int, end: int,
                      retry: Optional[RetryPolicy] = None
                      ) -> Iterator[str]:
    """Decoded lines owned by byte range [start, end) of ``path``
    (ownership rules of _iter_owned_chunks). Splits on newlines BEFORE
    decoding so a multibyte UTF-8 character straddling a chunk boundary
    survives intact — the one implementation of the tail-carry split
    shared by _iter_lines and probe_uniq_bucket (the C++ fast path
    consumes raw bytes and never forms lines in Python)."""
    tail = b""
    for chunk in _iter_owned_chunks(path, start, end, retry=retry):
        parts = (tail + chunk if tail else chunk).split(b"\n")
        tail = parts.pop()
        for raw in parts:
            yield raw.decode("utf-8")
    if tail:  # final owned line missing its newline
        yield tail.decode("utf-8")


def _owned_start_line_index(path: str, start: int,
                            retry: Optional[RetryPolicy] = None) -> int:
    """Global line index of the first line OWNED by a byte range
    beginning at ``start`` (ownership rules of _iter_owned_chunks) == the
    newline count in [0, s) where s is that line's byte offset. A pure
    memchr-speed scan (~GB/s) — it aligns line-parallel sidecar files
    (weight_files) with a byte-range data shard without parsing.

    Memoized per file VERSION: train() builds a fresh iterator per
    epoch and this value is constant per (path, start) given the
    byte-range sharding's standing assumption that input files don't
    change mid-run — but the cache is module-level, so a long-lived
    process (pytest session, REPL) that rewrites the same path between
    runs must not be served the old file's count; size+mtime_ns+inode
    in the key invalidates rewrites (inode catches the common
    regenerate-then-rename) short of an in-place same-size rewrite
    inside one mtime clock tick, which no stat-based key can see."""
    st = os.stat(path)
    return _owned_start_line_index_for(path, start, st.st_size,
                                       st.st_mtime_ns, st.st_ino,
                                       retry)


@functools.lru_cache(maxsize=512)
def _owned_start_line_index_for(path: str, start: int, _size: int,
                                _mtime_ns: int, _ino: int,
                                retry: Optional[RetryPolicy] = None
                                ) -> int:
    if start <= 0:
        return 0
    n = 0
    # RetryPolicy is a frozen (hashable) dataclass, so it rides the
    # memo key; the scan is a pure prefix read, safe to re-drive whole.
    with (open(path, "rb") if retry is None else
          open_with_retry(path, "rb", policy=retry,
                          op="sidecar_align")) as fh:
        # Newlines strictly before `start - 1`, then resolve the
        # boundary: the newline at/after start-1 terminates the previous
        # owner's line, so the first owned line is one past it.
        remaining = start - 1
        while remaining > 0:
            b = fh.read(min(4 << 20, remaining))
            if not b:
                return n
            n += b.count(b"\n")
            remaining -= len(b)
        while True:
            b = fh.read(4 << 20)
            if not b:
                return n  # EOF before a newline: range owns nothing more
            i = b.find(b"\n")
            if i >= 0:
                return n + 1
            # keep scanning: the straddling line continues


def _iter_lines(files: Sequence[str], weight_files: Sequence[str],
                shard_index: int, num_shards: int,
                keep_empty: bool = False,
                retry: Optional[RetryPolicy] = None,
                file_marks: Optional[FileMarks] = None
                ) -> Iterator[Tuple[str, float, Tuple[str, int, int,
                                                      int]]]:
    """Yield (line, weight, source) triples for this shard, where
    ``source = (path, rel_lineno, shard_index, num_shards)`` is the
    line's provenance: ``rel_lineno`` is 1-based within the shard's
    owned byte range, resolved to an absolute file line number only on
    the error path (_resolve_source — the newline scan is lazy, so
    clean runs never pay it).

    Sharding is per-file byte ranges (shard_byte_range): each worker
    PARSES only its ~1/N of the bytes. Weight files are line-parallel to
    data files, so the weighted path aligns them by counting the data
    shard's starting line index (_owned_start_line_index — a newline
    scan, not a parse) and skipping that many weight lines; weight files
    are ~20x smaller than their data, so each worker streaming its own
    prefix of the weight file is cheap. (Until round 4 this path
    index-modulo-sharded over a FULL read of the data — N workers each
    reading and parsing every byte.)"""
    if weight_files:
        if len(weight_files) != len(files):
            raise ValueError(
                "weight sidecar list must pair 1:1 with its data files "
                f"after glob expansion ({len(weight_files)} sidecars vs "
                f"{len(files)} files)")
        for path, wpath in zip(files, weight_files):
            start, end = shard_byte_range(path, shard_index, num_shards)
            n_skip = _owned_start_line_index(path, start, retry)
            wfh = (open(wpath) if retry is None else
                   open_with_retry(wpath, policy=retry,
                                   op="sidecar_open"))
            with wfh:
                # Weight files are LINE-PARALLEL sidecars; a missing or
                # blank weight line means the pairing is broken
                # (truncated copy, corrupted file) and every example
                # from there on would silently train with the wrong
                # weight — fail loudly instead of substituting 1.0.
                for i in range(n_skip):
                    if not wfh.readline():
                        raise ValueError(
                            f"weight file {wpath} is shorter than its "
                            f"data file {path}: ended at line {i} while "
                            f"skipping to this shard's start ({n_skip})")
                lineno = n_skip
                rel = 0
                for line in _iter_range_lines(path, start, end,
                                              retry=retry):
                    wline = wfh.readline()
                    lineno += 1
                    rel += 1
                    if not wline:
                        raise ValueError(
                            f"weight file {wpath} is shorter than its "
                            f"data file {path}: no weight for data "
                            f"line {lineno}")
                    if not line.strip(WHITESPACE) and not keep_empty:
                        continue
                    try:
                        # fmlint: disable=R001 -- parses a weight-file
                        # TEXT line; no device value exists here
                        w = float(wline)
                    except ValueError:
                        raise ValueError(
                            f"bad weight {wline.strip()!r} at {wpath} "
                            f"line {lineno}") from None
                    yield line, w, (path, rel, shard_index, num_shards)
        return
    yielded = 0
    for path in files:
        if file_marks is not None:
            # keep_empty sweeps yield one example per owned line, so
            # the yielded count IS the example offset (batch_iterator
            # rejects file_marks without keep_empty).
            file_marks.start_file(path, yielded)
        start, end = shard_byte_range(path, shard_index, num_shards)
        rel = 0
        for line in _iter_range_lines(path, start, end, retry=retry):
            rel += 1
            # strip() pinned to the libsvm separator set: a line holding
            # only \x1c would read as blank here (skipped) but as a
            # parse-error line on the C++ fast path otherwise.
            if line.strip(WHITESPACE) or keep_empty:
                yielded += 1
                yield line, 1.0, (path, rel, shard_index, num_shards)


# Both parser paths prefix errors "line <block-relative-index>: ...";
# the pipeline layers the real provenance (file, absolute lineno,
# shard) on top, so a bad line in a 40-file glob is findable.
_LINE_MSG = re.compile(r"^line (\d+): (.*)$", re.S)


def _source_lineno(src: Tuple[str, int, int, int]) -> Tuple[str, int]:
    """(path, absolute 1-based file lineno) for a provenance tuple —
    what the quarantine record carries. The newline scan resolving the
    shard's starting line is memoized and error/bad-line-path-only;
    falls back to the shard-relative lineno when the file went
    unreadable underneath us."""
    path, rel, si, ns = src
    try:
        start, _ = shard_byte_range(path, si, ns)
        return path, _owned_start_line_index(path, start) + rel
    except OSError:
        return path, rel


def _resolve_source(src: Tuple[str, int, int, int]) -> str:
    """Human-findable rendering of a provenance tuple (_source_lineno's
    absolute lineno, plus the shard byte range when sharded)."""
    path, rel, si, ns = src
    _, abs_ln = _source_lineno(src)
    if ns <= 1:
        return f"{path} line {abs_ln}"
    try:
        start, end = shard_byte_range(path, si, ns)
    except OSError:
        return f"{path} line {abs_ln} (of shard {si}/{ns})"
    return f"{path} line {abs_ln}, shard {si}/{ns} (bytes {start}-{end})"


def _strip_line_prefix(msg: str) -> str:
    m = _LINE_MSG.match(msg)
    return m.group(2) if m else msg


def _attach_block_source(e: ParseError,
                         provenance: Sequence[Tuple[str, int, int, int]]
                         ) -> ParseError:
    """Rewrite a block-relative ParseError ("line 3: bad label ...")
    with the failing line's file/lineno/shard provenance."""
    m = _LINE_MSG.match(str(e))
    if not m:
        return e
    i = int(m.group(1))
    if i >= len(provenance):
        return e
    return ParseError(f"{_resolve_source(provenance[i])}: {m.group(2)}")


def _host_cpus() -> int:
    """Usable host cores, cgroup/cpuset-aware — the ONE counting rule
    behind the auto host_threads resolution, the per-worker feed-thread
    decision, and prefetch's GIL-bound passthrough gate (three callers
    that must never disagree about what 'the host has N cores'
    means)."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:
        return os.cpu_count() or 1


def resolve_host_threads(cfg: FmConfig) -> int:
    """The parallel data plane's CONFIGURED batch-build worker count:
    ``host_threads`` as set, or — 0 (auto) — min(4, host cores). 1
    keeps the serial path, byte-for-byte the pre-parallel behavior.
    Whether a given input actually fans out additionally depends on
    routing (C++ availability, weight sidecars, ...): use
    ``host_parallel_workers`` for the honest per-input answer."""
    n = int(getattr(cfg, "host_threads", 0))
    if n > 0:
        return n
    return max(1, min(4, _host_cpus()))


def host_parallel_workers(cfg: FmConfig, weight_files: Sequence[str] = (),
                          keep_empty: bool = False,
                          fixed_shape: bool = False) -> int:
    """The worker count the data plane will ACTUALLY use for these
    inputs — resolve_host_threads when a parallel route exists (the
    C++ fast path, or the tolerant generic path minus its serial-only
    features), else 1. This is the SAME predicate _batch_iterator_impl
    routes on, shared so train's startup log (and any other reporter)
    can never claim a fan-out the pipeline won't perform."""
    workers = resolve_host_threads(cfg)
    if workers <= 1:
        return 1
    from fast_tffm_tpu.data import cparser
    if not cparser.available():
        return 1
    if _fast_path_eligible(cfg, weight_files):
        return workers
    if (getattr(cfg, "bad_line_policy", "error") != "error"
            and not weight_files and not fixed_shape):
        # Tolerant generic plane. keep_empty rides it too since the C++
        # block parser grew the blank-line-preserving mode (ABI 7):
        # chunk composition stays line-deterministic — under keep_empty
        # a bad line becomes a zero-feature example instead of
        # dropping, so boundaries can't shift at all — and the parse
        # is the GIL-releasing C++ pass, so fanning it out is real
        # parallelism (the old Python-parser route made keep_empty
        # serial by routing; that was the shape predict's quarantine
        # sweeps ran single-threaded).
        return workers
    return 1


def _worker_feed_threads(workers: int, spill_capable: bool) -> int:
    """Feed parse threads per pool-worker builder. Spill-capable mode
    (fixed U) REQUIRES the serial feed: the rewind protocol needs the
    byte-exact consumed offset of a budget close, which the threaded
    feed's pending queue hides. Otherwise give each worker 2 feed
    threads when the host has cores to spare — the pool supplies the
    main fan-out, this only shortens a single group's critical path."""
    if spill_capable:
        return 1
    return 2 if _host_cpus() >= 2 * workers else 1


def _make_builder(cfg: FmConfig, B: int, raw_ids: bool, keep_empty: bool,
                  fixed_shape: bool, uniq_bucket: int,
                  num_threads: int = 0,
                  shards: Optional["RowShards"] = None):
    """The ONE BatchBuilder construction, shared by the serial fast
    path and the parallel plane's per-worker builders — a knob threaded
    into one and missed in the other would silently fork the batch
    contract between host_threads settings. Raises RuntimeError when
    the C++ extension is unavailable (batch_iterator's routing then
    takes the generic path for both planes)."""
    from fast_tffm_tpu.data.cparser import BatchBuilder
    # A ladder value (power of two past the top), so batches with
    # max_features_per_example > ladder[-1] land in the same extended
    # pow2 buckets the generic path compiles for.
    L_cap = effective_L_cap(cfg)
    row_shards = None
    if fixed_shape and shards is not None:
        # The fixed bucket ships as one segment per row shard, each
        # with its pad slot (segment_plan): the builder closes a batch
        # before a shard's rows outgrow theirs, as it does at max_uniq.
        U = uniq_bucket or _uniq_ladder(B, L_cap)[-1]
        row_shards = (shards.rows, shards.n, U // shards.n - 1)
    return BatchBuilder(B, L_cap, cfg.vocabulary_size,
                        hash_feature_id=cfg.hash_feature_id,
                        field_aware=cfg.model_type == "ffm",
                        field_num=cfg.field_num,
                        raw_ids=raw_ids, keep_empty=keep_empty,
                        max_features_per_example=(
                            cfg.max_features_per_example),
                        max_uniq=(uniq_bucket if fixed_shape else 0),
                        num_threads=num_threads, row_shards=row_shards)


class _BatchEmitter:
    """Builder-output tuple -> DeviceBatch, plus the window-shuffle
    drain: ONE implementation shared by the serial fast path, the
    parallel ring coordinator and stream mode. The host_threads=1 vs
    >1 bit-identical parity guarantee rests on this being the same
    object, fed batches in the same stream order: the window's picks
    come from one generator in emit order, and a shuffled batch's row
    order from ``(the emitter's seed, the batch's number in the
    stream)`` alone (``row_perm``), so whoever runs ``finish`` (this
    thread or a build worker) writes the same rows in the same places
    and a batch the spill-rewind protocol discards takes no draw from
    another. ``_emit`` gathers nothing: a batch is permuted once, where
    the builder pads it out."""

    def __init__(self, cfg: FmConfig, B: int, L_cap: int,
                 fixed_shape: bool, uniq_bucket: int, shuffle: bool,
                 seed: Optional[int], stats: Optional[SpillStats],
                 shards: Optional[RowShards] = None,
                 counters: str = TRAIN_PLANE):
        self.cfg = cfg
        self.B = B
        self.L_cap = L_cap
        self.fixed_shape = fixed_shape
        self.uniq_bucket = uniq_bucket
        self.shards = shards
        self.shuffle = shuffle
        self.stats = stats
        self.pyrng = random.Random(cfg.seed if seed is None else seed)
        self.perm_seed = self.pyrng.getrandbits(64)
        # The number the stream's next batch takes: counted by finish()
        # on the serial path, by the coordinator that cuts the groups
        # on the parallel one.
        self.seq = 0
        self._emit_span = counters + "/emit"  # the plane's own name
        self.window: List[DeviceBatch] = []
        self.window_cap = (max(2, cfg.queue_size // B) if shuffle
                           else 1)

    def cols(self, max_nnz: int) -> int:
        """The width a batch ships at: the ladder rung over its widest
        example. Pure, so build workers hand it to finish() and get
        their arrays at that width."""
        return (self.L_cap if self.fixed_shape
                else _ladder_fit(max(max_nnz, 1), self.cfg.bucket_ladder))

    def slots(self, uniq, max_nnz):
        """How a batch's unique slots ship, for the builder's finish():
        ``(uniq_ids[U], remap)`` with U the ladder's rung or the fixed
        bucket and, for a mesh step's feed (``shards``), the rows
        ordered by owning row shard and ``remap`` re-pointing the
        cells (segment_plan). Pure, so build workers hand it to
        finish() and the cells are written once, where they ship."""
        fit = functools.partial(_fit_slots, B=self.B,
                                L=self.cols(max_nnz),
                                fixed_shape=self.fixed_shape,
                                uniq_bucket=self.uniq_bucket,
                                mesh=self.shards is not None)
        if self.shards is not None:
            return segment_plan(uniq, self.shards, fit)
        # The builder's uniq already CONTAINS the reserved pad slot
        # (index 0), unlike the generic path's real-ids-only set —
        # fitting len+1 here would double-reserve and inflate U to the
        # next rung exactly at boundaries (2x gather/scatter width, and
        # a fast-vs-generic shape divergence that defeats compile-cache
        # reuse).
        uniq_ids = np.full(fit(len(uniq)), self.cfg.pad_id, dtype=np.int32)
        uniq_ids[:len(uniq)] = uniq  # slot 0 already pad_id (C++)
        return uniq_ids, None

    def row_perm(self, seq: int, n: int) -> Optional[np.ndarray]:
        """Where the ``n`` real rows of the stream's ``seq``-th batch
        ship under shuffle: row ``r`` at ``perm[r]``. Only the real
        rows move: consumers rely on the padding block staying at the
        tail ([:num_real] slicing). Pure, so build workers draw it."""
        if not self.shuffle or n <= 1:
            return None
        return np.random.default_rng((self.perm_seed, seq)).permutation(n)

    def finish(self, bb, seq: Optional[int] = None):
        """A builder's batch as ``emit_drain`` takes it: its finish()
        at the width, in the slots and in the row order it ships, its
        cell count and the cells its lines lost at the per-example
        cap. ``seq``: the batch's number in the emitted stream, from a
        coordinator that hands groups to build workers (pure in ``bb``
        then); None counts the batches finished here, the serial
        path's."""
        serial = seq is None
        out = bb.finish(self.cols, self.slots, functools.partial(
            self.row_perm, self.seq if serial else seq))
        if serial and out[0]:
            self.seq += 1
        return out + (bb.cells, bb.truncated)

    def emit_drain(self, out, spilled: bool) -> Iterator[DeviceBatch]:
        """Emit one ``finish(bb)`` tuple and drain through the bounded
        shuffle window (a passthrough when shuffle is off)."""
        from fast_tffm_tpu.obs.trace import span
        with span(self._emit_span,
                  seconds=self._emit_span + "_seconds"):
            batch = self._emit(*out, spilled=spilled)
            if self.shuffle:
                self.window.append(batch)
                batch = (self.window.pop(
                    self.pyrng.randrange(len(self.window)))
                    if len(self.window) >= self.window_cap else None)
        if batch is not None:
            yield batch

    def flush_window(self) -> Iterator[DeviceBatch]:
        while self.window:
            yield self.window.pop(
                self.pyrng.randrange(len(self.window)))

    def _emit(self, n, labels, uniq_ids, li, vals, fields, max_nnz,
              cells=None, truncated: int = 0,
              spilled: bool = False) -> DeviceBatch:
        cfg, B = self.cfg, self.B
        row_shards = self.shards.n if self.shards else 1
        if self.stats is not None:
            self.stats.count(n, B, spilled,
                             num_uniq=_num_uniq(uniq_ids, cfg.pad_id,
                                                row_shards))
        L = self.cols(max_nnz)
        if L < li.shape[1]:  # a finish() that was not given cols
            li = np.ascontiguousarray(li[:, :L])
            vals = np.ascontiguousarray(vals[:, :L])
            if fields is not None:
                fields = np.ascontiguousarray(fields[:, :L])
        weights = np.zeros(B, np.float32)
        weights[:n] = 1.0
        labels[n:] = 0.0  # C++ buffer may hold stale labels past n
        return DeviceBatch(labels=labels, weights=weights,
                           uniq_ids=uniq_ids, local_idx=li, vals=vals,
                           fields=fields, num_real=n,
                           row_shards=row_shards, nnz=cells,
                           truncated=truncated)


class _BuildRing:
    """Bounded ORDERED ring between a pool of batch-build workers and
    the consuming iterator — the fan-out/fan-in seam of the parallel
    host data plane. ``submit(payload)`` assigns the next sequence
    number; workers pull tasks FIFO, build outside the lock, and post
    results keyed by sequence; ``wait(seq)`` hands the consumer exactly
    the in-order stream. ``invalidate_after(seq)`` implements the
    spill-rewind protocol: a generation bump discards every queued task
    and completed-but-unconsumed result past ``seq``, and in-flight
    stale work discards itself at post time (its captured generation no
    longer matches) — speculative batches are dropped, never emitted.

    Thread-safety: every shared mutation (task deque, result map,
    generation, liveness counts) holds ``self._lock``; the condition
    variable rides the same lock (fmlint R008 checks these
    thread-reachable writes). Worker-local build state (the per-worker
    BatchBuilder) lives in objects created inside each worker thread
    and never shared. Workers are daemon threads named ``fm-build-<i>``
    so their telemetry spans render as per-worker tracks in fmtrace;
    ``close()`` stops and joins them (bounded), so an aborted run never
    leaks the pool."""

    def __init__(self, workers: int, depth: int, work,
                 make_state=None, counters: str = TRAIN_PLANE):
        self._build_seconds = counters + "/worker_build_seconds"
        self._idle_seconds = counters + "/worker_idle_seconds"
        self._ring_wait = counters + "/ring_wait"
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._tasks: collections.deque = collections.deque()
        self._results: Dict[int, tuple] = {}
        self._gen = 0
        self._next_seq = 0
        self._stop = False
        self._pool_error: Optional[BaseException] = None
        self._alive = 0
        self._started = 0
        self._work = work
        self._make_state = make_state
        self.depth = max(int(depth), 2)
        self.workers = int(workers)
        self._threads: List[threading.Thread] = []
        for i in range(self.workers):
            t = threading.Thread(target=self._worker_main,
                                 name=f"fm-build-{i}", daemon=True)
            self._threads.append(t)
            t.start()

    def submit(self, payload) -> int:
        with self._lock:
            seq = self._next_seq
            self._next_seq += 1
            self._tasks.append((self._gen, seq, payload))
            self._cv.notify_all()
            return seq

    def has(self, seq: int) -> bool:
        with self._lock:
            return seq in self._results

    def wait(self, seq: int) -> tuple:
        """Block until ``seq``'s result is ready and take it:
        ("ok", value) or ("error", exception). Raises instead when the
        pool itself is unusable (a worker's state factory failed, or
        every worker exited) — the consumer must never park forever on
        a ring nobody will fill. A result that is not there yet is
        waited for under the span ``<plane>/ring_wait``
        [``<plane>/ring_wait_seconds``]: open only while the builders
        are what the coordinator waits for."""
        from fast_tffm_tpu.obs.trace import span
        with self._lock:
            res = self._take(seq)
        if res is not None:
            return res
        with span(self._ring_wait, seconds=self._ring_wait + "_seconds"):
            with self._lock:
                while True:
                    res = self._take(seq)
                    if res is not None:
                        return res
                    self._cv.wait()

    def _take(self, seq: int) -> Optional[tuple]:
        """``seq``'s result if it is in, under the lock."""
        res = self._results.pop(seq, None)
        if res is None:
            if self._pool_error is not None:
                raise self._pool_error
            if self._started >= self.workers and self._alive == 0:
                raise RuntimeError(
                    "all batch-build workers exited; the host "
                    "data plane cannot make progress")
        return res

    def invalidate_after(self, seq: int) -> None:
        with self._lock:
            self._gen += 1
            self._tasks.clear()
            self._results = {s: r for s, r in self._results.items()
                             if s <= seq}

    def close(self) -> None:
        with self._lock:
            self._stop = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)

    def _worker_main(self) -> None:
        from fast_tffm_tpu.obs.telemetry import active
        from fast_tffm_tpu.obs.trace import span
        import time as _time
        try:
            state = (self._make_state()
                     if self._make_state is not None else None)
        except BaseException as e:  # builder creation failed: poison
            with self._lock:
                self._started += 1
                self._pool_error = e
                self._cv.notify_all()
            return
        with self._lock:
            self._started += 1
            self._alive += 1
        try:
            while True:
                idle = 0.0
                with self._lock:
                    if not self._tasks and not self._stop:
                        # fmlint: disable=R003 -- feeds the plane's
                        # worker_idle_seconds counter (summed over the
                        # workers, as worker_build_seconds is): a
                        # worker with no task, not a stage's span
                        t0 = (_time.perf_counter()
                              if active() is not None else None)
                        while not self._tasks and not self._stop:
                            self._cv.wait()
                        if t0 is not None:
                            # fmlint: disable=R003 -- closes the sample
                            idle = _time.perf_counter() - t0
                    if self._stop:
                        return
                    gen, seq, payload = self._tasks.popleft()
                tel = active()
                if idle and tel is not None:
                    tel.count(self._idle_seconds, idle)
                try:
                    if tel is None:
                        res = ("ok", self._work(state, payload))
                    else:
                        # fmlint: disable=R003 -- feeds the pipeline/
                        # worker_build_seconds counter (per-worker
                        # aggregate; the build_worker span beside it is
                        # the timeline view)
                        t0 = _time.perf_counter()
                        with span("pipeline/build_worker"):
                            res = ("ok", self._work(state, payload))
                        # fmlint: disable=R003 -- closes the sample
                        tel.count(self._build_seconds,
                                  _time.perf_counter() - t0)
                except BaseException as e:  # delivered at wait(seq)
                    res = ("error", e)
                with self._lock:
                    if gen == self._gen:
                        self._results[seq] = res
                        self._cv.notify_all()
        finally:
            with self._lock:
                self._alive -= 1
                self._cv.notify_all()


class _Group:
    """One dispatched line group: the raw bytes of exactly one batch's
    worth of example-producing lines (newline-terminated), plus its
    stream provenance — the count of stream lines before it and inside
    it (for error rebasing and spill rewind). ``seq``: the number its
    batch has in the emitted stream (what a shuffled batch's row order
    is drawn from), set by the coordinator that submits it, and
    ``epoch`` the ``_Epoch`` it was cut from, where a ring builds one
    epoch's groups behind another's (None: the workers keep one
    emitter, the stream's)."""

    __slots__ = ("blob", "line_start", "lines", "seq", "epoch")

    def __init__(self, blob: bytes, line_start: int, lines: int):
        self.blob = blob
        self.line_start = line_start
        self.lines = lines
        self.seq = 0
        self.epoch: Optional[_Epoch] = None


class _GroupScanner:
    """Cuts the shard's byte stream into per-batch line groups for the
    parallel fast plane — the deterministic interleave the pool fans
    out over.

    Group invariant: every non-final group holds exactly B
    example-producing lines by the BUILDER'S OWN counting rule
    (cparser.scan_examples shares the C++ blank-line table), is
    newline-terminated, and never splits a line — so feeding it to a
    fresh-state builder yields exactly the batch the serial builder
    would emit at that stream position. The scan is memchr-speed C++;
    Python here only slices blobs and walks 4 MB chunks, so the
    coordinator thread stays far faster than the parse it feeds.

    ``file_spans`` and the consumed-line counter mirror the serial
    path's error-provenance map (_attach_stream_source); ``pushback``
    is the spill-rewind entry: unconsumed bytes return to the stream
    head and the line counter rewinds with them, so re-cut groups get
    the same line numbers they would have had serially."""

    def __init__(self, files: Sequence[str], shard_index: int,
                 num_shards: int, B: int, keep_empty: bool,
                 retry: Optional[RetryPolicy],
                 file_marks: Optional[FileMarks] = None,
                 counters: str = TRAIN_PLANE):
        self._read_span = counters + "/scan_read"
        self._files = list(files)
        self._fi = 0
        self._chunks: Optional[Iterator[bytes]] = None
        self._buf = b""
        self._pos = 0
        self._B = B
        self._keep_empty = keep_empty
        self._retry = retry
        self._si, self._ns = shard_index, num_shards
        self._file_marks = file_marks
        self.lines = 0  # stream lines consumed into groups so far
        self.file_spans: List[Tuple[int, str, int, int]] = []

    def pushback(self, blob: bytes, line_start: int) -> None:
        self._buf = blob + self._buf[self._pos:]
        self._pos = 0
        self.lines = line_start

    def next_group(self) -> Optional[_Group]:
        from fast_tffm_tpu.data.cparser import scan_examples
        # The scan resumes where it stopped when a chunk is appended
        # (complete lines are counted once, a partial tail is never
        # consumed), so a group is scanned once however many chunks it
        # spans.
        found = consumed = nlines = 0
        while True:
            f, c, n = scan_examples(
                self._buf, self._B - found, self._keep_empty,
                offset=self._pos + consumed)
            found, consumed, nlines = found + f, consumed + c, nlines + n
            if found >= self._B:
                return self._cut(consumed, nlines)
            chunk = self._next_chunk()
            if chunk is None:
                if found:
                    g = self._cut(consumed, nlines)
                else:
                    g = None
                # Trailing blank lines (never example-producing) are
                # dropped — the serial path feeds them to the builder,
                # which skips them with no observable effect.
                self._buf = b""
                self._pos = 0
                return g
            self._buf = self._buf[self._pos:] + chunk
            self._pos = 0

    def _cut(self, consumed: int, nlines: int) -> _Group:
        g = _Group(self._buf[self._pos:self._pos + consumed],
                   self.lines, nlines)
        self._pos += consumed
        self.lines += nlines
        return g

    def _next_chunk(self) -> Optional[bytes]:
        from fast_tffm_tpu.obs.trace import span
        while True:
            if self._chunks is not None:
                # The file read alone (its open too: the generator's
                # first next()), inside the group's <plane>/scan: what
                # scan holds beyond it is the buffer's appends and cut.
                with span(self._read_span,
                          seconds=self._read_span + "_seconds"):
                    chunk = next(self._chunks, None)
                if chunk is not None:
                    return chunk
                self._chunks = None
                # File exhausted: terminate a newline-less final line
                # so its group cuts exactly where the serial path's
                # `feed(tail + b"\n")` would.
                tail = self._buf[self._pos:]
                if tail and not tail.endswith(b"\n"):
                    return b"\n"
            if self._fi >= len(self._files):
                return None
            path = self._files[self._fi]
            self._fi += 1
            start, end = shard_byte_range(path, self._si, self._ns)
            # Lines before this file = lines already consumed into
            # groups + complete lines still buffered (all from earlier
            # files; a newline-less tail was terminated above) — the
            # serial path's fed_lines at the same stream point.
            base = self.lines + self._buf.count(b"\n", self._pos)
            self.file_spans.append((base, path, start, end))
            if self._file_marks is not None:
                # base counts every stream line before this file; under
                # keep_empty (the only file_marks mode) lines ARE
                # examples, and a spill rewind re-counts to the same
                # values — the recorded base never moves.
                self._file_marks.start_file(path, base)
            # Chunks of half a large group: 4 MB ones re-copy the
            # growing buffer four times over at B = 32768. No larger:
            # the buffer has to stay under glibc's 32 MB mmap ceiling,
            # past which every append is fresh pages to fault in.
            self._chunks = _iter_owned_chunks(
                path, start, end,
                chunk_bytes=min(max(4 << 20, self._B << 8), 8 << 20),
                retry=self._retry)


class _FastWorkerState:
    """Per-worker build state: ONE BatchBuilder owned by one pool
    thread (the per-worker builder ownership the C++ concurrency
    contract requires), plus a mirror of its internal line counter for
    rebasing builder-relative error linenos onto the stream. Created
    inside the worker thread and never shared."""

    def __init__(self, make_builder, finish=None):
        self.make_builder = make_builder
        self.bb = make_builder()
        # How a built batch leaves the builder (_BatchEmitter.finish:
        # the width, the slots and the row order it ships at, its cell
        # count), where the groups name no epoch of their own.
        self.finish = finish or (lambda bb, seq: bb.finish())
        self.fed = 0  # lines consumed by self.bb since creation

    def reset(self, make_builder=None) -> None:
        # After a parse error the builder holds a half-built batch and
        # an unrecoverable line counter; a fresh builder restores both
        # invariants (the old handle frees via __del__). Another
        # ``make_builder``: an epoch whose unique budget moved at its
        # barrier.
        if make_builder is not None:
            self.make_builder = make_builder
        self.bb = self.make_builder()
        self.fed = 0


def _fast_group_work(state: _FastWorkerState, group: _Group):
    """Build ONE group (one batch's worth of lines) on a pool worker.
    Returns ``(finish_tuple, bytes_consumed)``; ``consumed <
    len(blob)`` IS the spill signal — the builder closed the batch
    early on the unique budget and left the offending line unconsumed,
    so the coordinator must rewind. ParseErrors rebase from
    builder-relative to stream-relative line numbers HERE, where the
    group's line offset is known; the coordinator then attaches file
    provenance exactly like the serial path."""
    ep, finish = group.epoch, state.finish
    if ep is not None:
        finish = ep.emitter.finish
        if ep.make_builder is not state.make_builder:
            state.reset(ep.make_builder)
    bb = state.bb
    fed_before = state.fed
    try:
        _full, consumed = bb.feed(group.blob, 0)
        out = finish(bb, group.seq)
    except ParseError as e:
        state.reset()
        m = _LINE_MSG.match(str(e))
        if m:
            k = int(m.group(1)) - fed_before
            raise ParseError(
                f"line {group.line_start + k}: {m.group(2)}") from None
        raise
    state.fed += (group.lines if consumed >= len(group.blob)
                  else group.blob[:consumed].count(b"\n"))
    return out, consumed


class _Epoch:
    """One epoch of a ring-fed stream, as the group source opens it:
    its number, the ``_BatchEmitter`` its batches leave through (seed,
    window, stats: the job-long feed makes one an epoch, the plain
    iterator keeps one for all), the ``_GroupScanner`` over its files,
    and the builder factory its groups need (another one than the last
    epoch's only where a barrier moved the unique budget). ``groups``:
    how many of its groups may still be cut (a capped sweep's; None: as
    many as its files hold). A group is a batch only where no spill
    hands lines back, so a capped epoch has no fixed-U protocol."""

    __slots__ = ("number", "emitter", "scanner", "make_builder", "groups")

    def __init__(self, number: int, emitter: _BatchEmitter,
                 scanner: _GroupScanner, make_builder,
                 groups: Optional[int] = None):
        self.number = number
        self.emitter = emitter
        self.scanner = scanner
        self.make_builder = make_builder
        self.groups = groups


class _EpochEnd:
    """In the group stream, after an epoch's last group."""

    __slots__ = ("epoch",)

    def __init__(self, epoch: _Epoch):
        self.epoch = epoch


class EpochMark:
    """In a feed's batch stream, after an epoch's last batch: the
    epoch's number and its ``SpillStats`` (``EpochFeed``)."""

    __slots__ = ("epoch", "stats")

    def __init__(self, epoch: int, stats: Optional[SpillStats]):
        self.epoch = epoch
        self.stats = stats


class _GroupSource:
    """The groups of one epoch after another, an ``_EpochEnd`` behind
    each epoch's last, None when there is no further epoch. ``epochs``
    opens an epoch when it is asked for one (and may wait there: a feed
    held at a mark), so the next epoch's files open the moment this
    one's run out. ``epoch`` is the one being cut: a spill rewind that
    hands lines back to a scanner this source had finished with sets it
    back. One group's read, append and cut run under the span
    ``<counters>/scan`` [``<counters>/scan_seconds``] on whichever
    thread asks (``fm-scan``, or the coordinator's where a spill may
    rewind); the wait for an epoch that may not be cut yet does not."""

    def __init__(self, epochs: Iterator[_Epoch],
                 counters: str = TRAIN_PLANE):
        self._epochs = epochs
        self._scan_span = counters + "/scan"
        self.epoch: Optional[_Epoch] = None

    def next(self):
        from fast_tffm_tpu.obs.trace import span
        if self.epoch is None:
            self.epoch = next(self._epochs, None)
            if self.epoch is None:
                return None
        ep = self.epoch
        with span(self._scan_span, seconds=self._scan_span + "_seconds"):
            g = None if ep.groups == 0 else ep.scanner.next_group()
        if g is None:
            end, self.epoch = _EpochEnd(ep), None
            return end
        if ep.groups is not None:
            ep.groups -= 1
        g.epoch = ep
        return g


def _ring_batches(epochs: Iterator[_Epoch], make_builder, workers: int,
                  spill_capable: bool, num_shards: int,
                  counters: str = TRAIN_PLANE, marks: bool = False,
                  hold: bool = False) -> Iterator:
    """The parallel plane's coordinator: ONE ``_BuildRing`` (its worker
    threads, a C++ builder each from ``make_builder``) and one scanner
    thread for every epoch of ``epochs``. An epoch's groups go into the
    ring behind the last one's, so no worker idles at a boundary;
    batches leave in group order through their own epoch's emitter,
    whose window is flushed where its ``_EpochEnd`` comes up, followed
    (``marks``) by an ``EpochMark``. Nothing is cut past an epoch's end
    until that end has been emitted where a spill may still hand lines
    back to the epoch's scanner (``spill_capable``) or where the
    consumer holds the source at the mark (``hold``)."""
    from fast_tffm_tpu.obs.telemetry import active
    ring = _BuildRing(workers, depth=2 * workers,
                      work=_fast_group_work,
                      make_state=lambda: _FastWorkerState(make_builder),
                      counters=counters)
    tel = active()
    if tel is not None:
        tel.set(counters + "/host_threads", workers)
    source = _GroupSource(epochs, counters)
    next_group, ahead = source.next, None
    if not spill_capable:
        # No rewind ever reaches a scanner, so groups are cut on a
        # thread of its own, ahead of the ring: reading, appending and
        # cutting a 15 MB group (14 ms at B = 32768) no longer waits
        # for the emit beside it.
        ahead = _read_ahead(iter(source.next, None), 2, "fm-scan", counters)
        next_group = functools.partial(next, ahead, None)
    inflight: Dict[int, _Group] = {}
    order: collections.deque = collections.deque()  # ring seqs, _EpochEnds
    ends = 0            # _EpochEnds in ``order``
    scan_done = False

    def head_ready() -> bool:
        return isinstance(order[0], _EpochEnd) or ring.has(order[0])

    try:
        while True:
            # Fill the ring — a group for the batch just emitted, then
            # on to depth, but not past a finished head: the batch that
            # is ready goes out first (an epoch's first batch sat behind
            # the cutting of depth groups, 0.35 s at B = 32768).
            filled = 0
            while (not scan_done and len(inflight) < ring.depth
                   and not (ends and (spill_capable or hold))
                   and not (filled and head_ready())):
                filled += 1
                g = next_group()
                if g is None:
                    scan_done = True
                elif isinstance(g, _EpochEnd):
                    order.append(g)
                    ends += 1
                else:
                    # The batch's number in its emitter's stream: a
                    # rewind drops what is in flight, and the re-cut
                    # groups count on from the spilled batch.
                    g.seq = g.epoch.emitter.seq
                    g.epoch.emitter.seq += 1
                    s = ring.submit(g)
                    inflight[s] = g
                    order.append(s)
            if not order:
                break
            s = order.popleft()
            if isinstance(s, _EpochEnd):
                ends -= 1
                yield from s.epoch.emitter.flush_window()
                if marks:
                    yield EpochMark(s.epoch.number, s.epoch.emitter.stats)
                continue
            g = inflight.pop(s)
            kind, payload = ring.wait(s)
            scanner = g.epoch.scanner
            if kind == "error":
                if isinstance(payload, ParseError):
                    raise _attach_stream_source(
                        payload, scanner.file_spans, num_shards) from None
                raise payload
            out, consumed = payload
            spilled = consumed < len(g.blob)
            yield from g.epoch.emitter.emit_drain(out, spilled)
            if spilled:
                # Rewind: the unconsumed tail of this group plus every
                # in-flight group after it returns to the scanner,
                # which re-cuts from the spilled line — exactly the
                # lines the serial builder would open the next batch
                # with. All of them are this epoch's: nothing was cut
                # past its end.
                lines_used = g.blob[:consumed].count(b"\n")
                leftover = g.blob[consumed:] + b"".join(
                    inflight[t].blob for t in order
                    if not isinstance(t, _EpochEnd))
                ring.invalidate_after(s)
                inflight.clear()
                order.clear()
                ends = 0
                g.epoch.emitter.seq = g.seq + 1
                scanner.pushback(leftover, g.line_start + lines_used)
                source.epoch = g.epoch
                scan_done = False
    finally:
        if ahead is not None:
            ahead.close()
        ring.close()


def _worker_builder_factory(cfg: FmConfig, B: int, raw_ids: bool,
                            keep_empty: bool, fixed_shape: bool,
                            uniq_bucket: int, workers: int,
                            row_shards: Optional[RowShards]):
    """What a ring's worker makes its C++ builder with."""
    return functools.partial(
        _make_builder, cfg, B, raw_ids, keep_empty, fixed_shape,
        uniq_bucket,
        _worker_feed_threads(workers, bool(fixed_shape and uniq_bucket)),
        shards=row_shards)


def _parallel_fast_batch_iterator(cfg: FmConfig, files: List[str],
                                  B: int, n_epochs: int, shuffle: bool,
                                  seed: Optional[int],
                                  fixed_shape: bool, shard_index: int,
                                  num_shards: int, uniq_bucket: int,
                                  stats: Optional[SpillStats],
                                  raw_ids: bool, keep_empty: bool,
                                  workers: int,
                                  file_marks: Optional[FileMarks] = None,
                                  row_shards: Optional[RowShards] = None,
                                  counters: str = TRAIN_PLANE
                                  ) -> Iterator[DeviceBatch]:
    """Parallel host data plane, fast path: parse+hash+dedup+pack fans
    out across ``workers`` pool threads — each owning its own C++
    BatchBuilder — over a deterministic per-batch interleave of the
    shard's line groups; finished batches re-serialize through a
    bounded ordered ring (_BuildRing, driven by ``_ring_batches``) that
    the existing prefetch() H2D stage drains.

    Parity guarantee (pinned by tests/test_parallel_pipeline.py): the
    emitted batch stream is BIT-IDENTICAL to ``host_threads = 1`` for
    the same config/seed. The load-bearing pieces:

    - groups are cut at example boundaries by the builder's own
      counting rule (_GroupScanner), so group k's lines are exactly
      serial batch k's lines;
    - each group meets a fresh-state builder (finish() resets; the C++
      library clears row buffers per batch), so batch arrays cannot
      depend on which worker built them or what it built before;
    - batches re-serialize in group order; the window's picks happen
      in the shared _BatchEmitter on the consuming side (same rng, same
      draw order as serial), and a shuffled batch's row order is drawn
      in the worker's finish() from the batch's number in the stream
      (_BatchEmitter.row_perm), which serial counts the same way;
    - a unique-budget spill (fixed-U mode) invalidates every in-flight
      group past it and re-cuts from the spilled line — the serial
      stream's requeue replayed at group granularity; speculative work
      is discarded, never emitted (spills cost a little wasted build,
      never correctness, mirroring the spill protocol's own contract).

    As the serial path does over ``n_epochs``, ONE emitter serves every
    epoch and epoch e's file order is drawn from ``(seed, e)``; the
    training feed's epochs, an emitter and a seed each, are
    ``EpochFeed``'s."""
    spill_capable = bool(fixed_shape and uniq_bucket)
    make_builder = _worker_builder_factory(
        cfg, B, raw_ids, keep_empty, fixed_shape, uniq_bucket, workers,
        row_shards)
    emitter = _BatchEmitter(cfg, B, effective_L_cap(cfg), fixed_shape,
                            uniq_bucket, shuffle, seed, stats,
                            shards=row_shards, counters=counters)
    retry = RetryPolicy.from_config(cfg)
    file_seed = cfg.seed if seed is None else seed
    epochs = (_Epoch(epoch, emitter, _GroupScanner(
        epoch_file_order(files, shuffle, file_seed, epoch), shard_index,
        num_shards, B, keep_empty, retry, file_marks=file_marks,
        counters=counters), make_builder) for epoch in range(n_epochs))
    return _ring_batches(epochs, make_builder, workers, spill_capable,
                         num_shards, counters)


def _fast_batch_iterator(cfg: FmConfig, bb, files: List[str], B: int,
                         n_epochs: int, shuffle: bool,
                         seed: Optional[int], fixed_shape: bool,
                         shard_index: int = 0, num_shards: int = 1,
                         uniq_bucket: int = 0,
                         stats: Optional[SpillStats] = None,
                         file_marks: Optional[FileMarks] = None,
                         row_shards: Optional[RowShards] = None,
                         counters: str = TRAIN_PLANE
                         ) -> Iterator[DeviceBatch]:
    """Chunked C++ fast path: raw file bytes stream straight into the
    C++ BatchBuilder (parse + hash + dedup + padded scatter in one native
    pass); Python never touches individual lines. Sharded input reads
    only this worker's byte ranges (shard_byte_range) — N workers read
    each byte once, not N times.

    Shuffle here is a window-of-batches pick plus a within-batch row
    permutation — the same mixing radius as the reference's bounded
    shuffle queue of ``queue_size`` lines (SURVEY §2 "Input pipeline"),
    expressed at batch granularity. Exact reservoir-per-line semantics
    remain on the generic path (weight files or an unavailable C++
    extension force it; FFM and keep_empty both ride this fast path —
    field-aware tokens and blank-line examples are builder modes).

    With ``uniq_bucket`` (fixed_shape multi-process mode) the builder
    caps each batch's unique rows; a too-dense batch closes early with
    n < B real examples (the spill protocol) and shapes stay constant.

    Emission (stats counting, window shuffle) and the per-batch row
    permutation handed to the builder's finish() are the shared
    _BatchEmitter's — the same code the parallel plane's ring
    coordinator and its workers run, which is what makes
    ``host_threads`` a pure throughput knob (bit-identical streams).
    """
    emitter = _BatchEmitter(cfg, B, bb.L, fixed_shape, uniq_bucket,
                            shuffle, seed, stats, shards=row_shards,
                            counters=counters)

    tail = b""
    fed_lines = 0       # complete lines fed to the builder so far —
    # mirrors the C++ builder's internal lineno (it counts every fed
    # line; a spilled line is re-fed but counted once on both sides)
    file_spans: List[Tuple[int, str, int, int]] = []  # (lines_before,
    # path, start, end) per file fed — the provenance map builder
    # "line N" errors resolve against (threaded feeds DEFER errors, so
    # one can surface while a later file is being fed)

    def feed_all(data: bytes) -> Iterator[DeviceBatch]:
        nonlocal tail, fed_lines
        fed_lines += data.count(b"\n")  # complete lines get consumed
        off = 0
        while True:
            full, consumed = bb.feed(data, off)
            off += consumed
            if not full:
                break
            out = emitter.finish(bb)
            # The builder returns "full" either at B examples or when a
            # line would blow the unique budget — the latter closes the
            # batch short (the spill being counted).
            yield from emitter.emit_drain(out, spilled=out[0] < B)
        tail = data[off:]  # unconsumed partial line, re-fed next chunk

    retry = RetryPolicy.from_config(cfg)
    file_seed = cfg.seed if seed is None else seed
    try:
        for epoch in range(n_epochs):
            for path in epoch_file_order(files, shuffle, file_seed,
                                         epoch):
                start, end = shard_byte_range(path, shard_index,
                                              num_shards)
                tail = b""
                file_spans.append((fed_lines, path, start, end))
                if file_marks is not None:
                    # fed_lines at file start == examples before it
                    # (keep_empty: every line is an example; batches
                    # holding this file's lines are yielded only from
                    # feeds AFTER this append).
                    file_marks.start_file(path, fed_lines)
                for chunk in _iter_owned_chunks(path, start, end,
                                                retry=retry):
                    yield from feed_all(tail + chunk if tail else chunk)
                if tail:  # final owned line missing its newline
                    yield from feed_all(tail + b"\n")
            out = emitter.finish(bb)
            if out[0]:  # short final batch of the epoch
                yield from emitter.emit_drain(out, spilled=False)
            yield from emitter.flush_window()
    except ParseError as e:
        raise _attach_stream_source(e, file_spans, num_shards) from None


def _attach_stream_source(e: ParseError,
                          file_spans: Sequence[Tuple[int, str, int,
                                                     int]],
                          num_shards: int) -> ParseError:
    """Rewrite a builder-stream ParseError ("line N: ..." where N
    counts every line fed to the builder since its creation) with the
    owning file's path and the absolute file line number. The span map
    is searched rather than assuming the current file: the threaded
    builder defers a parse error until batch consumption reaches it,
    which can be while a LATER file is feeding."""
    m = _LINE_MSG.match(str(e))
    if not m or not file_spans:
        return e
    n = int(m.group(1))
    owner = file_spans[0]
    for span_rec in file_spans:
        if span_rec[0] < n:
            owner = span_rec
        else:
            break
    base, path, start, end = owner
    try:
        abs_ln = _owned_start_line_index(path, start) + (n - base)
    except OSError:
        return ParseError(f"{path}: {e}")
    note = (f", shard bytes {start}-{end}" if num_shards > 1 else "")
    return ParseError(f"{path} line {abs_ln}{note}: {m.group(2)}")


def _num_uniq(uniq_ids, pad_id: int, segments: int = 1) -> int:
    """Real unique-row count of a host-deduped uniq array (pad_id slots
    are fill; no real feature id can equal it). 0 for raw-ids (None).
    Of a segmented array (``segments``, one per row shard of a mesh)
    that many times the fullest segment's rows: the slots the batch
    needs, which is what its U follows. The ONE counting rule for both pipeline paths — the shrink
    decision in train.adapt_uniq_bucket compares their stats
    directly."""
    if uniq_ids is None:
        return 0
    return segments * int(
        (uniq_ids.reshape(segments, -1) != pad_id).sum(axis=1).max())


def _batch_num_uniq(batch: DeviceBatch, cfg: FmConfig) -> int:
    return _num_uniq(batch.uniq_ids, cfg.pad_id, batch.row_shards)


def batch_iterator(cfg: FmConfig, files: Sequence[str],
                   training: bool = True,
                   weight_files: Sequence[str] = (),
                   shard_index: int = 0, num_shards: int = 1,
                   epochs: Optional[int] = None,
                   batch_size: Optional[int] = None,
                   seed: Optional[int] = None,
                   keep_empty: bool = False,
                   fixed_shape: bool = False,
                   uniq_bucket: int = 0,
                   stats: Optional[SpillStats] = None,
                   raw_ids: bool = False,
                   bad_lines: Optional[BadLineTracker] = None,
                   file_marks: Optional[FileMarks] = None,
                   vocab=None,
                   row_shards: Optional[RowShards] = None,
                   counters: str = TRAIN_PLANE
                   ) -> Iterator[DeviceBatch]:
    """Epoch/shuffle/batch loop over text files (see _batch_iterator_impl
    for the full contract). This wrapper is the pipeline's telemetry
    seam: with a run's metrics active (obs/), each built batch feeds
    the pipeline counters (examples, padding waste, dedup inputs) and
    a build-seconds histogram — timed HERE, on the producing side, so
    under prefetch it measures actual build cost on the worker thread,
    not consumer stall. Inactive (the default), batches pass straight
    through.

    ``vocab`` (a vocab.VocabMap/VocabRuntime; vocab_mode = admit) is
    ALSO seamed here: the inner iterator builds batches in the hashed
    id space (``vocab.build_cfg`` — same config, vocabulary_size
    swapped for the 2^30 hash space, so every parser/builder below
    mods into it), and every emitted batch is remapped to physical
    rows before anything downstream — telemetry included — sees it.
    None (the default, and always for vocab_mode = fixed) is
    bit-identical to the historical pipeline.

    ``row_shards`` (a mesh train step's feed): how the mesh cuts the
    table's rows; every batch's unique rows come ordered by owning
    shard (segment_plan; ``DeviceBatch.row_shards`` says so). Under
    ``vocab`` the rows are only known after the remap, which then
    orders them itself (``vocab.row_shards``).

    ``counters``: the prefix this plane's counts carry
    (``TRAIN_PLANE``; a validation sweep's ``VALIDATION_PLANE``)."""
    it = _batch_iterator_impl(cfg if vocab is None
                              else vocab.build_cfg(cfg), files,
                              training=training,
                              weight_files=weight_files,
                              shard_index=shard_index,
                              num_shards=num_shards, epochs=epochs,
                              batch_size=batch_size, seed=seed,
                              keep_empty=keep_empty,
                              fixed_shape=fixed_shape,
                              uniq_bucket=uniq_bucket, stats=stats,
                              raw_ids=raw_ids, bad_lines=bad_lines,
                              file_marks=file_marks,
                              row_shards=(row_shards if vocab is None
                                          else None),
                              counters=counters)
    return _seamed(it, cfg, None if vocab is None else vocab.remap,
                   counters)


def _seamed(it: Iterator, cfg: FmConfig, remap, counters: str) -> Iterator:
    """``batch_iterator``'s telemetry and vocab seam over a stream of
    built batches (``remap``: the vocab's, or None); a feed's
    ``EpochMark`` goes through as it is."""
    from fast_tffm_tpu.obs.telemetry import active
    tel = active()
    if tel is None:
        if remap is None:
            yield from it
        else:
            for batch in it:
                yield (batch if isinstance(batch, EpochMark)
                       else remap(batch))
        return
    import time as _time
    from fast_tffm_tpu.obs.trace import span
    pad_id = cfg.pad_id
    while True:
        # fmlint: disable=R003 -- feeds the pipeline/batch_build_seconds
        # histogram (always-on aggregate); the span beside it is the
        # timeline view and is a no-op unless the run traces
        t0 = _time.perf_counter()
        # span (obs/trace): the same interval, as a timeline event on
        # the producing (prefetch) thread's track.
        with span("pipeline/build"):
            batch = next(it, None)
        if batch is None:
            return
        if isinstance(batch, EpochMark):
            yield batch
            continue
        if remap is not None:
            # Remap INSIDE the build bracket (it is build cost) and
            # before pipeline_batch: the padding-waste counter must
            # see the physical pad_id the remap writes.
            batch = remap(batch)
        # fmlint: disable=R003 -- closes the build-seconds sample
        tel.pipeline_batch(batch, pad_id,
                           build_seconds=_time.perf_counter() - t0,
                           prefix=counters)
        yield batch


def _batch_iterator_impl(cfg: FmConfig, files: Sequence[str],
                         training: bool = True,
                         weight_files: Sequence[str] = (),
                         shard_index: int = 0, num_shards: int = 1,
                         epochs: Optional[int] = None,
                         batch_size: Optional[int] = None,
                         seed: Optional[int] = None,
                         keep_empty: bool = False,
                         fixed_shape: bool = False,
                         uniq_bucket: int = 0,
                         stats: Optional[SpillStats] = None,
                         raw_ids: bool = False,
                         bad_lines: Optional[BadLineTracker] = None,
                         file_marks: Optional[FileMarks] = None,
                         row_shards: Optional[RowShards] = None,
                         counters: str = TRAIN_PLANE
                         ) -> Iterator[DeviceBatch]:
    """Epoch/shuffle/batch loop over text files.

    Shuffling is a bounded reservoir of ``cfg.queue_size`` lines, the same
    memory/coverage contract as the reference's shuffle queue (SURVEY §2
    "Input pipeline"); deterministic given ``seed``.

    ``uniq_bucket`` (fixed_shape mode): fixed unique-row count per batch
    — see probe_uniq_bucket. Overfull batches spill: they close early
    with fewer real examples and the remainder opens the next batch.

    ``raw_ids`` (dedup=device): skip the host unique pass; batches carry
    raw ids in local_idx and uniq_ids=None (models/fm dedups on device).

    ``bad_lines``: the run-scoped BadLineTracker when the caller owns
    one (train passes a single tracker through every epoch so the
    bad-fraction breaker and the quarantine dedupe see the whole run);
    with a tolerant ``cfg.bad_line_policy`` and no caller tracker, one
    is created per iteration (evaluate/predict). Tolerant policies
    ride the generic path — the streaming C++ builder stays
    all-or-nothing (_fast_path_eligible) and per-line failures are
    reported through the block-level salvage parse
    (cparser.parse_lines_salvage).
    """
    from fast_tffm_tpu.data.parser import parse_lines
    from fast_tffm_tpu.data.cparser import parse_lines_fast

    if weight_files:
        # Sidecars expand PER PATTERN PAIR (expand_paired_files): a flat
        # post-expansion zip can silently pair weights to the wrong
        # files when multiple patterns are in play; the per-pair count
        # check fails loudly instead (ADVICE round 5). The count check
        # in _iter_lines still catches sets drifting between expansion
        # and open.
        files, weight_files = expand_paired_files(files, weight_files)
    else:
        files = expand_files(files)
        weight_files = ()
    B = batch_size or cfg.batch_size
    n_epochs = epochs if epochs is not None else (cfg.epoch_num if training
                                                  else 1)
    rng = random.Random(cfg.seed if seed is None else seed)
    do_shuffle = training and cfg.shuffle
    uniq_bucket = uniq_bucket or cfg.uniq_bucket
    _refuse_raw_fixed(raw_ids, fixed_shape)
    if file_marks is not None:
        # The ledger maps example offsets to files; that mapping only
        # exists for a single in-order keep_empty pass (one example per
        # line, no reordering, no re-reads).
        if not keep_empty or do_shuffle or n_epochs != 1 or weight_files:
            raise ValueError(
                "file_marks requires keep_empty=True, a single epoch, "
                "no shuffle, and no weight sidecars (the per-file "
                "example-offset ledger is only meaningful for an "
                "in-order one-example-per-line pass)")

    # Chunked C++ fast path (see _fast_batch_iterator): applies whenever
    # no feature needs per-line Python handling — including sharded
    # multi-process input (byte ranges), field-aware FFM tokens, and
    # keep_empty line alignment (predict). With host_threads > 1 the
    # same path fans out across the parallel data plane's worker pool
    # (bit-identical stream; README "Data plane"). The routing
    # predicate is host_parallel_workers — the SAME one train's
    # startup log reports, so the log can't claim a fan-out this
    # function won't perform.
    workers = host_parallel_workers(cfg, weight_files, keep_empty,
                                    fixed_shape)
    if _fast_path_eligible(cfg, weight_files):
        bb = _proven_builder(cfg, B, raw_ids, keep_empty, fixed_shape,
                             uniq_bucket, row_shards)
        if bb is not None:
            if workers > 1:
                # The pool's workers each make their own builder; this
                # one only proved that they can.
                yield from _parallel_fast_batch_iterator(
                    cfg, files, B, n_epochs, do_shuffle, seed,
                    fixed_shape, shard_index, num_shards, uniq_bucket,
                    stats, raw_ids, keep_empty, workers,
                    file_marks=file_marks, row_shards=row_shards,
                    counters=counters)
            else:
                yield from _fast_batch_iterator(
                    cfg, bb, files, B, n_epochs, do_shuffle, seed,
                    fixed_shape, shard_index, num_shards, uniq_bucket,
                    stats=stats, file_marks=file_marks,
                    row_shards=row_shards, counters=counters)
            return
    # Blank-line-preserving parse rides the C++ block parser too since
    # ABI 7 (keep_empty mode); _parse_block threads the flag through.
    parse = parse_lines_fast
    retry = RetryPolicy.from_config(cfg)
    tracker = bad_lines
    own_tracker = False
    if tracker is None:
        tracker = BadLineTracker.from_config(cfg)
        own_tracker = tracker is not None

    def parse_chunk(chunk, precounted: int = 0):
        """One pending chunk -> (surviving chunk, block, weights).

        Error policy: a ParseError propagates with the failing line's
        file/lineno/shard provenance attached. Tolerant policies: bad
        lines are recorded in the tracker (which may raise the
        max_bad_fraction breaker) and dropped from the chunk — except
        under keep_empty, where the parser already replaced them with
        zero-feature examples so predict's line alignment holds.

        ``precounted``: the first this-many chunk items already passed
        through the tracker on an earlier pass (a UniqOverflow spill
        requeues its tail at the front of pending) — they must not
        count or record a second time, or spills would inflate the
        totals and break the skip-count-equals-injected contract."""
        lines = [c[0] for c in chunk]
        if tracker is None:
            try:
                block = _parse_block(lines, cfg, parse, keep_empty)
            except ParseError as e:
                raise _attach_block_source(
                    e, [c[2] for c in chunk]) from None
        else:
            bads: List[Tuple[int, str, str]] = []
            block = _salvage_block(lines, cfg, keep_empty, bads)
            fresh_bads = [b for b in bads if b[0] >= precounted]
            tracker.count_ok(len(lines) - precounted
                             - len(fresh_bads))
            if fresh_bads:
                for i, raw, msg in fresh_bads:
                    path, abs_ln = _source_lineno(chunk[i][2])
                    tracker.record(path, abs_ln, raw,
                                   _strip_line_prefix(msg))
            if bads and not keep_empty:
                badset = {i for i, _, _ in bads}
                chunk = [c for i, c in enumerate(chunk)
                         if i not in badset]
        w = np.array([c[1] for c in chunk], dtype=np.float32)
        return chunk, block, w

    # Generic-path fan-out (tolerant bad-line policies): chunk
    # composition is line-order-deterministic — a bad line drops from
    # the parsed BLOCK, never shifts the B-line chunk boundaries — and
    # with fixed_shape off no UniqOverflow can reorder the stream, so
    # each chunk's parse+build is an independent task farmed to the
    # pool and re-serialized in submit order (same bounded ordered
    # ring as the fast plane). The run-scoped LOCKED tracker is shared
    # by every worker, so the max_bad_fraction breaker and the
    # quarantine (file, lineno) dedupe stay global; only the ORDER of
    # quarantine records may interleave across workers — the set is
    # identical, pinned by the parity tests. keep_empty rides the pool
    # too (ABI 7: the C++ parser preserves blanks, and a bad line
    # becomes a zero-feature example — boundaries can't shift at all);
    # weighted and fixed-shape inputs stay serial (GIL-bound pairing
    # and the spill-requeue's sequential composition).
    pool: Optional[_BuildRing] = None
    pool_order: collections.deque = collections.deque()
    if tracker is not None and workers > 1:
        # workers > 1 already folds in the route conditions (C++
        # available, no weights/fixed_shape; keep_empty allowed since
        # ABI 7) via host_parallel_workers above.
        def _pool_work(_state, payload):
            raw_chunk, precounted = payload
            chunk, block, w = parse_chunk(raw_chunk,
                                          precounted=precounted)
            if block.batch_size == 0:
                return None  # every line of the chunk was bad
            return make_device_batch(block, cfg, weights=w,
                                     batch_size=B,
                                     fixed_shape=fixed_shape,
                                     uniq_bucket=uniq_bucket,
                                     raw_ids=raw_ids, shards=row_shards)
        pool = _BuildRing(workers, depth=2 * workers,
                          work=_pool_work, counters=counters)
        from fast_tffm_tpu.obs.telemetry import active as _active
        _tel = _active()
        if _tel is not None:
            _tel.set(counters + "/host_threads", workers)

    def pool_drain(limit: int) -> Iterator[DeviceBatch]:
        """Yield completed pool batches in submit order: every
        already-finished head eagerly, plus (blocking) enough to keep
        the in-flight count within ``limit`` (0 = drain everything)."""
        while pool_order and (len(pool_order) > limit
                              or pool.has(pool_order[0])):
            s = pool_order.popleft()
            kind, val = pool.wait(s)
            if kind == "error":
                raise val
            if val is None:
                continue  # all-bad chunk: nothing to emit
            if stats is not None:
                stats.count(val.num_real, B, False,
                            num_uniq=_batch_num_uniq(val, cfg))
            yield val

    file_seed = cfg.seed if seed is None else seed
    try:
        for epoch in range(n_epochs):
            pending: List[Tuple[str, float, tuple]] = []
            buf: List[Tuple[str, float, tuple]] = []
            # How many FRONT items of `pending` already passed through
            # the tracker (spill-requeued tails); see parse_chunk.
            requeue_counted = [0]

            def flush_batches(done: bool):
                while len(pending) >= B or (done and pending):
                    raw_chunk = pending[:B]
                    del pending[:B]
                    k = min(requeue_counted[0], len(raw_chunk))
                    requeue_counted[0] -= k
                    if pool is not None:
                        pool_order.append(pool.submit((raw_chunk, k)))
                        yield from pool_drain(pool.depth)
                        continue
                    chunk, block, w = parse_chunk(raw_chunk,
                                                  precounted=k)
                    if tracker is not None and block.batch_size == 0:
                        continue  # every line of the chunk was bad
                    try:
                        out = make_device_batch(block, cfg, weights=w,
                                                batch_size=B,
                                                fixed_shape=fixed_shape,
                                                uniq_bucket=uniq_bucket,
                                                raw_ids=raw_ids,
                                                shards=row_shards)
                        if stats is not None:
                            stats.count(out.num_real, B, False,
                                        num_uniq=_batch_num_uniq(out,
                                                                 cfg))
                        yield out
                    except UniqOverflow:
                        # Spill: emit the longest example prefix that
                        # fits the unique budget; the tail reopens the
                        # queue.
                        m = _uniq_prefix_examples(block, uniq_bucket,
                                                  row_shards)
                        if m == 0:
                            raise ValueError(
                                "single example exceeds uniq_bucket "
                                f"{uniq_bucket}; raise it (or set 0 "
                                "for auto)")
                        pending[0:0] = chunk[m:]
                        if tracker is not None:
                            # The requeued tail is already tracked; it
                            # must not count/record again next pass.
                            requeue_counted[0] += len(chunk) - m
                        # Re-parse of already-validated survivors: no
                        # tracker (they were counted once above).
                        head = _parse_block([c[0] for c in chunk[:m]],
                                            cfg, parse, keep_empty,
                                            salvage=tracker is not None)
                        out = make_device_batch(head, cfg,
                                                weights=w[:m],
                                                batch_size=B,
                                                fixed_shape=fixed_shape,
                                                uniq_bucket=uniq_bucket,
                                                shards=row_shards)
                        if stats is not None:
                            stats.count(out.num_real, B, True,
                                        num_uniq=_batch_num_uniq(out,
                                                                 cfg))
                        yield out

            for item in _iter_lines(
                    epoch_file_order(files,
                                     do_shuffle and not weight_files,
                                     file_seed, epoch),
                    weight_files,
                    shard_index, num_shards, keep_empty=keep_empty,
                    retry=retry, file_marks=file_marks):
                if do_shuffle:
                    buf.append(item)
                    if len(buf) >= max(cfg.queue_size, B):
                        j = rng.randrange(len(buf))
                        buf[j], buf[-1] = buf[-1], buf[j]
                        pending.append(buf.pop())
                else:
                    pending.append(item)
                yield from flush_batches(False)
            if do_shuffle and buf:
                rng.shuffle(buf)
                pending.extend(buf)
            yield from flush_batches(True)
            if pool is not None:  # epoch barrier: ring fully drained
                yield from pool_drain(0)
    finally:
        if pool is not None:
            pool.close()
        if own_tracker:
            tracker.close()


def _uniq_prefix_examples(block: ParsedBlock, uniq_bucket: int,
                          shards: Optional[RowShards] = None) -> int:
    """Largest count of leading examples whose id union fits the unique
    bucket (one slot reserved for padding) — the generic-path spill
    split point. On a mesh (``shards``) each row shard's ids have to
    fit its segment of the bucket."""
    if block.batch_size == 0:
        return 0
    ids, first_pos = np.unique(block.ids, return_index=True)
    # Example index owning each first occurrence -> uniques per example.
    ex = np.searchsorted(block.poses, first_pos, side="right") - 1
    if shards is None:
        cum = np.cumsum(np.bincount(ex, minlength=block.batch_size))
        return int(np.searchsorted(cum, uniq_bucket - 1, side="right"))
    per = np.zeros((shards.n, block.batch_size), np.int64)
    np.add.at(per, (ids // shards.rows, ex), 1)
    fits = (np.cumsum(per, axis=1) < uniq_bucket // shards.n).all(axis=0)
    return block.batch_size if fits.all() else int(fits.argmin())


def probe_uniq_bucket(cfg: FmConfig, files: Sequence[str],
                      batch_size: Optional[int] = None,
                      shards: Optional[RowShards] = None) -> int:
    """Pick the fixed unique-row bucket for multi-process training by
    measuring the data instead of assuming the worst case (the ladder
    top is next_pow2(B*L) — ~50x a realistic Criteo batch's uniques).

    Parses one batch each from the head, middle, and tail of the FIRST,
    LAST, and LARGEST files (day-partitioned datasets whose later files
    are denser would defeat a first-file-only probe) — every process
    reads the same bytes, so all agree without a collective — and
    returns the next power of two >= 2x the max measured unique count
    (>= 64, > the per-example cap, <= the ladder top). Densities the
    probe still missed are absorbed by the spill protocol, costing
    throughput, never correctness — counted by SpillStats, warned at
    epoch end, and recovered by train()'s epoch-boundary bucket raise.

    For a mesh train step's feed (``shards``) the count is the row
    shards times the fullest shard's rows, as the batch's U follows the
    fullest shard (segment_plan).
    """
    B = batch_size or cfg.batch_size
    files = expand_files(files)
    top = uniq_bucket_top(cfg, B, shards)
    n = shards.n if shards else 1
    retry = RetryPolicy.from_config(cfg)
    from fast_tffm_tpu.data.cparser import parse_lines_fast
    parse = parse_lines_fast
    # Tolerant bad-line policies must not die in the PROBE on a line
    # the training sweep would skip: the probe's density estimate
    # simply ignores bad lines (they are recorded/counted later, when
    # the real iterators scan them).
    tolerant = getattr(cfg, "bad_line_policy", "error") != "error"

    cand = sorted({files[0], files[-1],
                   max(files, key=os.path.getsize)})
    u_max = 0
    got_lines = False
    for path in cand:
        size = retry_io(os.path.getsize, path, policy=retry,
                        op="probe_stat")
        for start in sorted({0, size // 3, 2 * size // 3}):
            lines: List[str] = []
            for line in _iter_range_lines(path, start, size,
                                          retry=retry):
                if line.strip(WHITESPACE):
                    lines.append(line)
                if len(lines) >= B:
                    break
            if not lines:
                continue
            got_lines = True
            try:
                block = _parse_block(lines[:B], cfg, parse,
                                     salvage=tolerant)
            except ParseError as e:
                raise ParseError(f"{path} (uniq-bucket probe near "
                                 f"byte {start}): "
                                 f"{_strip_line_prefix(str(e))}"
                                 ) from None
            uniq = np.unique(block.ids)
            u_max = max(u_max, len(uniq) if shards is None else
                        shards.n * np.bincount(
                            uniq // shards.rows).max(initial=0))
    if not got_lines:
        return min(1 << 10, top)
    b = 64
    while b < 2 * (u_max + 2 * n) or b // n <= cfg.max_features_per_example:
        b *= 2
    return min(b, top)


def uniq_bucket_top(cfg: FmConfig, batch_size: Optional[int] = None,
                    shards: Optional[RowShards] = None) -> int:
    """The worst-case unique bucket (ladder top; on a mesh every row
    could sit on one shard, so that per segment) — the ceiling for
    train()'s epoch-boundary adaptive raise."""
    return (shards.n if shards else 1) * _uniq_ladder(
        batch_size or cfg.batch_size, effective_L_cap(cfg))[-1]


def empty_batch(cfg: FmConfig, batch_size: Optional[int] = None,
                uniq_bucket: int = 0,
                shards: Optional[RowShards] = None) -> DeviceBatch:
    """An all-padding batch (num_real=0, zero weights): the SPMD filler a
    data-exhausted process feeds while peers finish their shards — every
    term it contributes to loss/grad/reg is exactly zero by the padding
    invariants above. ``uniq_bucket`` must match the live batches'."""
    fields = (np.zeros(0, np.int32) if cfg.model_type == "ffm" else None)
    block = ParsedBlock(labels=np.zeros(0, np.float32),
                        poses=np.zeros(1, np.int32),
                        ids=np.zeros(0, np.int32),
                        vals=np.zeros(0, np.float32), fields=fields)
    return make_device_batch(block, cfg, batch_size=batch_size,
                             fixed_shape=True,
                             uniq_bucket=uniq_bucket or cfg.uniq_bucket,
                             shards=shards)


def _refuse_raw_fixed(raw_ids: bool, fixed_shape: bool) -> None:
    if raw_ids and fixed_shape:
        raise ValueError("raw_ids (dedup=device) has no fixed-U protocol; "
                         "multi-process mode needs dedup=host")


def _proven_builder(cfg: FmConfig, B: int, raw_ids: bool,
                    keep_empty: bool, fixed_shape: bool, uniq_bucket: int,
                    row_shards: Optional[RowShards]):
    """ONE C++-or-generic decision for both planes, taken before any
    pool thread exists: the serial path's builder, or None where none
    can be made, which sends the serial AND the parallel plane down
    the generic path (cparser logged why, at WARNING). The decision is
    the construction, not cparser.available(): "no C++" is the
    builder's RuntimeError, which is also the seam
    tests/test_sharded_input.py forces the generic path through."""
    try:
        return _make_builder(cfg, B, raw_ids, keep_empty, fixed_shape,
                             uniq_bucket, shards=row_shards)
    except RuntimeError:
        return None


def _fast_path_eligible(cfg: FmConfig,
                        weight_files: Sequence[str]) -> bool:
    """The ONE gate for the chunked C++ fast path: no per-line Python
    handling (weight sidecars pair weights to lines in Python), a
    hard per-example cap (the builder writes fixed-stride rows;
    max_features_per_example = 0 means "unlimited" and stays generic),
    and the strict bad-line policy — the streaming builder is
    all-or-nothing on a parse error by design (its batch state is not
    recoverable mid-line), so skip/quarantine tolerance lives on the
    generic path, whose blocks still parse through the C++ block
    parser with a per-line Python salvage retry only for a FAILING
    block (cparser.parse_lines_salvage).
    batch_iterator's path selection and gil_bound_iteration's
    GIL-contention answer must agree, so both call here — a hand-copied
    predicate drifting between them would silently thread a GIL-bound
    iterator (or passthrough a releasing one)."""
    return (not weight_files and cfg.max_features_per_example > 0
            and getattr(cfg, "bad_line_policy", "error") == "error")


def gil_bound_iteration(cfg: FmConfig, weight_files: Sequence[str] = (),
                        keep_empty: bool = False) -> bool:
    """Whether batch_iterator's iteration for these inputs is dominated
    by GIL-holding Python work — the SAME path selection
    batch_iterator makes (_fast_path_eligible), exposed so prefetch
    callers can gate the worker thread on it. That happens when the
    C++ extension is unavailable, on the generic keep_empty shapes
    (their block parse is C++ since ABI 7, but the per-line Python
    iteration of _iter_lines still holds the GIL), and on the WEIGHTED
    path: its block parse is C++ (GIL released) but the per-line weight
    pairing (readline/float/strip and a Python yield per line) holds
    the GIL — threading it on a single core is the contention class
    the gate exists to passthrough."""
    from fast_tffm_tpu.data import cparser
    if not cparser.available():
        return True
    if weight_files:
        return True
    if getattr(cfg, "bad_line_policy", "error") != "error":
        # Tolerant policies ride the generic path: C++ block parse
        # (GIL released) but per-line Python iteration holds the GIL —
        # the weighted path's contention class.
        return True
    return (not _fast_path_eligible(cfg, weight_files)) and keep_empty


def prefetch(iterator: Iterator[DeviceBatch], depth: int = 2,
             gil_bound: bool = False,
             counters: Optional[str] = None) -> Iterator[DeviceBatch]:
    """Run ``iterator`` in a background thread, ``depth`` batches ahead.

    The reference overlaps input with compute via TF queue-runner threads
    (SURVEY §2 "Input pipeline"); here one host thread prepares the next
    batches while the device runs the current step. The C++ parser,
    numpy, and the device-transfer waits all release the GIL, so the
    overlap is real even on a single-core host: the builder thread runs
    while the consumer waits on H2D (round 4, on an earlier one-core
    host, never measured slower than serial; not re-measured on the
    v5e host — ROADMAP D8).

    ``gil_bound`` (see gil_bound_iteration): the iterator parses in pure
    Python and would CONTEND with jax dispatch on a single core
    (measured 4x slower in round 2, when Python was the only parser) —
    that combination keeps the passthrough.

    What this hands over are HOST batches. Where they cross to the
    device is the consumer's: a job's feeds (``EpochFeed``: the
    training plane's, a validating job's sweeps') add a stage of their
    own behind this one (``place_ahead``: placement a batch ahead, off
    the loop's thread), predict places on its own thread as it
    dispatches. ``counters``: ``_read_ahead``'s (a job's feed hands
    its plane's prefix; predict's sweep and the stream count nothing).
    """
    if gil_bound:
        if _host_cpus() <= 1:
            yield from iterator
            return

    ledgered = False
    ahead = _read_ahead(iterator, depth, "prefetch", counters)
    try:
        for item in ahead:
            if not ledgered:
                # Ledger (obs/memory.py): the prefetch window's
                # standing footprint — queue depth + the in-hand batch,
                # sized from the first batch (bucketed shapes keep
                # later ones comparable). Host-resident numpy until the
                # wire layer places it (host=True: gauged, excluded
                # from the device live total). Once, not per batch —
                # this is the hottest host loop in the tree.
                ledgered = True
                nb = 0
                for v in getattr(item, "__dict__", {}).values():
                    nb += getattr(v, "nbytes", 0)
                if nb:
                    from fast_tffm_tpu.obs.memory import LEDGER
                    LEDGER.register("prefetch_batches",
                                    (max(depth, 1) + 1) * nb,
                                    host=True)
            yield item
    finally:
        ahead.close()
        from fast_tffm_tpu.obs.memory import LEDGER
        LEDGER.release("prefetch_batches")


def place_ahead(batches: Iterator[DeviceBatch], place, depth: int,
                loop: str) -> Iterator[tuple]:
    """The feed's last stage: ``(batch, placed)`` for every batch of
    ``batches``. ``place(batch) -> (batch, placed)`` (train.py
    ``StepLoop.feed_place``: wire encoding and host-to-device
    placement; a sweep's, models/fm.py ``make_score_placer``: the score
    call's arguments) runs on a thread of its own, ``fm-place``, at
    most ``depth`` batches ahead of the consumer, under the span
    ``feed/place`` [``<loop>/place_seconds``, ``loop`` the consumer's
    prefix: ``train``, a sweep's feed ``validation``]: no leaf of the
    loop's partition, since its thread does not wait for it. A separate
    stage and not the emitting thread's work: emit and placement in
    series would be one thread's. What ``place`` raises is raised at
    the consumer's next(); a consumer that stops closes the stage, and
    the batches it had placed are let go with it. ``place`` None (the
    loop places for itself): every batch with ``placed`` None, on the
    consumer's thread. The stage's blocked seconds count beside them
    (``<loop>/fm_place_put_wait_seconds``); the wait for it is the
    consumer's own span (``train/input_wait``), counted there and not
    here a second time."""
    feed = _each_placed(batches, place, loop + "/place_seconds")
    if place is None:
        return feed
    return _read_ahead(feed, depth, "fm-place", loop, get_wait=False)


def _each_placed(batches: Iterator[DeviceBatch], place,
                 seconds: str) -> Iterator[tuple]:
    from fast_tffm_tpu.obs.trace import span
    try:
        for batch in batches:
            if isinstance(batch, EpochMark):
                yield batch
                continue
            if place is None:
                yield batch, None
                continue
            with span("feed/place", seconds=seconds):
                item = place(batch)
            yield item
    finally:  # closed with the stage: the stages behind it stop too
        batches.close()


class EpochFeed:
    """A job's feed of one plane, opened once: ``(batch, placed)`` for
    every batch of epochs ``epochs`` (a range; a validating job's
    sweeps are the epochs of a feed of their own, as many as come) and,
    in band, one ``EpochMark`` behind each epoch's last batch. What is
    an epoch's stays an epoch's (the emitter with its seed and shuffle
    window, the scanner, the ``SpillStats``) and what is the plane's is
    made once (the ``prefetch`` and ``fm-place`` threads and, on the
    parallel fast path, the build ring with its workers' C++ builders
    and the ``fm-scan`` thread: ``_ring_batches``; the other routes
    open each epoch's ``_batch_iterator_impl`` on the producing thread
    as the last one runs out). So epoch e + 1's first batches are cut,
    built and placed while epoch e's last steps run (a sweep's: while
    the training interval runs), as far ahead as the queues there are
    allow.

    What the plane IS, the session's to say. ``training`` (the
    default): epoch e's batches are, array for array and in order,
    those of ``batch_iterator(cfg, files, epochs=1, seed=cfg.seed + e,
    ...)``. A sweep's plane (``training`` False): every epoch's are
    those of ``batch_iterator(cfg, files, training=False, epochs=1,
    ...)``, no shuffle and the files in their order, at most
    ``max_batches`` of them where the session caps a sweep (the next
    one starts at the files' start again), remapped through the
    ``vocab.eval_view()`` taken once the epoch may be cut. ``counters``:
    the prefix the plane's counts carry; ``loop``: the consumer's, which
    its ``feed/place`` spans count under (``<loop>/place_seconds``).

    ``hold``: a barrier can change what the next epoch's batches are,
    or the consumer wants the next epoch's builders out of the way of
    its own work behind a mark (a sweep's drain): the caller's
    predicate. Nothing of epoch e + 1 is then cut until the consumer
    has called ``release(e)``, and ``uniq_bucket()`` is read after
    that. ``place``: ``place_ahead``'s. ``stats(e)``: the
    ``SpillStats`` of an epoch whose mark has not been taken yet (a
    loop that stops inside it). ``marked``: the newest epoch whose mark
    the consumer has taken. ``close()`` stops every thread of the feed
    within ``_read_ahead``'s bound and lets go of what was placed.
    Counts ``<counters>/epochs_fed_ahead``: barriers the loop came out
    of (``release``) with the next epoch's first batch already out of
    the builders."""

    def __init__(self, cfg: FmConfig, files: Sequence[str], epochs: range,
                 place, hold: bool, uniq_bucket,
                 weight_files: Sequence[str] = (), shard_index: int = 0,
                 num_shards: int = 1, fixed_shape: bool = False,
                 raw_ids: bool = False,
                 bad_lines: Optional[BadLineTracker] = None, vocab=None,
                 row_shards: Optional[RowShards] = None,
                 training: bool = True, counters: str = TRAIN_PLANE,
                 loop: str = "train",
                 max_batches: Optional[int] = None):
        from fast_tffm_tpu.obs.telemetry import active, feed_counters
        if max_batches and fixed_shape:
            raise ValueError("a capped epoch counts groups as batches; "
                             "under fixed shapes a spill re-cuts them")
        self._cfg, self._files, self._epochs = cfg, files, epochs
        self._hold, self._uniq_bucket = hold, uniq_bucket
        self._training, self._counters = training, counters
        self._max_batches = max_batches or None
        # The map an epoch's batches are remapped through: a training
        # epoch's the runtime itself, a sweep's the view _open takes.
        self._vocab = self._view = vocab
        self._build_cfg = cfg if vocab is None else vocab.build_cfg(cfg)
        self._plane = dict(
            weight_files=weight_files, shard_index=shard_index,
            num_shards=num_shards, fixed_shape=fixed_shape,
            raw_ids=raw_ids, bad_lines=bad_lines,
            row_shards=row_shards if vocab is None else None)
        self._cv = threading.Condition()
        self._closed = False
        self._released = epochs.start - 1   # barriers the loop is past
        self._first_out = epochs.start - 1  # newest epoch with a batch out
        self.marked = epochs.start - 1
        self._stats: Dict[int, SpillStats] = {}
        self._tel = active()
        self._fed_ahead = counters + "/epochs_fed_ahead"
        if self._tel is not None:
            # From 0, whichever route the plane takes: a window in
            # which no stage waited reads 0.0 and not nothing.
            for name in (self._fed_ahead, *feed_counters(
                    counters, None if place is None else loop)):
                self._tel.count(name, 0)
        self._it = place_ahead(
            prefetch(self._host_batches(), depth=cfg.prefetch_depth,
                     gil_bound=gil_bound_iteration(cfg, weight_files),
                     counters=counters),
            place, cfg.prefetch_depth, loop)

    # -- the consumer's side ---------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        item = next(self._it)
        if isinstance(item, EpochMark):
            self.marked = item.epoch
            with self._cv:
                self._stats.pop(item.epoch, None)
        return item

    def release(self, epoch: int) -> None:
        """The loop is past ``epoch``'s barrier and about to ask for the
        next epoch's first batch: fed ahead, if that batch has left the
        builders by now (never where the feed was held until this). A
        sweep's barrier is the training interval behind it: its consumer
        (train.py ``evaluate``) says ``release(marked)`` as the next
        sweep starts, which is where this counts, and once before,
        behind the sweep's drain, where no barrier changes the next
        sweep's batches: the feed cuts them from then on."""
        with self._cv:
            fed_ahead = self._first_out > epoch
            self._released = epoch
            self._cv.notify_all()
        if fed_ahead and self._tel is not None:
            self._tel.count(self._fed_ahead)

    def stats(self, epoch: int) -> SpillStats:
        with self._cv:
            return self._stats.get(epoch) or SpillStats()

    def close(self) -> None:
        with self._cv:  # first: a producer held at a mark wakes and ends
            self._closed = True
            self._cv.notify_all()
        self._it.close()

    # -- the producers' side ---------------------------------------------

    def _budget(self) -> int:
        """The unique budget as the session has it now."""
        return self._uniq_bucket() or self._build_cfg.uniq_bucket

    def _seed(self, epoch: int) -> Optional[int]:
        return self._cfg.seed + epoch if self._training else None

    def _past_barrier(self, epoch: int) -> bool:
        """Wait until the loop is past ``epoch``'s barrier (at once
        where nothing holds the feed there); False where the feed was
        closed meanwhile."""
        with self._cv:
            while (self._hold and self._released < epoch
                   and not self._closed):
                self._cv.wait()
            return not self._closed

    def _open(self, epoch: int) -> Optional[SpillStats]:
        """``epoch``'s stats once it may be cut, None where the feed
        was closed meanwhile."""
        if not self._past_barrier(epoch - 1):
            return None
        if self._vocab is not None and not self._training:
            # A telemetry-silent snapshot (a held-out sweep's unique
            # tail is mostly unadmitted and would inflate the training
            # stream's cold-hit rate), taken after the barrier that
            # admits and evicts: a vocab's feed is held, so the last
            # sweep's batches are all through the old one by now.
            self._view = self._vocab.eval_view()
        with self._cv:
            stats = self._stats[epoch] = SpillStats()
        return stats

    def _remap(self, batch):
        return self._view.remap(batch)

    def _host_batches(self) -> Iterator:
        """The batches as the placement stage takes them, behind
        ``batch_iterator``'s seam. A held feed waits here too, outside
        the seam's build bracket."""
        it = _seamed(self._stream(), self._cfg,
                     None if self._vocab is None else self._remap,
                     self._counters)
        epoch, first = self._epochs.start, True
        try:
            for item in it:
                if isinstance(item, EpochMark):
                    yield item
                    epoch, first = item.epoch + 1, True
                    if (epoch < self._epochs.stop
                            and not self._past_barrier(item.epoch)):
                        return
                    continue
                if first:
                    first = False
                    with self._cv:
                        self._first_out = epoch
                yield item
        finally:
            it.close()

    def _stream(self) -> Iterator:
        cfg, plane = self._build_cfg, self._plane
        fixed_shape = plane["fixed_shape"]
        workers = host_parallel_workers(cfg, plane["weight_files"], False,
                                        fixed_shape)
        if (workers > 1 and _fast_path_eligible(cfg, plane["weight_files"])
                and _proven_builder(
                    cfg, cfg.batch_size, plane["raw_ids"], False,
                    fixed_shape, self._budget(),
                    plane["row_shards"]) is not None):
            return self._ring_stream(workers)
        return self._chained_stream()

    def _chained_stream(self) -> Iterator:
        for epoch in self._epochs:
            stats = self._open(epoch)
            if stats is None:
                return
            it = _batch_iterator_impl(
                self._build_cfg, self._files, training=self._training,
                epochs=1, seed=self._seed(epoch),
                uniq_bucket=self._budget(), stats=stats,
                counters=self._counters, **self._plane)
            try:
                yield from itertools.islice(it, self._max_batches)
            finally:  # a capped epoch's, or the feed's close: the
                it.close()  # iterator's pool and files go with it
            yield EpochMark(epoch, stats)

    def _ring_stream(self, workers: int) -> Iterator:
        cfg, plane = self._build_cfg, self._plane
        files = expand_files(self._files)
        B, shuffle = cfg.batch_size, self._training and cfg.shuffle
        fixed_shape, row_shards = plane["fixed_shape"], plane["row_shards"]
        _refuse_raw_fixed(plane["raw_ids"], fixed_shape)
        retry = RetryPolicy.from_config(cfg)
        # A budget's builder factory, the same object while the budget
        # stands: how a worker knows its builder is still the right one.
        makers: Dict[int, functools.partial] = {}

        def make_builder(uniq_bucket: int):
            if uniq_bucket not in makers:
                makers[uniq_bucket] = _worker_builder_factory(
                    cfg, B, plane["raw_ids"], False, fixed_shape,
                    uniq_bucket, workers, row_shards)
            return makers[uniq_bucket]

        def epochs() -> Iterator[_Epoch]:
            for epoch in self._epochs:
                stats = self._open(epoch)
                if stats is None:
                    return
                uniq_bucket = self._budget()
                seed = self._seed(epoch)
                yield _Epoch(
                    epoch,
                    _BatchEmitter(cfg, B, effective_L_cap(cfg), fixed_shape,
                                  uniq_bucket, shuffle, seed, stats,
                                  shards=row_shards,
                                  counters=self._counters),
                    _GroupScanner(epoch_file_order(files, shuffle, seed, 0),
                                  plane["shard_index"], plane["num_shards"],
                                  B, False, retry,
                                  counters=self._counters),
                    make_builder(uniq_bucket), self._max_batches)

        first_bucket = self._budget()
        return _ring_batches(
            epochs(), make_builder(first_bucket), workers,
            bool(fixed_shape and first_bucket), plane["num_shards"],
            self._counters, marks=True, hold=self._hold)


def _read_ahead(iterator: Iterator, depth: int, name: str,
                counters: Optional[str] = None,
                get_wait: bool = True) -> Iterator:
    """``iterator`` run on a daemon thread called ``name``, at most
    ``depth`` items ahead of the consumer; what it raises is raised
    here. Shared by prefetch() (batches ahead of the step loop), the
    parallel plane's group scanner (groups ahead of the build ring) and
    place_ahead() (placed batches ahead of the step loop). Closing it
    stops the thread, closes ``iterator`` on the thread that ran it (a
    generator's ``finally`` blocks stop the stages behind it) and waits
    for the thread, bounded: what it held is let go before the caller
    goes on.

    The one seam where the feed hands an item from a thread to the
    next, so where who waited for whom is counted. ``counters`` (a
    plane's prefix; ``name`` with ``-`` folded to ``_``):
    ``<counters>/<name>_put_wait_seconds`` on the producing thread, the
    time this stage stood BLOCKED with an item in hand and no room (the
    stage behind it is slower), and ``<counters>/<name>_get_wait_seconds``
    on the consuming thread, the time the consumer was STARVED by this
    stage (``get_wait`` False: the consumer times that wait itself).
    A hand-over that does not wait reads no clock. Spans with
    ``leaf=False``: counters and JSONL events, never profiler
    annotations, because a healthy feed's stages are blocked nearly all
    the time and a device's idle gap must not be named after a blocked
    worker; on the timeline a stage waits where its work span is not
    open. No ``counters``: nothing is counted and no clock read."""
    import queue
    import threading
    from fast_tffm_tpu.obs.trace import span

    q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
    sentinel = object()
    stop = threading.Event()
    errbox: List[BaseException] = []
    put_wait = starved = None
    if counters:
        stage = counters + "/" + name.replace("-", "_")
        put_wait = stage + "_put_wait"
        starved = stage + "_get_wait" if get_wait else None

    def waiting(what: Optional[str]):
        if what is None:
            return contextlib.nullcontext()
        return span(what, seconds=what + "_seconds", leaf=False)

    def put(item) -> None:
        """Bounded put + stop checks so an abandoned consumer (step
        raised, caller broke out) can't strand this thread blocked
        forever holding file handles/batches."""
        try:
            q.put_nowait(item)
            return
        except queue.Full:
            pass
        with waiting(put_wait):
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def worker():
        try:
            try:
                for item in iterator:
                    put(item)
                    if stop.is_set():
                        return
            finally:
                close = getattr(iterator, "close", None)
                if close is not None:
                    close()
        except BaseException as e:  # re-raised on the consumer side
            errbox.append(e)
        finally:
            # Same bounded-put dance: a live consumer must get the
            # sentinel, a gone one (stop set) must not block us.
            while not stop.is_set():
                try:
                    q.put(sentinel, timeout=0.1)
                    break
                except queue.Full:
                    continue

    # Named thread: span events from the pipeline carry the thread name
    # as their Perfetto track (tools/fmtrace).
    thread = threading.Thread(target=worker, name=name, daemon=True)
    thread.start()
    try:
        while True:
            try:
                item = q.get_nowait()
            except queue.Empty:
                with waiting(starved):
                    item = q.get()
            if item is sentinel:
                if errbox:
                    raise errbox[0]
                return
            yield item
    finally:
        stop.set()
        try:  # a put that waits for room goes through, and sees the stop
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        thread.join(timeout=5.0)


def _salvage_block(lines: Sequence[str], cfg: FmConfig,
                   keep_empty: bool,
                   bads: List[Tuple[int, str, str]]) -> ParsedBlock:
    """The ONE cfg -> parse_lines_salvage plumbing (tolerant block
    parse; cparser). Every tolerant call site goes through here so a
    future parser knob can't be threaded into one site and missed in
    another."""
    from fast_tffm_tpu.data.cparser import parse_lines_salvage
    return parse_lines_salvage(
        lines, cfg.vocabulary_size,
        hash_feature_id=cfg.hash_feature_id,
        field_aware=cfg.model_type == "ffm", field_num=cfg.field_num,
        max_features_per_example=cfg.max_features_per_example,
        keep_empty=keep_empty, bad_lines=bads)


def _parse_block(lines: Sequence[str], cfg: FmConfig, fast_parse,
                 keep_empty: bool = False,
                 salvage: bool = False) -> ParsedBlock:
    from fast_tffm_tpu.data.parser import parse_lines
    field_aware = cfg.model_type == "ffm"
    if salvage:
        # Tolerant re-parse (the generic path's spill split re-parses
        # survivor lines whose bad neighbors were already recorded):
        # bad lines drop silently instead of raising.
        return _salvage_block(lines, cfg, keep_empty, [])
    from fast_tffm_tpu.data import cparser
    if fast_parse is not None and cparser.available():
        return fast_parse(
            lines, cfg.vocabulary_size,
            hash_feature_id=cfg.hash_feature_id,
            field_aware=field_aware, field_num=cfg.field_num,
            max_features_per_example=cfg.max_features_per_example,
            keep_empty=keep_empty)
    return parse_lines(
        lines, cfg.vocabulary_size, hash_feature_id=cfg.hash_feature_id,
        field_aware=field_aware, field_num=cfg.field_num,
        max_features_per_example=cfg.max_features_per_example,
        keep_empty=keep_empty)
