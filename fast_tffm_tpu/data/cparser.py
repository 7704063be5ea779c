"""ctypes loader for the C++ parser extension (``_parser.cc``).

The reference keeps line parsing in a C++ TF op because at target
throughput (SURVEY.md §7 hard part #4: ~280k lines/s/host-group) Python
string handling is the bottleneck. Here the same role is played by a plain
shared object built from ``_parser.cc`` with g++ on first use (no TF/pybind
dependency; see SURVEY §7 layer 2). ``parse_lines_fast`` matches
``parser.parse_lines``'s contract bit-for-bit (golden tests enforce it).

The binary is built with ``-march=native``, so it is only ever loaded
under a name that carries its build key: a hash of the source, the
compiler flags and this CPU's identity (``artifact_path``). A binary
copied in with the tree from another CPU, or left over from an older
source, has another name and is never dlopen'ed; the loader builds its
own beside it. Binaries under other keys are left where they are (on a
shared tree another host's CPU may own them); ``make clean`` removes
them all.

If the extension cannot be built/loaded, ``available()`` says so ONCE,
at WARNING, and callers fall back to the Python parser.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

from fast_tffm_tpu.data.parser import ParsedBlock, ParseError

# The run logger by NAME (utils.logging.get_logger configures it): this
# module stays importable without jax, which utils/ pulls in.
_log = logging.getLogger("fast_tffm_tpu")


def _tel():
    """The active run telemetry (obs/), or None. Parser-level counters
    (lines parsed, parse errors, bytes fed) live HERE — the one layer
    that sees every line regardless of which pipeline path consumed it.
    Lazy import: this module must stay importable without obs/ costs
    when telemetry is off."""
    from fast_tffm_tpu.obs.telemetry import active
    return active()

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "_parser.cc")
# The artifact's stem: the loaded file is ``_parser.<build key>.so``.
_SO = os.path.join(_HERE, "_parser.so")
_CXXFLAGS = ("-O3", "-march=native", "-std=c++17", "-shared", "-fPIC",
             "-pthread")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_error: Optional[str] = None


def _cpu_identity() -> str:
    """What ``-march=native`` resolved against on this machine: the
    first processor's model and ISA feature lines, plus the machine
    type (the whole answer where /proc/cpuinfo does not exist)."""
    keys = ("model name", "flags", "Features", "CPU implementer",
            "CPU part")
    found = {}
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                k, _, v = line.partition(":")
                k = k.strip()
                if k in keys and k not in found:
                    found[k] = " ".join(sorted(v.split()))
                if not line.strip() and found:
                    break  # end of the first processor block
    except OSError:
        pass
    return platform.machine() + "|" + "|".join(
        f"{k}={found[k]}" for k in keys if k in found)


def build_key() -> str:
    """Hash of everything the binary depends on: source bytes, compiler
    flags, CPU identity."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as fh:
        h.update(fh.read())
    h.update(" ".join(_CXXFLAGS).encode())
    h.update(_cpu_identity().encode())
    return h.hexdigest()[:16]


def artifact_path() -> str:
    """``_parser.<build key>.so`` — the only name the loader opens and
    the Makefile builds (``python -m fast_tffm_tpu.data.cparser``)."""
    stem, ext = os.path.splitext(_SO)
    return f"{stem}.{build_key()}{ext}"


def _build(out: str) -> None:
    # Build to a temp name and os.replace: atomic for concurrent
    # processes, and never rewrites a live mmap in place.
    tmp = f"{out}.tmp.{os.getpid()}"
    cmd = ["g++", *_CXXFLAGS, "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


# Must equal fm_abi_version() in _parser.cc. Bump both together whenever
# an exported signature changes.
_ABI_VERSION = 11


def _open_checked(path: str) -> ctypes.CDLL:
    """dlopen the .so and verify every symbol exists AND the compiled-in
    ABI version matches this wrapper. The binary was built from THIS
    source (its name says so), so a mismatch means wrapper and source
    disagree and no rebuild can help: RuntimeError."""
    lib = ctypes.CDLL(path)
    try:
        for sym in ("fm_abi_version", "fm_auto_threads", "fm_parse_block",
                    "fm_dedup_ids", "fm_scan_examples", "fm_bb_new",
                    "fm_bb_feed", "fm_bb_peek", "fm_bb_finish",
                    "fm_bb_row_shards", "fm_bb_uniq", "fm_bb_cells",
                    "fm_bb_truncated", "fm_bb_free"):
            getattr(lib, sym)
        lib.fm_abi_version.restype = ctypes.c_int64
        lib.fm_abi_version.argtypes = []
        ok = lib.fm_abi_version() == _ABI_VERSION
    except AttributeError:
        ok = False
    if not ok:
        raise RuntimeError(
            f"{path} is a stale ABI: _parser.cc and the wrapper's "
            f"_ABI_VERSION = {_ABI_VERSION} disagree")
    return lib


def _load() -> ctypes.CDLL:
    global _lib, _load_error
    with _lock:
        if _lib is not None:
            return _lib
        if _load_error is not None:
            raise RuntimeError(_load_error)
        try:
            path = artifact_path()
            built = not os.path.exists(path)
            if built:
                _build(path)
            lib = _open_checked(path)
        except (OSError, subprocess.CalledProcessError,
                RuntimeError) as e:
            detail = (e.stderr or "").strip()[-500:] if isinstance(
                e, subprocess.CalledProcessError) else ""
            _load_error = f"C++ parser unavailable: {e} {detail}".strip()
            _log.warning(
                "%s — host parsing falls back to the PYTHON parser, "
                "far below the C++ rate", _load_error)
            raise RuntimeError(_load_error)
        _log.info(
            "host parser: C++ %s (%s)", os.path.basename(path),
            "built here" if built else "build key matched")
        lib.fm_auto_threads.restype = ctypes.c_int
        lib.fm_auto_threads.argtypes = []
        lib.fm_parse_block.restype = ctypes.c_int
        lib.fm_parse_block.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,              # buffer, length
            ctypes.c_int64, ctypes.c_int,                 # vocab, hash flag
            ctypes.c_int, ctypes.c_int64,                 # field flag, count
            ctypes.c_int,                                 # max feats/example
            ctypes.c_int,                                 # keep_empty
            ctypes.c_int,                                 # num threads
            ctypes.POINTER(ctypes.c_int64),               # out: n_examples
            ctypes.POINTER(ctypes.c_int64),               # out: nnz
            ctypes.POINTER(ctypes.c_int64),               # out: truncated
            np.ctypeslib.ndpointer(np.float32),           # labels buf
            np.ctypeslib.ndpointer(np.int32),             # poses buf
            np.ctypeslib.ndpointer(np.int32),             # ids buf
            np.ctypeslib.ndpointer(np.float32),           # vals buf
            np.ctypeslib.ndpointer(np.int32),             # fields buf
            ctypes.c_char_p, ctypes.c_int64,              # err buf, err cap
        ]
        lib.fm_dedup_ids.restype = ctypes.c_int64
        lib.fm_dedup_ids.argtypes = [
            np.ctypeslib.ndpointer(np.int32), ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32),             # uniq out
            np.ctypeslib.ndpointer(np.int32),             # inverse out
        ]
        lib.fm_scan_examples.restype = ctypes.c_int64
        lib.fm_scan_examples.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,              # blob, length
            ctypes.c_int64, ctypes.c_int,                 # n_target, keep
            ctypes.POINTER(ctypes.c_int64),               # out: consumed
            ctypes.POINTER(ctypes.c_int64)]               # out: lines
        lib.fm_bb_new.restype = ctypes.c_void_p
        lib.fm_bb_new.argtypes = [ctypes.c_int64, ctypes.c_int64,
                                  ctypes.c_int64, ctypes.c_int,
                                  ctypes.c_int, ctypes.c_int64,  # field flag, count
                                  ctypes.c_int,                  # raw_ids
                                  ctypes.c_int,                  # keep_empty
                                  ctypes.c_int, ctypes.c_int64,
                                  ctypes.c_int]                  # num_threads
        lib.fm_bb_free.argtypes = [ctypes.c_void_p]
        lib.fm_bb_row_shards.restype = ctypes.c_int
        lib.fm_bb_row_shards.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                         ctypes.c_int64, ctypes.c_int64]
        lib.fm_bb_feed.restype = ctypes.c_int
        lib.fm_bb_feed.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_char_p, ctypes.c_int64]
        lib.fm_bb_peek.restype = ctypes.c_int64
        lib.fm_bb_peek.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),               # n_uniq
            ctypes.POINTER(ctypes.c_int64)]               # max_nnz
        lib.fm_bb_finish.restype = ctypes.c_int64
        lib.fm_bb_finish.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,              # handle, cols
            np.ctypeslib.ndpointer(np.float32),           # labels
            np.ctypeslib.ndpointer(np.int32),             # uniq
            np.ctypeslib.ndpointer(np.int32),             # local_idx
            np.ctypeslib.ndpointer(np.float32),           # vals
            np.ctypeslib.ndpointer(np.int32),             # fields
            ctypes.c_void_p,                              # remap or NULL
            ctypes.c_void_p]                              # perm or NULL
        lib.fm_bb_cells.restype = ctypes.c_int64
        lib.fm_bb_cells.argtypes = [ctypes.c_void_p]
        lib.fm_bb_truncated.restype = ctypes.c_int64
        lib.fm_bb_truncated.argtypes = [ctypes.c_void_p]
        lib.fm_bb_uniq.restype = None
        lib.fm_bb_uniq.argtypes = [ctypes.c_void_p,
                                   np.ctypeslib.ndpointer(np.int32)]
        _lib = lib
        return lib


def available() -> bool:
    """The one C++-or-Python decision, taken (and logged) once per
    process at first use; every routing site asks here."""
    try:
        _load()
        return True
    except RuntimeError:
        return False


def auto_threads() -> int:
    """The parse-thread count a ``num_threads=0`` builder actually uses
    — read from the library (fm_auto_threads) so reporting can't drift
    from the C++ rule. 1 when the extension is unavailable (the generic
    Python path is single-threaded)."""
    try:
        return int(_load().fm_auto_threads())
    except RuntimeError:
        return 1


def parse_lines_fast(lines: Sequence[str], vocabulary_size: int,
                     hash_feature_id: bool = False,
                     field_aware: bool = False, field_num: int = 0,
                     max_features_per_example: int = 0,
                     keep_empty: bool = False,
                     num_threads: int = 0) -> ParsedBlock:
    """C++-accelerated ``parse_lines`` (FM and field-aware FFM formats).
    ``keep_empty`` preserves blank lines as zero-feature label-0
    examples (the predict path's line alignment), matching the Python
    parser bit-for-bit. Raises RuntimeError when the extension is
    unusable, ParseError on malformed input."""
    lib = _load()
    # The output buffers below are sized from len(lines), but the C++
    # side splits the joined blob on '\n' — an EMBEDDED newline in one
    # input string would make it emit more examples than allocated
    # (heap overflow, reproduced as a SIGSEGV). The Python parser
    # treats '\n' inside a line as plain token whitespace (str.split),
    # so mapping it to ' ' preserves bit-for-bit parity while keeping
    # the example count equal to len(lines).
    lines = [ln.replace("\n", " ") if "\n" in ln else ln for ln in lines]
    blob = "\n".join(lines).encode("utf-8")
    if keep_empty and lines:
        # Terminate the final line: "a\nb".split('\n') drops no line in
        # C++, but a trailing EMPTY line ("a\n".join ending in "") is
        # invisible to the newline walk — and under keep_empty every
        # input line owes an example. Harmless otherwise, but only
        # keep_empty NEEDS it, so the strict path's blob stays
        # byte-identical to what it always fed.
        blob += b"\n"
    n_lines = len(lines)
    # Worst-case token count bounds the output buffers: a feature token is
    # at least 2 bytes ("i "), a line at least 2 ("0\n").
    max_nnz = max(len(blob) // 2 + 1, 1)
    labels = np.empty(n_lines, dtype=np.float32)
    poses = np.empty(n_lines + 1, dtype=np.int32)
    ids = np.empty(max_nnz, dtype=np.int32)
    vals = np.empty(max_nnz, dtype=np.float32)
    fields = np.empty(max_nnz if field_aware else 1, dtype=np.int32)
    n_ex = ctypes.c_int64(0)
    nnz = ctypes.c_int64(0)
    cut = ctypes.c_int64(0)
    errbuf = ctypes.create_string_buffer(512)
    rc = lib.fm_parse_block(
        blob, len(blob), vocabulary_size, int(hash_feature_id),
        int(field_aware), field_num,
        max_features_per_example, int(keep_empty), num_threads,
        ctypes.byref(n_ex), ctypes.byref(nnz), ctypes.byref(cut),
        labels, poses, ids, vals, fields, errbuf, len(errbuf))
    tel = _tel()
    if rc != 0:
        if tel is not None:
            tel.count("pipeline/parse_errors")
        raise ParseError(errbuf.value.decode("utf-8", "replace"))
    if tel is not None:
        tel.count("pipeline/lines_parsed", len(lines))
    b = n_ex.value
    z = nnz.value
    return ParsedBlock(labels=labels[:b].copy(), poses=poses[:b + 1].copy(),
                       ids=ids[:z].copy(), vals=vals[:z].copy(),
                       fields=fields[:z].copy() if field_aware else None,
                       truncated=int(cut.value))


def scan_examples(data: bytes, n_target: int, keep_empty: bool = False,
                  offset: int = 0,
                  end: Optional[int] = None) -> "tuple[int, int, int]":
    """Count example-producing lines in the COMPLETE lines of
    ``data[offset:end]`` up to ``n_target``, without parsing: returns
    ``(found, bytes_consumed, lines_consumed)`` where ``bytes_consumed``
    ends at the last counted line's newline (relative to ``offset``)
    and ``lines_consumed`` includes the blank lines inside that span.
    The counting rule is the BatchBuilder's own (C++ ``is_ws``), so the
    parallel data plane's group cutter and the builder can never
    disagree about which lines fill a batch. Raises RuntimeError when
    the extension is unusable. Zero-copy via pointer arithmetic, like
    BatchBuilder.feed."""
    lib = _load()
    base = ctypes.cast(ctypes.c_char_p(data), ctypes.c_void_p).value
    consumed = ctypes.c_int64(0)
    nlines = ctypes.c_int64(0)
    stop = len(data) if end is None else min(int(end), len(data))
    found = lib.fm_scan_examples(ctypes.c_void_p((base or 0) + offset),
                                 max(stop - offset, 0), n_target,
                                 int(keep_empty), ctypes.byref(consumed),
                                 ctypes.byref(nlines))
    return int(found), int(consumed.value), int(nlines.value)


def parse_lines_salvage(lines: Sequence[str], vocabulary_size: int,
                        hash_feature_id: bool = False,
                        field_aware: bool = False, field_num: int = 0,
                        max_features_per_example: int = 0,
                        keep_empty: bool = False,
                        bad_lines: Optional[list] = None) -> ParsedBlock:
    """Tolerant block parse — the per-line failure surface of
    ``bad_line_policy = skip|quarantine`` over the C++ fast path.

    The C++ block parser is all-or-nothing by design (its threads
    abort the failing shard; per-line bookkeeping would slow the
    clean-corpus hot path that is 99.99%+ of production bytes). So
    tolerance is layered: the block goes through the C++ parser first,
    and only a FAILING block is retried through the Python parser's
    per-line tolerant mode, which identifies every bad line (recorded
    into ``bad_lines`` as ``(index, raw, message)``) and returns the
    block minus those lines. Clean blocks pay zero extra cost; a block
    with a bad line pays one Python re-parse of that block only.

    ``keep_empty`` rides the same layering since ABI 7 (fm_parse_block
    grew the blank-line-preserving mode): a clean keep_empty block is
    one C++ pass, and under ``keep_empty`` the Python retry replaces a
    bad line with a zero-feature example instead of dropping it, so
    predict's one-score-per-input-line alignment survives corruption.

    Pool-safe: every buffer here is per-call, the C++ block parser
    holds no global state, and the telemetry counters go through the
    locked registry — the parallel data plane calls this concurrently
    from its build workers (one bad block's Python retry runs on the
    worker that hit it, not a shared salvage structure).
    """
    if bad_lines is None:
        bad_lines = []
    try:
        return parse_lines_fast(
            lines, vocabulary_size,
            hash_feature_id=hash_feature_id,
            field_aware=field_aware, field_num=field_num,
            max_features_per_example=max_features_per_example,
            keep_empty=keep_empty)
    except (OSError, RuntimeError):
        pass  # C++ extension unavailable -> Python handles it all
    except ParseError:
        pass  # failing block -> tolerant Python retry below
    from fast_tffm_tpu.data.parser import parse_lines
    return parse_lines(
        lines, vocabulary_size, hash_feature_id=hash_feature_id,
        field_aware=field_aware, field_num=field_num,
        max_features_per_example=max_features_per_example,
        keep_empty=keep_empty, bad_lines=bad_lines)


class BatchBuilder:
    """Streaming raw-bytes -> padded-batch builder (C++ `fm_bb_*`).

    ``feed(chunk)`` consumes whole lines until the batch holds B
    examples, returning True when full (unconsumed tail bytes of the
    chunk must be re-fed). ``finish()`` returns the padded arrays —
    labels [B], uniq [n_uniq] with slot 0 = pad_id, local_idx [B, L],
    vals [B, L] — and resets for the next batch. One parse pass does
    parse + hash + dedup + padded scatter; there is no per-line Python.

    Concurrency contract (the parallel host data plane relies on it):
    the C++ library keeps ALL state per handle — distinct builders on
    distinct threads never share anything, so a pool of workers each
    OWNING one builder is safe, and every ctypes call releases the GIL
    for its duration. A single handle is NOT internally locked; one
    builder must stay owned by one thread at a time.
    """

    def __init__(self, batch_size: int, max_cols: int,
                 vocabulary_size: int, hash_feature_id: bool = False,
                 field_aware: bool = False, field_num: int = 0,
                 raw_ids: bool = False, keep_empty: bool = False,
                 max_features_per_example: int = 0, max_uniq: int = 0,
                 num_threads: int = 0, row_shards=None):
        """``max_uniq`` > 0 caps the batch's unique-row count (incl. the
        pad slot): a line that would exceed it closes the batch early
        (spill) and opens the next one — the fixed-U protocol for
        multi-process SPMD. Must exceed the per-example feature cap.
        ``field_aware`` parses FFM ``field:fid[:val]`` tokens and makes
        ``finish()`` return a fields array. ``raw_ids`` (dedup=device)
        skips the dedup pass: local_idx holds raw feature ids (pad cells
        = vocabulary_size) and finish() returns uniq=None; incompatible
        with max_uniq. ``keep_empty`` turns blank lines into
        zero-feature examples (label 0) — the predict path's
        one-score-per-input-line alignment. ``num_threads`` sets the
        feed parse-thread count (0 = auto: min(8, cores)); with more
        than one thread each fed chunk is parsed in parallel and
        drained serially, with byte-identical outputs. ``row_shards``
        (a mesh's fixed-U feed): ``(rows per shard, shards, cap)`` — a
        line that would give one row shard more than ``cap`` unique
        rows closes the batch early too, as its rows ship in that
        shard's segment of the bucket (pipeline.segment_plan)."""
        self._lib = _load()
        self.B, self.L = batch_size, max_cols
        self.field_aware = field_aware
        self.raw_ids = raw_ids
        self._h = self._lib.fm_bb_new(batch_size, max_cols,
                                      vocabulary_size,
                                      int(hash_feature_id),
                                      int(field_aware), field_num,
                                      int(raw_ids), int(keep_empty),
                                      max_features_per_example,
                                      max_uniq, num_threads)
        if not self._h:
            # ValueError, not RuntimeError: the extension IS available,
            # the arguments are wrong — callers must not read this as
            # "no C++, use the slow path" and silently degrade.
            raise ValueError("fm_bb_new rejected its arguments (bad "
                             "sizes, or max_uniq <= max feature count "
                             "per example)")
        if row_shards is not None and self._lib.fm_bb_row_shards(
                self._h, *row_shards):
            raise ValueError(
                f"row shards {row_shards} (rows per shard, shards, "
                "unique rows a shard may hold): one example's features "
                "have to fit a shard's segment of uniq_bucket")
        self._err = ctypes.create_string_buffer(512)

    def feed(self, chunk: bytes, offset: int = 0) -> "tuple[bool, int]":
        """Feed ``chunk[offset:]`` (zero-copy via pointer arithmetic —
        the caller re-feeds from a moving offset after each full batch).
        Returns (batch_full, bytes_consumed)."""
        base = ctypes.cast(ctypes.c_char_p(chunk), ctypes.c_void_p).value
        consumed = ctypes.c_int64(0)
        rc = self._lib.fm_bb_feed(self._h,
                                  ctypes.c_void_p((base or 0) + offset),
                                  len(chunk) - offset,
                                  ctypes.byref(consumed), self._err,
                                  len(self._err))
        if rc < 0:
            tel = _tel()
            if tel is not None:
                tel.count("pipeline/parse_errors")
            raise ParseError(self._err.value.decode("utf-8", "replace"))
        tel = _tel()
        if tel is not None:
            # The streaming builder never forms Python lines; bytes fed
            # is its honest parse-volume counter (lines land in
            # pipeline/examples via the batch wrapper).
            tel.count("pipeline/bytes_fed", consumed.value)
        return rc == 1, consumed.value

    def finish(self, cols=None, slots=None, rows=None):
        """-> (n_examples, labels[B], uniq[n_uniq], local_idx[B,C],
        vals[B,C], fields[B,C]-or-None, max_nnz); resets the builder.
        C is ``max_cols``, or ``cols(max_nnz)`` where a caller fits the
        width to the batch's widest example (the builder stages cells
        flat, so a narrow batch is padded out once, to the width it
        ships at). ``slots(uniq, max_nnz) -> (uniq_ids, remap)`` says
        how the unique slots ship (pipeline._BatchEmitter.slots): the
        tuple then holds ``uniq_ids`` for ``uniq``, and where ``remap``
        is not None every cell of ``local_idx`` is ``remap[slot]``,
        re-pointed as the cells are padded out. ``rows(n_examples) ->
        perm`` (or None) gives the shuffle's within-batch order
        (pipeline._BatchEmitter.row_perm): example ``r`` is written at
        row ``perm[r]``, its label with it, as the rows are padded out;
        the padding block stays at the tail. ``self.cells`` then
        holds the batch's feature cells (padding not counted) and
        ``self.truncated`` the feature tokens skipped past the
        per-example cap since the last finish()."""
        self.cells = int(self._lib.fm_bb_cells(self._h))
        self.truncated = int(self._lib.fm_bb_truncated(self._h))
        n_uniq = ctypes.c_int64(0)
        max_nnz = ctypes.c_int64(0)
        n_ex = self._lib.fm_bb_peek(self._h, ctypes.byref(n_uniq),
                                    ctypes.byref(max_nnz))
        perm = None if rows is None else rows(int(n_ex))
        if perm is not None:
            perm = np.ascontiguousarray(perm, np.int32)
            if len(perm) != n_ex:
                raise ValueError(f"finish: a permutation of {len(perm)} "
                                 f"rows for a batch of {n_ex}")
        C = self.L if cols is None else int(cols(int(max_nnz.value)))
        labels = np.empty(self.B, np.float32)
        uniq = np.empty(n_uniq.value, np.int32)
        ships = remap = None
        if slots is not None and not self.raw_ids:
            self._lib.fm_bb_uniq(self._h, uniq)
            ships, remap = slots(uniq, int(max_nnz.value))
            if remap is not None:
                remap = np.ascontiguousarray(remap, np.int32)
        li = np.empty((self.B, C), np.int32)
        vals = np.empty((self.B, C), np.float32)
        fields = np.empty((self.B, C) if self.field_aware else (1, 1),
                          np.int32)
        n = self._lib.fm_bb_finish(
            self._h, C, labels, uniq, li, vals, fields,
            None if remap is None else remap.ctypes.data,
            None if perm is None else perm.ctypes.data)
        if n == -2:
            raise ValueError("finish: rows() gave no permutation of the "
                             "batch's examples")
        if n < 0:
            raise ValueError(f"finish: {C} columns do not hold the "
                             f"batch's widest example ({max_nnz.value}) "
                             f"or exceed max_cols ({self.L})")
        if ships is not None:
            uniq = ships
        return (int(n), labels, None if self.raw_ids else uniq, li, vals,
                fields if self.field_aware else None, int(max_nnz.value))

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.fm_bb_free(h)
            self._h = None


def dedup_ids_fast(ids: np.ndarray):
    """First-occurrence unique + inverse (np.unique(return_inverse=True)
    contract minus sortedness, which callers treat as opaque). ~5x faster
    than the sort-based np.unique on batch-sized id arrays. Raises
    RuntimeError when the extension is unusable."""
    lib = _load()
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    n = len(ids)
    if n == 0:
        return ids[:0], np.zeros(0, dtype=np.int32)
    uniq = np.empty(n, dtype=np.int32)
    inverse = np.empty(n, dtype=np.int32)
    n_uniq = lib.fm_dedup_ids(ids, n, uniq, inverse)
    return uniq[:n_uniq].copy(), inverse


if __name__ == "__main__":
    # The Makefile's build rule: build (if absent) and load the keyed
    # artifact exactly as a run would, then print its path.
    _load()
    print(artifact_path())
