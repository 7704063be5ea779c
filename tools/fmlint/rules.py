"""fmlint rules — the hot-loop device-fetch/print invariants.

Scope: HOT_MODULES below — the modules whose loops dispatch (or feed)
the jitted step stream. Everything else may fetch scalars freely; the
bench and tools print by design.
"""

from __future__ import annotations

import ast
from typing import Iterator, List

from tools.fmlint.core import Finding

# The hot-loop surface (ISSUE 2 satellite): the train/predict drivers,
# the batch pipeline, and the whole telemetry layer (obs/ must never
# cause the stalls it exists to measure).
HOT_MODULE_SUFFIXES = (
    "fast_tffm_tpu/train.py",
    "fast_tffm_tpu/predict.py",
    "fast_tffm_tpu/data/pipeline.py",
)
HOT_PACKAGE_FRAGMENTS = ("fast_tffm_tpu/obs/",)


def is_hot_module(path: str) -> bool:
    p = path.replace("\\", "/")
    return (p.endswith(HOT_MODULE_SUFFIXES)
            or any(frag in p for frag in HOT_PACKAGE_FRAGMENTS))


def _loops(tree: ast.AST) -> Iterator[ast.AST]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
            yield node


def r001_scalar_fetch(path: str, tree: ast.AST) -> List[Finding]:
    """float(x)/int(x) inside any loop body, and .item() anywhere, in
    hot modules: each is a synchronous per-scalar device->host fetch
    when x is a device array — one such fetch in the hot stream stalls
    the async dispatch pipeline until the device has caught up.
    Host-value exceptions carry a justified pragma; bulk paths go through utils/fetch.bulk_fetch."""
    if not is_hot_module(path):
        return []
    found: List[Finding] = []
    in_loop: set = set()
    for loop in _loops(tree):
        for node in ast.walk(loop):
            in_loop.add(id(node))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if (isinstance(f, ast.Name) and f.id in ("float", "int")
                and len(node.args) == 1
                and not isinstance(node.args[0], ast.Constant)
                and id(node) in in_loop):
            found.append(Finding(
                "R001", path, node.lineno,
                f"{f.id}() in a hot-loop body is a per-scalar device "
                "fetch if its argument is a device array; buffer and "
                "bulk_fetch at a barrier, or justify with a pragma"))
        if (isinstance(f, ast.Attribute) and f.attr == "item"
                and not node.args):
            found.append(Finding(
                "R001", path, node.lineno,
                ".item() is a per-scalar device fetch on device "
                "arrays; buffer and bulk_fetch at a barrier, or "
                "justify with a pragma"))
    return found


def r002_bare_print(path: str, tree: ast.AST) -> List[Finding]:
    """print() in hot modules: blocks the dispatch loop on stdout and
    bypasses the logging/telemetry sinks (get_logger / obs)."""
    if not is_hot_module(path):
        return []
    found: List[Finding] = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "print"):
            found.append(Finding(
                "R002", path, node.lineno,
                "bare print() in a hot-loop module; use "
                "utils.logging.get_logger or the obs/ sink"))
    return found


def r003_raw_perf_counter(path: str, tree: ast.AST) -> List[Finding]:
    """time.perf_counter() inside a loop body in hot modules: the
    hand-rolled version of span timing. obs/trace.span() is a no-op
    when no run traces (one module-global read), emits into the same
    JSONL stream fmtrace replays, and can't be forgotten half-paired.
    Raw timing that feeds an always-on aggregate (a telemetry counter/
    histogram) is legitimate — justify it with a pragma."""
    if not is_hot_module(path):
        return []
    in_loop: set = set()
    for loop in _loops(tree):
        for node in ast.walk(loop):
            in_loop.add(id(node))
    found: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or id(node) not in in_loop:
            continue
        f = node.func
        named = (isinstance(f, ast.Attribute) and f.attr == "perf_counter"
                 ) or (isinstance(f, ast.Name) and f.id == "perf_counter")
        if named:
            found.append(Finding(
                "R003", path, node.lineno,
                "raw perf_counter() in a hot-loop body; use the "
                "no-op-when-inactive obs.trace.span() for timeline "
                "timing, or justify an aggregate-feeding timer with "
                "a pragma"))
    return found


def _is_broad_handler(node: ast.ExceptHandler) -> bool:
    """Bare ``except:``, ``except Exception:``/``BaseException:``, or a
    tuple containing either."""
    t = node.type
    if t is None:
        return True
    names = []
    if isinstance(t, ast.Tuple):
        names = [e.id for e in t.elts if isinstance(e, ast.Name)]
    elif isinstance(t, ast.Name):
        names = [t.id]
    return any(n in ("Exception", "BaseException") for n in names)


def r004_swallowed_exception(path: str, tree: ast.AST) -> List[Finding]:
    """Broad swallow-and-continue in hot modules: a bare/``Exception``
    handler whose body is only ``pass``/``continue`` turns an
    unexpected failure — a wedged filesystem, a poisoned batch, a
    telemetry bug — into silence exactly where the fault-tolerance
    layer needs a counter, a health event, or a loud abort
    (data/badlines.py, utils/retry.py give it both). Narrow handlers
    (``except ParseError:``, ``except FileNotFoundError:``) are fine:
    they document the one expected failure they absorb. Deliberate
    broad swallows (a watchdog that must outlive its own bugs) carry
    a justified pragma."""
    if not is_hot_module(path):
        return []
    found: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        body_swallows = all(isinstance(s, (ast.Pass, ast.Continue))
                            for s in node.body)
        if body_swallows and _is_broad_handler(node):
            found.append(Finding(
                "R004", path, node.lineno,
                "broad except swallows and continues; narrow the "
                "exception type, count/emit the failure (obs/, "
                "data/badlines), or justify with a pragma"))
    return found


def r005_ckpt_delete(path: str, tree: ast.AST) -> List[Finding]:
    """``os.remove``/``os.unlink``/``shutil.rmtree`` aimed at
    checkpoint state OUTSIDE checkpoint.py: quarantine-not-delete is
    the state-plane invariant (a bad step dir is renamed
    ``corrupt-<step>`` so the bytes survive for forensics/recovery;
    only ``fmckpt gc`` — an explicit operator action — reclaims them).
    Heuristic: the deleted path's source expression mentions a
    checkpoint (``ckpt``) or a step dir. Applies to every linted
    module, not just hot ones — a cold cleanup path deleting a
    checkpoint is exactly as fatal. Deliberate deletions carry a
    justified pragma, as with R001–R004."""
    p = path.replace("\\", "/")
    if p.endswith("checkpoint.py"):
        return []
    found: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in ("remove",
                                                       "unlink",
                                                       "rmtree"):
            name = f.attr
        elif isinstance(f, ast.Name) and f.id == "rmtree":
            name = f.id
        else:
            continue
        try:
            arg_src = ast.unparse(node.args[0])
        except Exception:  # noqa: BLE001 - unparsable arg: skip
            continue
        low = arg_src.lower()
        if "ckpt" in low or "step_dir" in low:
            found.append(Finding(
                "R005", path, node.lineno,
                f"{name}() on a checkpoint path outside checkpoint.py "
                "breaks the quarantine-not-delete invariant; rename to "
                "corrupt-<step> (CheckpointState.quarantine_step) or "
                "justify with a pragma"))
    return found


# R006 scope: the modules whose blocking host collectives can park a
# whole cluster — the drivers, the lockstep protocol, the restore
# broadcasts, and (post the wire/stream PRs) the data plane's own
# agreement primitives: data/stream.py OWNS broadcast_blob /
# allgather_blob, and wire.py is the packed-transfer layer those
# payloads ride. parallel/liveness.py is the guard's own
# implementation (it receives collectives as arguments, never names
# them bare).
R006_MODULE_SUFFIXES = (
    "fast_tffm_tpu/train.py",
    "fast_tffm_tpu/predict.py",
    "fast_tffm_tpu/checkpoint.py",
    "fast_tffm_tpu/data/stream.py",
    "fast_tffm_tpu/wire.py",
)
R006_PACKAGE_FRAGMENTS = ("fast_tffm_tpu/parallel/",)
R006_COLLECTIVES = ("process_allgather", "broadcast_one_to_all",
                    "sync_global_devices")


def r006_unguarded_collective(path: str, tree: ast.AST) -> List[Finding]:
    """A bare blocking host collective (``process_allgather``,
    ``broadcast_one_to_all``, ``sync_global_devices``) CALLED outside
    ``guarded_collective()`` in the cluster-critical modules: one dead
    or wedged peer parks every caller of such a collective forever —
    the hang-forever failure mode the deadline guards exist to remove
    (parallel/liveness.py). Pass the collective INTO
    ``guarded_collective(multihost_utils.process_allgather, ...)`` —
    referencing the function is fine, calling it bare is the finding.
    Deliberate unguarded calls carry a justified pragma."""
    p = path.replace("\\", "/")
    in_scope = (p.endswith(R006_MODULE_SUFFIXES)
                or any(frag in p for frag in R006_PACKAGE_FRAGMENTS))
    if not in_scope or p.endswith("parallel/liveness.py"):
        return []
    found: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = None
        if isinstance(f, ast.Attribute) and f.attr in R006_COLLECTIVES:
            name = f.attr
        elif isinstance(f, ast.Name) and f.id in R006_COLLECTIVES:
            name = f.id
        if name is None:
            continue
        found.append(Finding(
            "R006", path, node.lineno,
            f"bare {name}() blocks forever on a dead peer; run it "
            "under parallel.liveness.guarded_collective(fn, ...) so a "
            "lost worker raises a named WorkerLostError, or justify "
            "with a pragma"))
    return found


# R011 scope: every linted module EXCEPT the two that ARE the
# embedding-storage seam — lookup.py (the backend gather/apply/reset
# surface) and the vocab/ package (the slot map itself).
R011_EXEMPT_SUFFIXES = ("fast_tffm_tpu/lookup.py",)
R011_EXEMPT_FRAGMENTS = ("fast_tffm_tpu/vocab/",)


def r011_raw_table_index(path: str, tree: ast.AST) -> List[Finding]:
    """Direct integer indexing into the embedding table (``table[ids]``
    or ``x.table[ids]``) outside lookup.py/vocab/: with ``vocab_mode =
    admit`` every id must route through the slot-indirection seam
    (vocab.VocabMap.remap / a lookup backend's gather) — a raw gather
    on unmapped ids is how eviction bugs are born: it reads rows the
    slot map may have reassigned or reset. Plain slices
    (``table[:n]``, checkpoint layout trims) are fine — they address
    LAYOUT, not ids. The jitted math that runs BELOW the seam (the
    batch reaching it is already physical-space) carries the usual
    justified pragma."""
    p = path.replace("\\", "/")
    if (p.endswith(R011_EXEMPT_SUFFIXES)
            or any(frag in p for frag in R011_EXEMPT_FRAGMENTS)):
        return []
    found: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Subscript):
            continue
        v = node.value
        named_table = ((isinstance(v, ast.Name) and v.id == "table")
                       or (isinstance(v, ast.Attribute)
                           and v.attr == "table"))
        if not named_table:
            continue
        def _layout(e) -> bool:
            # Slices and fixed rows address LAYOUT, not id routing.
            # Negative constants (table[-1], the dead tail row) parse
            # as UnaryOp(USub, Constant), not Constant.
            return (isinstance(e, (ast.Slice, ast.Constant))
                    or (isinstance(e, ast.UnaryOp)
                        and isinstance(e.op, ast.USub)
                        and isinstance(e.operand, ast.Constant)))

        sl = node.slice
        if _layout(sl):
            continue
        if isinstance(sl, ast.Tuple) and all(_layout(e)
                                             for e in sl.elts):
            continue
        found.append(Finding(
            "R011", path, node.lineno,
            "direct indexing into the embedding table bypasses the "
            "slot-indirection seam (vocab_mode = admit remaps ids to "
            "physical rows); gather through lookup.py / remap through "
            "vocab.VocabMap, or justify with a pragma"))
    return found


# R013 scope: the device-bound dispatch surfaces — train, predict, the
# scoring core, and the serving process. Every batch crossing the
# host->device wall there must route through the ONE wire-format
# encoder (fast_tffm_tpu/wire.py WireEncoder): an ad-hoc
# jax.device_put of raw [B, L] rectangles bypasses the packed format,
# the double-buffered dispatch, AND the h2d byte accounting at once.
# wire.py itself (the encoder's own put) is out of scope by
# construction.
R013_MODULE_SUFFIXES = (
    "fast_tffm_tpu/train.py",
    "fast_tffm_tpu/predict.py",
    "fast_tffm_tpu/scoring.py",
)
R013_PACKAGE_FRAGMENTS = ("fast_tffm_tpu/serve/",)


def r013_adhoc_device_put(path: str, tree: ast.AST) -> List[Finding]:
    """Ad-hoc ``jax.device_put`` (or a bare imported ``device_put``)
    in a device-bound dispatch module: batch arrays must cross the
    wall through the wire encoder (``WireEncoder.device_put`` after
    ``encode_train``/``encode_score``) so the packed format, the
    depth-2 double buffer, and the ``train/h2d_bytes`` accounting all
    see the same arrays. Non-batch payloads (a warmup probe scalar)
    carry the usual justified pragma."""
    p = path.replace("\\", "/")
    if not (p.endswith(R013_MODULE_SUFFIXES)
            or any(frag in p for frag in R013_PACKAGE_FRAGMENTS)):
        return []
    found: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        adhoc = ((isinstance(f, ast.Name) and f.id == "device_put")
                 or (isinstance(f, ast.Attribute)
                     and f.attr == "device_put"
                     and isinstance(f.value, ast.Name)
                     and f.value.id in ("jax", "jnp")))
        if not adhoc:
            continue
        found.append(Finding(
            "R013", path, node.lineno,
            "ad-hoc device_put in a dispatch module bypasses the wire-"
            "format layer (packed encoding, double buffering, h2d byte "
            "accounting); route batches through wire.WireEncoder "
            "(encode_train/encode_score + .device_put), or justify "
            "with a pragma"))
    return found


# R018 scope: everywhere except the one memory seam. The runtime's
# memory introspection (memory_stats / live_arrays) must route through
# fast_tffm_tpu/obs/memory.device_memory_stats so the unmeasured-is-
# None policy, the CPU-backend opt-out, and the FM_FAKE_HBM_BYTES test
# injection hold at EVERY consumer — a direct call site sees real
# stats where a test injected fake ones, and branches a capacity
# decision the chaos suite cannot reach. The seam module itself is out
# of scope by construction (same shape as R013's one-encoder rule).
R018_SEAM_SUFFIX = "fast_tffm_tpu/obs/memory.py"
R018_CALLS = ("memory_stats", "live_arrays")


def r018_adhoc_memory_stats(path: str, tree: ast.AST) -> List[Finding]:
    """Direct ``memory_stats()`` / ``live_arrays()`` outside the
    obs/memory seam: capacity reads must share one policy (None when
    unmeasured, CPU opt-out, fake-capacity injection). Justified
    pragma for genuinely raw probes."""
    p = path.replace("\\", "/")
    if p.endswith(R018_SEAM_SUFFIX):
        return []
    found: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        adhoc = ((isinstance(f, ast.Name) and f.id in R018_CALLS)
                 or (isinstance(f, ast.Attribute)
                     and f.attr in R018_CALLS))
        if not adhoc:
            continue
        found.append(Finding(
            "R018", path, node.lineno,
            "direct device-memory introspection bypasses the one "
            "memory seam (obs/memory.device_memory_stats): the "
            "unmeasured-is-None policy, the CPU-backend opt-out, and "
            "the FM_FAKE_HBM_BYTES injection only hold through the "
            "seam; route through it, or justify with a pragma"))
    return found


RULES = (r001_scalar_fetch, r002_bare_print, r003_raw_perf_counter,
         r004_swallowed_exception, r005_ckpt_delete,
         r006_unguarded_collective, r011_raw_table_index,
         r013_adhoc_device_put, r018_adhoc_memory_stats)
