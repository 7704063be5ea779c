"""Device-memory ledger + capacity planner (README "Memory
observability").

The bytes axis of the observability stack: PR 1/2/12/17 cover
counters, spans, SLOs, and cross-rank time, but an oversized
``vocabulary_size`` still died as a raw XLA RESOURCE_EXHAUSTED with no
owner attribution, and a serve hot-reload transiently holds old+new
tables (a silent 2x spike). This module gives every long-lived device
allocation an OWNER:

- **Ledger** (``LEDGER``): each resident allocation the framework
  creates — the embedding table, the Adagrad accumulator, the wire
  double-buffers, prefetched/in-flight batches, lockstep window
  arrays, serve's table and its old+new reload pair — registers with
  an owner tag and host-computed ``nbytes``. ``ledger_gauges()``
  derives the ``mem/*`` gauge rows every telemetry flush carries:
  per-owner bytes, live total, peak watermark, device capacity +
  utilization. Host-int arithmetic only — ZERO device fetches, the
  same contract ``anatomy_gauges`` keeps (pinned by
  tests/test_memory.py).
- **Seam** (``device_memory_stats``): the ONE place the runtime's
  ``memory_stats()`` is consulted (fmlint R018, the memory analogue of
  R013's one-encoder rule). ``FM_FAKE_HBM_BYTES`` injects a capacity
  for tests and the fmchaos ``oom-pressure`` scenario; a backend that
  reports no capacity (the CPU container) reports None and every
  capacity consumer — pre-flight, pressure, the planner's verdict —
  degrades to "unknown", never a fake number.
- **Pressure + forensics**: ``maybe_emit_pressure`` emits
  ``health: hbm_pressure`` ONCE per episode (Watchdog-style episode
  state: crossing ``mem_pressure_fraction`` fires, dropping back below
  re-arms) and ``oom_guard`` re-raises a dispatch-site
  RESOURCE_EXHAUSTED as ``HbmExhaustedError`` carrying the rendered
  per-owner ledger — an OOM names WHICH owner grew.
- **Planner** (``plan`` / ``fmstat capacity``): predicts
  table/accumulator/wire/serve-resident bytes against one device's
  capacity from config alone — with ``--what-if vocabulary_size=N,
  dtype=f16,shards=K`` overrides, so a row-sharded table (``shards``:
  each device holds 1/K of table and accumulator) or a quantized
  resident one (ROADMAP item 4) can be sized from any box.
  ``preflight_capacity`` is the same prediction as a fail-fast guard
  at ScorerServer startup and in the train session, which passes the
  size of the mesh it has just built as ``shards``: the check is per
  device of that mesh.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Dict, List, Optional

# The injected-capacity seam (tests, fmchaos oom-pressure): when set,
# device_memory_stats() reports this many bytes as the capacity and
# the ledger's live total as bytes_in_use, regardless of backend —
# the only way to exercise the capacity paths in the CPU container.
FAKE_CAPACITY_ENV = "FM_FAKE_HBM_BYTES"

F32_BYTES = 4
# What-if dtype names -> bytes per element (ROADMAP item 4's f16/int8
# resident-table sizing rides these).
DTYPE_BYTES = {"f32": 4, "float32": 4, "bf16": 2, "f16": 2,
               "float16": 2, "int8": 1}


def table_bytes(cfg=None, *, rows: Optional[int] = None,
                dim: Optional[int] = None,
                dtype_bytes: int = F32_BYTES) -> int:
    """The one table/accumulator sizing formula (satellite of ISSUE
    18): ``rows * row_dim * 4`` previously lived as four ad-hoc copies
    (lookup's pinned alloc, train's two export-npz guards, wire's
    logical-bytes sum) that the planner could silently disagree with.
    ``rows`` defaults to ``cfg.num_rows`` (the runtime table); pass
    ``cfg.ckpt_rows`` for the 4096-aligned checkpoint layout the
    offload backends allocate, or explicit ``rows=``/``dim=`` where no
    config is in scope (lookup backends size from their own state)."""
    if rows is None:
        rows = cfg.num_rows
    if dim is None:
        dim = cfg.row_dim
    return int(rows) * int(dim) * int(dtype_bytes)


# --- the memory_stats seam (fmlint R018) -----------------------------------

def _stats_of(dev) -> Optional[Dict[str, Any]]:
    """One device's ``memory_stats()``, None on the CPU backend by
    policy. On an accelerator a failing ``memory_stats()`` PROPAGATES:
    swallowing it would turn a broken runtime into "capacity UNKNOWN"
    and the pre-flight into a no-op exactly where it matters."""
    if dev.platform == "cpu":
        return None
    return dev.memory_stats() or None


def device_memory_stats() -> Optional[Dict[str, Any]]:
    """The ``memory_stats()`` seam (fmlint R018: no call site outside
    this module).

    Returns the first local device's stats dict (``bytes_limit``,
    ``bytes_in_use``, ...) or None when the backend reports none. The
    CPU backend reports None by policy even where jax exposes host
    stats: "device memory" there IS host RAM, and a capacity verdict
    against it would brand every beyond-HBM offload config broken —
    capacity planning is an accelerator concern. ``FM_FAKE_HBM_BYTES``
    overrides everything (the test/chaos seam)."""
    env = os.environ.get(FAKE_CAPACITY_ENV, "")
    if env:
        return {"bytes_limit": int(env),
                "bytes_in_use": LEDGER.live_bytes()}
    import jax
    return _stats_of(jax.local_devices()[0])


def local_bytes_in_use() -> Optional[List[int]]:
    """``bytes_in_use`` of EVERY local device, in device order — the
    mesh paths' check that a row-sharded table did not land on the
    first chip. None where unmeasured (the CPU backend)."""
    import jax
    all_stats = [_stats_of(dev) for dev in jax.local_devices()]
    if any(not st or st.get("bytes_in_use") is None for st in all_stats):
        return None
    return [st["bytes_in_use"] for st in all_stats]


def device_capacity_bytes() -> Optional[int]:
    """Device capacity from the seam, or None when unmeasurable — a 0
    must mean a MEASURED zero, never "couldn't measure" (the same
    policy lookup.memory_report documents)."""
    stats = device_memory_stats()
    if not stats:
        return None
    limit = stats.get("bytes_limit")
    if not limit:
        return None
    return int(limit)


# A validating job's held-out sweep, placed once and kept on the device
# for the job's later sweeps (data/resident.py), where its placed bytes
# come under this share of one device's capacity: 1/32, 0.53 GB of the
# v5e's 16.9. It admits a sweep of a million examples a validation
# (fm16-train-eval's 54 batches: 146 MB) and refuses a held-out day
# (3 GB and up), which streams as before.
RESIDENT_SWEEP_OWNER = "resident_sweep"
RESIDENT_SWEEP_SHARE = 32
# Where the backend reports no capacity (the CPU): a constant, so that
# which way a job goes there does not hang on the host.
RESIDENT_SWEEP_UNMEASURED_BYTES = 64 << 20


def resident_sweep_budget(capacity: Optional[int] = None,
                          used: Optional[int] = None) -> int:
    """The placed bytes a sweep may have and stay on the device: the
    share above of one device's capacity, and never more than HALF of
    what the device has left over ``used`` (the other half is a later,
    wider batch's). ``used``, not given, is the runtime's own
    high-water mark (``peak_bytes_in_use``, else ``bytes_in_use``):
    the holder asks as a job's first sweep opens, an epoch of steps
    behind it, so the mark holds the train step's peak (the v5e's
    12.92 GB of 16.9 at the benchmark's 9.13 GB state leaves 1.99 GB,
    over the share). A sweep is kept out of what is left and streams
    where that is too little: the pre-flight books nothing for it, and
    no job that started without it is refused for it. ``capacity`` and
    ``used``: the planner's, where it sizes from a config."""
    stats = None if capacity else device_memory_stats()
    cap = capacity or (stats or {}).get("bytes_limit")
    if not cap:
        return RESIDENT_SWEEP_UNMEASURED_BYTES
    if used is None:
        used = ((stats or {}).get("peak_bytes_in_use")
                or (stats or {}).get("bytes_in_use") or 0)
    return min(int(cap) // RESIDENT_SWEEP_SHARE,
               max(0, int(cap) - int(used)) // 2)


# --- ownership ledger ------------------------------------------------------

class MemoryLedger:
    """Per-process registry of long-lived allocations by owner tag.

    ``register`` upserts an owner's current bytes (host-computed by
    the caller — ``.nbytes`` is a plain int attribute, never a fetch);
    ``release`` drops it. ``host=True`` owners (the host-offload
    table/accumulator) are tracked and gauged but excluded from the
    DEVICE live total — pressure and OOM forensics reason about HBM,
    and the offload backends exist precisely to hold state outside it.
    Thread-safe: the serve reload thread and dispatcher update
    concurrently with the train loop's wire buffers."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._owners: Dict[str, int] = {}
        self._host_owners: Dict[str, int] = {}
        self._peak = 0
        self._in_pressure = False

    def register(self, owner: str, nbytes: int,
                 host: bool = False) -> None:
        with self._lock:
            book = self._host_owners if host else self._owners
            (self._owners if host else self._host_owners).pop(owner,
                                                              None)
            book[owner] = int(nbytes)
            live = sum(self._owners.values())
            if live > self._peak:
                self._peak = live

    def release(self, owner: str) -> None:
        with self._lock:
            self._owners.pop(owner, None)
            self._host_owners.pop(owner, None)

    def live_bytes(self) -> int:
        """Device-resident live total (host owners excluded)."""
        with self._lock:
            return sum(self._owners.values())

    def peak_bytes(self) -> int:
        with self._lock:
            return self._peak

    def owners(self) -> Dict[str, int]:
        """Device owners snapshot (copy)."""
        with self._lock:
            return dict(self._owners)

    def host_owners(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._host_owners)

    def begin_pressure_episode(self) -> bool:
        """True exactly once per episode: the first crossing arms it;
        further calls inside the episode return False."""
        with self._lock:
            if self._in_pressure:
                return False
            self._in_pressure = True
            return True

    def end_pressure_episode(self) -> None:
        with self._lock:
            self._in_pressure = False

    def reset(self) -> None:
        """Test/bench seam: forget every owner, the peak, and any open
        pressure episode (the ledger is process-global state)."""
        with self._lock:
            self._owners.clear()
            self._host_owners.clear()
            self._peak = 0
            self._in_pressure = False


LEDGER = MemoryLedger()


def ledger_gauges() -> Dict[str, float]:
    """The ``mem/*`` gauge rows for one telemetry flush: per-owner
    bytes, live total, peak watermark, and capacity + utilization
    where the seam provides one. Empty dict when nothing ever
    registered (pre-ledger streams and bare-registry tests stay
    byte-identical). Host arithmetic only — zero device fetches
    (pinned by tests/test_memory.py, the ``anatomy_gauges``
    contract)."""
    owners = LEDGER.owners()
    hosts = LEDGER.host_owners()
    peak = LEDGER.peak_bytes()
    if not owners and not hosts and not peak:
        return {}
    rows: Dict[str, float] = {}
    for name, v in owners.items():
        rows[f"mem/{name}_bytes"] = float(v)  # fmlint: disable=R001 -- ledger values are host ints, never device arrays
    for name, v in hosts.items():
        rows[f"mem/{name}_bytes"] = float(v)  # fmlint: disable=R001 -- ledger values are host ints, never device arrays
    live = float(sum(owners.values()))
    rows["mem/live_bytes"] = live
    rows["mem/peak_bytes"] = float(peak)
    if hosts:
        rows["mem/host_live_bytes"] = float(sum(hosts.values()))
    stats = device_memory_stats()
    if stats:
        cap = stats.get("bytes_limit")
        if cap:
            rows["mem/capacity_bytes"] = float(cap)
            rows["mem/utilization_fraction"] = live / float(cap)
        in_use = stats.get("bytes_in_use")
        if in_use is not None:
            rows["mem/device_in_use_bytes"] = float(in_use)
        peak_in_use = stats.get("peak_bytes_in_use")
        if peak_in_use is not None:
            # The runtime's own high-water mark, beside the ledger's:
            # what the planner's resident bytes are checked against.
            rows["mem/device_peak_bytes"] = float(peak_in_use)
    return rows


def maybe_emit_pressure(tel) -> None:
    """``health: hbm_pressure`` — once per episode. Crossing
    ``mem_pressure_fraction`` of device capacity emits one event
    (owner breakdown attached) and counts ``mem/pressure_events``;
    dropping back below the threshold re-arms, exactly the Watchdog's
    stall-episode model. No-op when the knob is 0 (default) or the
    backend reports no capacity."""
    frac = float(getattr(tel, "mem_pressure_fraction", 0.0) or 0.0)
    if frac <= 0:
        return
    cap = device_capacity_bytes()
    if not cap:
        return
    live = LEDGER.live_bytes()
    ratio = live / float(cap)
    if ratio < frac:
        LEDGER.end_pressure_episode()
        return
    if not LEDGER.begin_pressure_episode():
        return
    tel.count("mem/pressure_events")
    tel.sink.emit("health", {
        "status": "hbm_pressure",
        "live_bytes": int(live),
        "capacity_bytes": int(cap),
        "fraction": round(ratio, 4),
        "threshold": frac,
        "owners": {k: int(v) for k, v in LEDGER.owners().items()},
    })
    tel.sink.flush()


# --- OOM forensics ---------------------------------------------------------

class HbmExhaustedError(RuntimeError):
    """A dispatch-site RESOURCE_EXHAUSTED re-raised with the rendered
    per-owner ledger attached: the OOM names which owner grew instead
    of an opaque XLA abort. Chains from the original error."""


def is_oom(e: BaseException) -> bool:
    """Whether ``e`` is the runtime's out-of-device-memory failure.
    Matched on the message, not the type: jaxlib's XlaRuntimeError
    moved modules across releases, and the status-code string is the
    stable part of the contract."""
    msg = str(e)
    return ("RESOURCE_EXHAUSTED" in msg
            or "Resource exhausted" in msg
            or isinstance(e, HbmExhaustedError))


def render_ledger() -> str:
    """The per-owner breakdown block an OOM wrap (and fmstat's MEMORY
    section) renders: owners sorted by size, live/peak, capacity where
    known."""
    owners = LEDGER.owners()
    lines = ["device-memory ledger (per-owner resident bytes):"]
    for name, v in sorted(owners.items(), key=lambda kv: -kv[1]):
        lines.append(f"  {name:<24} {_mb(v)}")
    for name, v in sorted(LEDGER.host_owners().items(),
                          key=lambda kv: -kv[1]):
        lines.append(f"  {name:<24} {_mb(v)} (host)")
    if not owners and not LEDGER.host_owners():
        lines.append("  (no owners registered)")
    lines.append(f"  {'live total':<24} {_mb(LEDGER.live_bytes())}")
    lines.append(f"  {'peak watermark':<24} {_mb(LEDGER.peak_bytes())}")
    cap = device_capacity_bytes()
    if cap:
        lines.append(f"  {'device capacity':<24} {_mb(cap)}")
    return "\n".join(lines)


@contextlib.contextmanager
def oom_guard(where: str):
    """Wrap one dispatch site (train step, score_batch, serve reload):
    RESOURCE_EXHAUSTED re-raises as HbmExhaustedError carrying the
    rendered ledger; everything else passes through untouched."""
    try:
        yield
    except HbmExhaustedError:
        raise  # an inner guard already attributed it
    except Exception as e:
        if not is_oom(e):
            raise
        raise HbmExhaustedError(
            f"device out of memory at {where}: {e}\n"
            f"{render_ledger()}\n"
            "size a fix before rerunning: python -m tools.fmstat "
            "capacity <cfg> --what-if vocabulary_size=...,dtype=f16,"
            "shards=K") from e


# --- capacity planner ------------------------------------------------------

def _mb(n) -> str:
    n = float(n)
    if n >= 1 << 30:
        return f"{n:,.0f} B ({n / (1 << 30):.2f} GB)"
    return f"{n:,.0f} B ({n / (1 << 20):.2f} MB)"


def parse_what_if(spec: str) -> Dict[str, Any]:
    """``--what-if vocabulary_size=1000000,dtype=f16,shards=4`` ->
    override dict. Numeric values parse as ints; ``dtype`` keeps its
    name (resolved against DTYPE_BYTES at plan time)."""
    out: Dict[str, Any] = {}
    if not spec:
        return out
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"--what-if entry {part!r} is not key=value")
        k, v = part.split("=", 1)
        k, v = k.strip(), v.strip()
        if k == "dtype":
            if v not in DTYPE_BYTES:
                raise ValueError(
                    f"--what-if dtype {v!r} unknown; one of "
                    f"{sorted(DTYPE_BYTES)}")
            out[k] = v
        else:
            out[k] = int(v)  # fmlint: disable=R001 -- CLI string parse, host-only
    return out


def plan(cfg, kind: str = "train",
         overrides: Optional[Dict[str, Any]] = None,
         shards: int = 1, capacity: Optional[int] = None) -> Dict[str, Any]:
    """Predicted resident bytes per owner on one device, from config
    alone — what ``fmstat capacity`` renders and
    ``preflight_capacity`` enforces, cross-checked against the live
    ledger by a tier-1 test (within 10% for the default shapes).

    ``overrides`` (the --what-if surface): ``vocabulary_size``,
    ``factor_num``, ``field_num``, ``batch_size``,
    ``max_features_per_example`` take numeric overrides; ``dtype``
    resizes the resident table (ROADMAP item 4 — the Adagrad
    accumulator stays f32: the quantization frontier quantizes the
    serving/resident table, not the optimizer state); ``shards``
    (an override, or the argument the train session's pre-flight
    passes) is the number of devices a mesh shards the rows over.
    With more than one the rows are the mesh's padded layout
    (``config.mesh_rows``), ``table``/``adagrad_acc`` are one device's
    share and ``sharded_owners`` keeps the whole. The wire ceiling
    stays the whole batch's: the mesh path places its batch through
    ``shard_batch`` and books none of it.

    ``kind="train"``: table + accumulator + wire double-buffers (+
    prefetch window). With ``lookup = host`` the table/accumulator
    move to the host-owner list — they are exactly what the offload
    mode keeps OUT of device memory. A save and a resume add nothing
    to a device's plan: a mesh's shards are snapshotted by orbax, a
    one-device state goes to the host and comes back in row blocks
    (checkpoint.ckpt_state, device_rows). What they cost is HOST
    memory: with ``save_steps`` set a one-device job keeps
    ``ckpt_snapshot``, the [ckpt_rows, D] float32 pair its saves are
    taken into (9.13 GB at fm-k16-criteo1tb's size), for its life; an
    exit save alone, and a resume's restored pair, hold as much for
    as long as they take. ``kind="serve"``: the resident
    table plus the old+new reload transient headroom a hot reload
    needs (serve/server._load_step holds both until the swap).
    ``resident_sweep_bytes``: the most a validating job's kept
    held-out sweep may hold (``resident_sweep_budget``), 0 where it
    keeps none; beside ``total_bytes`` and not in it, because the
    holder keeps a sweep only out of what the device has left.
    ``capacity``: the device's, where the caller sizes for another
    chip than the backend's (``fmstat capacity --capacity-bytes``)."""
    o = dict(overrides or {})
    vocab = int(o.get("vocabulary_size", cfg.vocabulary_size))
    k = int(o.get("factor_num", cfg.factor_num))
    field = int(o.get("field_num", getattr(cfg, "field_num", 0)))
    dim = (k * field + 1
           if getattr(cfg, "model_type", "fm") == "ffm" else k + 1)
    dtype = o.get("dtype", "f32")
    shards = max(1, int(o.get("shards", shards)))
    batch = int(o.get("batch_size", cfg.batch_size))
    feats = int(o.get("max_features_per_example",
                      cfg.max_features_per_example))
    rows = vocab + 1  # num_rows: + the shared padding row
    if shards > 1:
        from fast_tffm_tpu.config import mesh_rows
        rows = mesh_rows(rows)
    tbl = table_bytes(rows=rows, dim=dim,
                      dtype_bytes=DTYPE_BYTES[dtype])
    acc = table_bytes(rows=rows, dim=dim)  # optimizer state stays f32
    per_shard_tbl = -(-tbl // shards)
    per_shard_acc = -(-acc // shards)
    # Wire double-buffer: depth 2 of the worst-case flat payload
    # (indices i32 + values f32 per slot, + per-example lengths) — the
    # encoder registers the ACTUAL shipped bytes at run time; this is
    # the from-config ceiling.
    wire = 2 * (batch * feats * (4 + F32_BYTES) + batch * 4)
    owners: Dict[str, int] = {}
    host_owners: Dict[str, int] = {}
    if kind == "serve":
        owners["serve_table"] = per_shard_tbl
        owners["serve_reload_transient"] = per_shard_tbl
    else:
        if getattr(cfg, "lookup", "device") == "host":
            host_owners["offload_table"] = per_shard_tbl
            host_owners["offload_acc"] = per_shard_acc
        else:
            owners["table"] = per_shard_tbl
            owners["adagrad_acc"] = per_shard_acc
            if shards == 1 and getattr(cfg, "save_steps", 0):
                from fast_tffm_tpu.config import mesh_rows
                host_owners["ckpt_snapshot"] = 2 * table_bytes(
                    rows=mesh_rows(rows), dim=dim)
        owners["wire_buffers"] = wire
    total = sum(owners.values())
    cap = capacity or device_capacity_bytes()
    # A validating job's kept sweep, at its ceiling and beside the
    # total, not in it: the budget out of what this plan leaves, or a
    # capped sweep's batches at their widest (0: no validation_files,
    # or the holder's rule refuses the job).
    kept = 0
    if kind != "serve" and getattr(cfg, "validation_files", ()):
        from fast_tffm_tpu.data.resident import sweep_refusal
        if sweep_refusal(
                places=getattr(cfg, "lookup", "device") != "host",
                view=getattr(cfg, "vocab_mode", "fixed") == "admit") is None:
            # ids, values and as many unique rows as cells; FFM's fields
            ffm = getattr(cfg, "model_type", "fm") == "ffm"
            widest = batch * feats * (3 + ffm) * F32_BYTES
            capped = int(getattr(cfg, "validation_max_batches", 0) or 0)
            kept = resident_sweep_budget(cap, total)
            if capped:
                kept = min(kept, capped * widest)
    out: Dict[str, Any] = {
        "kind": kind,
        "overrides": o,
        "owners": owners,
        "host_owners": host_owners,
        "shards": shards,
        "sharded_owners": ({"table": tbl, "adagrad_acc": acc}
                           if shards > 1 and "table" in owners else {}),
        "total_bytes": int(total),
        "capacity_bytes": cap,
    }
    if kind != "serve":
        out[RESIDENT_SWEEP_OWNER + "_bytes"] = kept
    if cap:
        out["utilization_fraction"] = total / float(cap)
        out["verdict"] = "EXCEEDS" if total > cap else "FITS"
    else:
        out["verdict"] = "UNKNOWN (backend reports no capacity)"
    return out


def render_plan(p: Dict[str, Any]) -> str:
    """The human form of one plan: per-owner predicted bytes, total,
    capacity verdict — the fmstat capacity body and the pre-flight
    error's breakdown."""
    lines = [f"capacity plan ({p['kind']})"
             + (f" what-if {p['overrides']}" if p["overrides"] else "")
             + ":"]
    whole = p["sharded_owners"]
    for name, v in sorted(p["owners"].items(), key=lambda kv: -kv[1]):
        share = (f" (per device, 1/{p['shards']} of {_mb(whole[name])})"
                 if name in whole else "")
        lines.append(f"  {name:<24} {_mb(v)}{share}")
    for name, v in sorted(p["host_owners"].items(),
                          key=lambda kv: -kv[1]):
        lines.append(f"  {name:<24} {_mb(v)} (host-resident)")
    lines.append(f"  {'predicted device total':<24} "
                 f"{_mb(p['total_bytes'])}"
                 + (" per device" if whole else ""))
    kept = p.get(RESIDENT_SWEEP_OWNER + "_bytes")
    if kept is not None:
        lines.append(f"  {RESIDENT_SWEEP_OWNER:<24} {_mb(kept)}"
                     + (" (at most, and not in the total: a validating "
                        "job's held-out sweep, kept where the device "
                        "has the room)" if kept else ""))
    cap = p.get("capacity_bytes")
    if cap:
        lines.append(f"  {'device capacity':<24} {_mb(cap)}")
        lines.append(f"  {'utilization':<24} "
                     f"{p['utilization_fraction']:.1%}")
    lines.append(f"verdict: {p['verdict']}")
    return "\n".join(lines)


def preflight_capacity(cfg, kind: str = "train", shards: int = 1) -> None:
    """Fail fast at train()/ScorerServer startup when the PREDICTED
    resident bytes exceed the device capacity — the planner's
    breakdown plus what the session can do about it, instead of an XLA
    OOM minutes into bring-up. ``shards`` is the size of the mesh the
    train session row-shards table and accumulator over: one device's
    share stands against one device's capacity. No-op when the backend
    reports no capacity (the CPU container) — and the log line says
    which it was, so a pre-flight that checked nothing is on record."""
    p = plan(cfg, kind, shards=shards)
    cap = p.get("capacity_bytes")
    from fast_tffm_tpu.utils.logging import get_logger
    whole = sum(p["sharded_owners"].values())
    get_logger().info(
        "capacity pre-flight (%s): predicted resident %d bytes%s, "
        "device capacity %s", kind, p["total_bytes"],
        f" per device, 1/{p['shards']} of {whole} bytes of table and "
        f"accumulator row-sharded over {p['shards']} devices"
        if whole else "",
        f"{cap} bytes" if cap else "UNKNOWN (backend reports none; "
        "nothing checked)")
    if not cap or p["total_bytes"] <= cap:
        return
    raise ValueError(
        f"predicted resident device memory for this config exceeds "
        f"the device capacity ({_mb(p['total_bytes'])} > {_mb(cap)}"
        + (f" on each of the {p['shards']} devices the rows are "
           "sharded over" if whole else "")
        + ") — refusing to start rather than OOM mid-bring-up.\n"
        f"{render_plan(p)}\n"
        "what this session can do: run on a host with more devices "
        "(a train session row-shards table and accumulator over every "
        "device it sees), or set lookup = host for the beyond-HBM "
        "offload path; size either first with: python -m tools.fmstat "
        "capacity <your.cfg> --what-if shards=K,vocabulary_size=...")
