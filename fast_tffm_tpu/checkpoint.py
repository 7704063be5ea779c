"""Checkpoint / resume via orbax — the ``tf.train.Saver`` equivalent.

Reference behavior (SURVEY.md §5 "Checkpoint / resume"): periodic save
through the managed session, restore-on-restart, final model at the
config's ``model_file`` path; predict restores the same. Same contract
here, with orbax's sharding-aware async-capable machinery underneath plus
a dense ``.npz`` exporter for parity checks outside JAX.

Self-healing state plane (README "Checkpoint integrity & fallback"):
every committed save gets an atomically-renamed ``manifest-<step>.json``
sidecar (per-file size + crc32, step/epoch/vocab echo), written by
process 0 once the step directory is finalized. Restore verifies the
candidate step against its manifest first (``ckpt_verify = off | size |
full``); a step that fails verification — or raises during the actual
orbax restore — is QUARANTINED (renamed ``corrupt-<step>``, never
deleted) and restore walks back to the next older step until one loads.
Multi-host: process 0 makes every step decision and broadcasts it (same
protocol as ``_apply_epoch_override``), so hosts can't diverge onto
different steps and deadlock the collectives. Steps written before the
manifest existed carry nothing to verify against and stay restorable.
``tools/fmckpt`` is the offline view of the same invariants.
"""

from __future__ import annotations

import functools
import json
import math
import os
import re
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

from fast_tffm_tpu.obs.trace import span
from fast_tffm_tpu.utils.logging import get_logger
from fast_tffm_tpu.utils.retry import RetryPolicy, retry_io

# ckpt_verify knob values (config.py): "off" skips verification
# entirely, "size" checks per-file byte counts against the manifest
# (catches torn/truncated writes for the cost of one stat per file),
# "full" additionally re-hashes every byte (catches silent bit rot; a
# full pass over a config-#5 checkpoint reads the whole state once).
CKPT_VERIFY_MODES = ("off", "size", "full")

# What only a periodic save feeds, counted from 0 by a job that has
# ``save_steps`` (train.py): a reader that differences two snapshots of
# the stream finds "none yet" as 0, not as absent.
SAVE_COUNTERS = ("checkpoint/saves", "checkpoint/save_seconds",
                 "checkpoint/settle_seconds", "checkpoint/snapshot_seconds",
                 "checkpoint/snapshot_bytes",
                 "train/checkpoint_pause_seconds")

# Quarantined step dirs: ``corrupt-<step>`` (+ ``.k`` suffixes when a
# step is quarantined more than once). Never auto-deleted — operators
# reclaim the space explicitly with ``fmckpt gc``.
QUARANTINE_PREFIX = "corrupt-"

_MANIFEST_FORMAT = 1
_HASH_CHUNK_BYTES = 1 << 20

# The ONE sidecar-name pattern the run-time orphan pruning
# (_prune_sidecars) and fmckpt's offline scan share — a sidecar rename
# updated in one place only would make the offline tool delete files
# the run still needs, or miss real orphans. Matches epoch overrides,
# manifests, stream watermarks, and torn .tmp files (a killed writer's
# litter).
SIDECAR_RE = re.compile(
    r"(?:epoch_override-(\d+)|manifest-(\d+)\.json(?:\.tmp)?"
    r"|watermark-(\d+)\.json(?:\.tmp)?"
    r"|vocab-(\d+)\.json\.gz(?:\.tmp)?)")

# Stream-mode publish pointer (README "Streaming / online learning"):
# a tiny file in the .ckpt directory naming the newest PUBLISHED step —
# atomically replaced, so a scorer watching it always reads a complete
# value and can hot-reload the manifest-verified step it names.
PUBLISHED_POINTER = "published"


def sidecar_step(name: str) -> Optional[int]:
    """The step a sidecar file name belongs to, or None for
    non-sidecar names."""
    m = SIDECAR_RE.fullmatch(name)
    if not m:
        return None
    return int(m.group(1) or m.group(2) or m.group(3) or m.group(4))


def manifest_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"manifest-{step}.json")


def read_epoch_override(directory: str, step: int) -> Optional[int]:
    """The step's epoch-correction sidecar value, or None
    (missing/garbled/unreadable) — shared by restore's overlay and
    fmckpt's listing so the two can't disagree on what restores."""
    try:
        with open(os.path.join(directory,
                               f"epoch_override-{step}")) as fh:
            return int(fh.read().strip())
    except (OSError, ValueError):
        return None


def _atomic_write_bytes(path: str, blob: bytes) -> None:
    """The ONE tmp-write + fsync + rename sequence every sidecar
    writer (manifest, epoch override, watermark, vocab sidecar,
    published pointer) shares: the file either exists complete or not
    at all, and a failed write never litters its .tmp (a hard kill
    still can — the SIDECAR_RE orphan scans sweep those). Deliberately
    unretried: save-side write failures must surface at the save site
    (CheckpointState docstring)."""
    tmp = path + ".tmp"
    try:
        # fmlint: disable=R010 -- save-side writes are deliberately
        # never retried (CheckpointState docstring): a failed sidecar
        # write must fail its save loudly, not mask a torn file
        # behind backoff
        with open(tmp, "wb") as fh:
            fh.write(blob)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


def _atomic_write_text(path: str, data: str) -> None:
    _atomic_write_bytes(path, data.encode("utf-8"))


def watermark_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"watermark-{step}.json")


def read_watermark(directory: str, step: int) -> Optional[dict]:
    """The step's durable stream-position sidecar (run_mode = stream),
    or None when the step has none (epoch-mode checkpoints never do).
    A garbled sidecar also returns None, WITH a warning: resuming a
    stream without its watermark re-reads from the beginning of every
    tracked file — train() refuses that loudly rather than silently
    double-training (see train's stream restore)."""
    path = watermark_path(directory, step)
    try:
        # fmlint: disable=R010 -- missing IS the common case (every
        # epoch-mode checkpoint) and a transiently unreadable sidecar
        # must become the same "no watermark" verdict the caller
        # handles, not a retry loop inside the restore decision
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except FileNotFoundError:
        return None
    except (ValueError, OSError):
        get_logger().warning(
            "stream watermark sidecar %s is unreadable/garbled; "
            "treating step %d as carrying no stream position", path,
            step, exc_info=True)
        return None


def write_watermark(directory: str, step: int, payload: dict) -> str:
    """Atomically-renamed watermark write (same contract as
    write_manifest): the sidecar either exists complete or not at all —
    a torn watermark must never resume a stream at a garbage offset."""
    path = watermark_path(directory, step)
    _atomic_write_text(path, json.dumps(payload, sort_keys=True))
    return path


def vocab_sidecar_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"vocab-{step}.json.gz")


def load_vocab_sidecar(directory: str, step: int
                       ) -> Tuple[Optional[dict], Optional[str]]:
    """(payload, reason) for a step's vocab-admission sidecar: the
    ONE torn-sidecar decision shared by the restore path and `fmckpt
    verify` so the two can never disagree on what a torn sidecar is.
    Absent -> (None, None); readable with a matching embedded crc32 ->
    (payload, None); unreadable gzip/json or a crc mismatch ->
    (None, <human-readable failure>)."""
    import gzip
    path = vocab_sidecar_path(directory, step)
    name = os.path.basename(path)
    try:
        # fmlint: disable=R010 -- missing IS the common case (every
        # fixed-mode checkpoint); a garbled sidecar must become the
        # same "no admission state" verdict the caller handles, not a
        # retry loop inside the restore decision
        with gzip.open(path, "rt", encoding="utf-8") as fh:
            payload = json.load(fh)
    except FileNotFoundError:
        return None, None
    except (ValueError, OSError, EOFError) as e:
        return None, f"vocab sidecar {name} is unreadable/garbled: {e}"
    from fast_tffm_tpu.vocab.table import payload_crc_ok
    if not payload_crc_ok(payload):
        return None, (f"vocab sidecar {name} failed its embedded "
                      "crc32 check (torn or bit-rotted)")
    return payload, None


def load_vocab_map(cfg, directory: str, step: Optional[int]):
    """The ONE inference-side (table, slot map, step) pairing load —
    predict and the serving reload both route here so the triple
    contract can't drift between them. Returns the step's VocabMap;
    raises FileNotFoundError when the step carries no readable sidecar
    (missing OR torn — scoring without the slot map would misroute
    every admitted id)."""
    payload = (read_vocab_sidecar(directory, int(step))
               if step is not None and step >= 0 else None)
    if payload is None:
        raise FileNotFoundError(
            f"checkpoint step {step} at {directory} carries no "
            "readable vocab admission sidecar (vocab-<step>.json.gz) "
            "but vocab_mode = admit: scoring without the slot map "
            "would misroute every admitted id. Was the model trained "
            "with vocab_mode = fixed?")
    from fast_tffm_tpu.vocab.table import VocabMap
    return VocabMap.from_payload(cfg, payload)


def refuse_fixed_mode_admit_step(cfg, directory: str,
                                 step: Optional[int],
                                 payload: Optional[dict] = None
                                 ) -> None:
    """The ONE admit-trained-under-fixed loud failure (train resume,
    predict, serve reload all call it): a step carrying a vocab
    admission sidecar was trained with ``vocab_mode = admit`` — its
    table rows are slot-mapped — so loading it under ``fixed`` would
    gather/train arbitrary rows with zero errors. Keys on sidecar
    EXISTENCE, not readability: a TORN sidecar still proves admit
    training. ``payload``: a sidecar payload the caller already read
    (the restore overlay), counted as the same evidence. No-op under
    admit mode or when ``step`` is unknown."""
    if getattr(cfg, "vocab_mode", "fixed") != "fixed":
        return
    if payload is None and (step is None or step < 0
                            or not os.path.exists(
                                vocab_sidecar_path(directory,
                                                   int(step)))):
        return
    raise ValueError(
        f"checkpoint step {step} carries a vocab admission sidecar — "
        "it was trained with vocab_mode = admit, so its table rows "
        "are slot-mapped — but this config has vocab_mode = fixed: "
        "modulo ids would gather/train the wrong rows. Set "
        "vocab_mode = admit (or start a fresh model_file).")


def read_vocab_sidecar(directory: str, step: int) -> Optional[dict]:
    """The step's vocab-admission sidecar payload (vocab_mode = admit;
    vocab/table.py), or None when the step has none (every fixed-mode
    checkpoint). A garbled/torn sidecar returns None WITH a warning:
    train() then refuses to silently continue with a scrambled slot
    map (its restore path treats a missing payload on an admit-mode
    resume as a loud fresh-admission-plus-row-reset fallback)."""
    payload, reason = load_vocab_sidecar(directory, step)
    if reason is not None:
        get_logger().warning(
            "%s; treating step %d as carrying no admission state",
            reason, step)
    return payload


def write_vocab_sidecar(directory: str, step: int,
                        payload: dict) -> str:
    """Atomically-renamed gzip write of the vocab admission payload
    (same tmp+fsync+rename contract as every other sidecar): it either
    exists complete or not at all — a torn slot map must never remap a
    resumed stream onto garbage rows. The payload carries its own
    crc32 (vocab/table.py), which read_vocab_sidecar and `fmckpt
    verify` both re-check."""
    import gzip
    path = vocab_sidecar_path(directory, step)
    _atomic_write_bytes(path, gzip.compress(
        json.dumps(payload, sort_keys=True).encode("utf-8")))
    return path


def read_published(directory: str) -> Optional[int]:
    """The step the ``published`` pointer names, or None (never
    published / unreadable / garbled)."""
    try:
        # fmlint: disable=R010 -- a scorer-side poll: absent is the
        # normal pre-first-publish state and any flake reads as "not
        # published yet" on this attempt, which the next poll heals
        with open(os.path.join(directory, PUBLISHED_POINTER),
                  encoding="utf-8") as fh:
            return int(fh.read().strip())
    except (OSError, ValueError):
        return None


def write_published(directory: str, step: int) -> str:
    """Atomically repoint the ``published`` pointer file at ``step`` —
    the ONE pointer-write sequence (tmp + fsync + rename via
    _atomic_write_text) shared by the stream driver's
    ``CheckpointState.publish_step`` and the ``fmckpt publish``
    operator path, so a concurrent reader (a serving process's reload
    poll) always reads either the old complete value or the new one,
    never a torn write. Callers own verification: repointing at an
    unverified step is how a scorer loads garbage."""
    path = os.path.join(directory, PUBLISHED_POINTER)
    _atomic_write_text(path, f"{int(step)}\n")
    return path


# Canary pointer (README "Serving fleet"): a SECOND pointer file
# beside ``published``, repointed by ``fmckpt publish --canary``. The
# fleet's canary replica follows it, so a candidate step can take a
# configured traffic fraction (or shadow traffic) before the real
# pointer moves — promotion is then an ordinary ``fmckpt publish`` of
# the same step, rollback is deleting/repointing the canary pointer.
CANARY_POINTER = "published-canary"


def read_canary(directory: str) -> Optional[int]:
    """The step the ``published-canary`` pointer names, or None (no
    canary in flight / unreadable / garbled — same healing contract as
    read_published)."""
    try:
        # fmlint: disable=R010 -- scorer-side poll: absent is the
        # normal no-canary state and any flake reads as "no canary"
        # on this attempt, healed by the next poll
        with open(os.path.join(directory, CANARY_POINTER),
                  encoding="utf-8") as fh:
            return int(fh.read().strip())
    except (OSError, ValueError):
        return None


def write_canary(directory: str, step: int) -> str:
    """Atomically repoint the canary pointer (same tmp+fsync+rename
    sequence as write_published, same torn-read-free contract).
    Callers own verification, exactly as for the real pointer."""
    path = os.path.join(directory, CANARY_POINTER)
    _atomic_write_text(path, f"{int(step)}\n")
    return path


def read_pointer(directory: str, pointer: str = "published"
                 ) -> Optional[int]:
    """Resolve a scorer's configured pointer (``serve_pointer``):
    ``published`` reads the real pointer; ``canary`` reads the canary
    pointer, falling back to ``published`` until a canary step exists
    (a canary replica with nothing to canary serves the fleet's
    step)."""
    if pointer == "canary":
        step = read_canary(directory)
        if step is not None:
            return step
    return read_published(directory)


# Sidecar of the published pointer: the validation AUC of the last
# SUCCESSFUL publish — the publish gate's drop baseline
# (obs/quality.PublishGate). It describes the POINTER (not a step), so
# it lives beside it, survives step GC like it, and a resumed trainer
# re-arms publish_max_auc_drop from it instead of exempting the first
# post-restart publish.
GATE_BASELINE = "gate_baseline"


def read_gate_baseline(directory: str) -> Optional[float]:
    """The persisted drop baseline, or None (never published through a
    gate / unreadable / garbled — the gate then starts baseline-free,
    exactly like a first publish)."""
    try:
        # fmlint: disable=R010 -- trainer-startup read: absent is the
        # normal no-gated-publish-yet state; any flake degrades to a
        # baseline-free (first-publish) gate, never a crash
        with open(os.path.join(directory, GATE_BASELINE),
                  encoding="utf-8") as fh:
            v = float(fh.read().strip())
        return v if math.isfinite(v) else None
    except (OSError, ValueError):
        return None


def write_gate_baseline(directory: str, auc: float) -> None:
    """Atomically persist the drop baseline beside the pointer (same
    tmp+fsync+rename sequence, same torn-read-free contract)."""
    _atomic_write_text(os.path.join(directory, GATE_BASELINE),
                       f"{float(auc):.10f}\n")


def wait_for_published(directory: str, last: Optional[int] = None,
                       timeout: Optional[float] = None,
                       poll_seconds: float = 0.5) -> Optional[int]:
    """Block until the ``published`` pointer names a step different
    from ``last`` (None = any published step), polling the pointer
    file. Returns the new step, or None on timeout. The pointer-watch
    primitive the serving subsystem builds on (serve/reload.py polls
    inline on its own thread; this helper is the blocking form for
    server startup and tests). A garbled/unreadable pointer reads as
    "not published yet" on that poll and heals on the next — the same
    contract as read_published."""
    deadline = (None if timeout is None
                else time.monotonic() + float(timeout))
    while True:
        step = read_published(directory)
        if step is not None and step != last:
            return step
        if deadline is not None and time.monotonic() >= deadline:
            return None
        time.sleep(poll_seconds)


def list_step_dirs(directory: str) -> List[int]:
    """Committed step numbers by DIRECT directory listing: orbax commits
    a step by atomically renaming its tmp dir to the bare number, so a
    digit-named directory IS a committed step (a killed writer leaves
    only non-digit tmp names). Listed fresh on every call — quarantine
    renames must be visible immediately, without trusting any manager's
    cached step list."""
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    return sorted(int(n) for n in names
                  if n.isdigit() and os.path.isdir(os.path.join(directory,
                                                                n)))


def _crc32_file(path: str) -> Tuple[int, int]:
    """(crc32, byte count) of one file, streamed — the ONE hashing loop
    the save-side manifest and the restore-side full verify share, so
    the two can never diverge on chunking or masking. Both the reads
    and zlib.crc32 on >4 KB buffers release the GIL, so the background
    manifest writer doesn't stall the train loop."""
    crc = 0
    n = 0
    # fmlint: disable=R010 -- callers own the OSError contract: the
    # save-side manifest writer downgrades a failed hash to
    # "unverifiable" and the restore-side full verify converts it to a
    # quarantine VERDICT; a retry loop here would stall the background
    # hasher against storage that verify is about to judge anyway
    with open(path, "rb") as fh:
        while True:
            chunk = fh.read(_HASH_CHUNK_BYTES)
            if not chunk:
                break
            crc = zlib.crc32(chunk, crc)
            n += len(chunk)
    return crc & 0xFFFFFFFF, n


def compute_manifest(directory: str, step: int,
                     payload: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
    """Walk a FINALIZED step directory into its integrity manifest:
    per-file byte count + crc32 (sizes come from the bytes actually
    read, so the size and the hash describe the same snapshot), plus
    the caller's payload echo (step/epoch/vocab). Cost: one sequential
    re-read of the step dir per committed save — the async-save path
    runs it on a background thread (CheckpointState), so the train
    loop never waits on the hash."""
    step_dir = os.path.join(directory, str(step))
    files: Dict[str, Dict[str, int]] = {}
    for root, _dirs, names in os.walk(step_dir):
        for name in sorted(names):
            p = os.path.join(root, name)
            rel = os.path.relpath(p, step_dir).replace(os.sep, "/")
            crc, n = _crc32_file(p)
            files[rel] = {"size": n, "crc32": crc}
    man: Dict[str, Any] = {"format": _MANIFEST_FORMAT, "step": int(step),
                           "files": files}
    if payload:
        man.update(payload)
    return man


def write_manifest(directory: str, step: int,
                   manifest: Dict[str, Any]) -> str:
    """Atomically-renamed manifest write (_atomic_write_text): a
    manifest either exists complete or not at all — a torn manifest
    must never brand an intact step corrupt."""
    path = manifest_path(directory, step)
    _atomic_write_text(path, json.dumps(manifest, sort_keys=True))
    return path


def read_manifest(directory: str, step: int) -> Optional[Dict[str, Any]]:
    """The step's manifest dict, or None when the step predates
    manifests. A garbled manifest raises ValueError (json) — callers
    decide whether that means corrupt (verify) or skip (ls)."""
    try:
        with open(manifest_path(directory, step), encoding="utf-8") as fh:
            return json.load(fh)
    except FileNotFoundError:
        return None


def verify_step_dir(directory: str, step: int,
                    mode: str = "size") -> Optional[str]:
    """Integrity verdict for one committed step: None when it passes —
    or has no manifest to check against (pre-manifest checkpoints stay
    restorable) — else a human-readable failure reason. ``size`` stats
    every manifest-listed file; ``full`` additionally re-hashes them.
    Extra files orbax adds later are ignored: the manifest pins what
    the save wrote, not what may legitimately appear."""
    if mode == "off":
        return None
    if mode not in CKPT_VERIFY_MODES:
        raise ValueError(f"unknown ckpt_verify mode {mode!r} "
                         f"(want one of {CKPT_VERIFY_MODES})")
    try:
        man = read_manifest(directory, step)
    except (ValueError, OSError) as e:
        # Garbled json AND unreadable file (EACCES, EIO, ESTALE) both
        # become a VERDICT, never an exception: an escape here would
        # crash restore on process 0 while its peers sit blocked in
        # the decision broadcast — quarantine preserves the bytes, and
        # the walk-back keeps the job alive.
        return f"unreadable manifest: {e}"
    if man is None:
        return None
    step_dir = os.path.join(directory, str(step))
    if not os.path.isdir(step_dir):
        return "step directory missing"
    files = man.get("files") or {}
    for rel in sorted(files):
        p = os.path.join(step_dir, rel.replace("/", os.sep))
        try:
            size = os.path.getsize(p)
        except OSError:
            return f"missing file {rel}"
        if int(size) != int(files[rel]["size"]):
            return (f"size mismatch on {rel}: {size} bytes on disk != "
                    f"{files[rel]['size']} in manifest")
    if mode == "full":
        for rel in sorted(files):
            p = os.path.join(step_dir, rel.replace("/", os.sep))
            try:
                crc, _ = _crc32_file(p)
            except OSError as e:
                return f"unreadable file {rel}: {e}"
            if crc != int(files[rel]["crc32"]):
                return f"crc32 mismatch on {rel}"
    return None


def _tel():
    from fast_tffm_tpu.obs.telemetry import active
    return active()


def _count_restored(restored):
    """``checkpoint/restore_bytes``: the arrays a restore brought in."""
    tel = _tel()
    if tel is not None and restored is not None:
        tel.count("checkpoint/restore_bytes", sum(
            int(getattr(restored.get(k), "nbytes", 0))
            for k in ("table", "acc")))
    return restored


def align_orbax_barrier_counters() -> None:
    """Re-zero orbax's cross-process barrier counters — the broadcast-
    to-newcomer seam elastic GROW needs.

    Orbax makes its ``sync_global_devices`` barrier keys unique with
    MODULE-GLOBAL ``itertools.count()`` counters
    (``orbax.checkpoint.multihost.counters``): every AsyncCheckpointer
    ever created in the process advances them, and the count is baked
    into every subsequent barrier key (``<n>_Checkpointer:restore.<step>``).
    Two processes whose checkpointer HISTORIES differ — an elastic-grow
    joiner (count 0) rendezvousing with an incumbent that already
    restored/saved through several sessions — would derive DIFFERENT
    keys for the same restore and fail orbax's barrier-name assertion
    (observed: ``sync_global_devices name mismatch
    ('0_Checkpointer:restore.N')``). Every member constructs its
    CheckpointState at the same synchronized point running identical
    code, so re-zeroing here keeps every later allocation aligned
    across ANY membership history. Best-effort by design: on orbax
    layout drift the historical behavior (aligned-by-luck fresh
    processes) remains."""
    import itertools
    try:
        from orbax.checkpoint.multihost import counters
    except ImportError:
        return
    for name in vars(counters):
        if name.startswith("_") and name.endswith("_counter"):
            try:
                setattr(counters, name, itertools.count())
            except Exception:  # noqa: BLE001 - one misaligned counter
                pass           # is no worse than not aligning at all


class CheckpointState:
    """Manages checkpoints under ``<model_file>.ckpt/`` (orbax needs a
    directory; the reference's ``model_file`` is a path prefix).

    ``retry`` (utils/retry.py; train/predict thread the config's
    ``io_retries``/``io_backoff_seconds`` here) wraps the orbax
    RESTORE entry points in the transient-IO retry loop — restore is
    a pure read, so re-driving it is always safe. SAVE is deliberately
    NOT retried, in either phase: a transient failure after orbax has
    created the step directory would make a blind re-dispatch collide
    as StepAlreadyExistsError — which save()'s handler treats as the
    benign same-step case — silently recording a half-written
    checkpoint as done (strictly worse than failing loudly); and an
    async save's background-write failure surfaces at a later wait,
    outside any wrapper, where the snapshot needed to re-drive it is
    gone. Only genuinely retryable errors (OSError/TimeoutError minus
    the missing-path family) retry on restore; orbax's semantic errors
    (shape mismatches) propagate on the first raise."""

    def __init__(self, model_file: str, max_to_keep: int = 3,
                 retry: Optional[RetryPolicy] = None,
                 verify: str = "size"):
        if verify not in CKPT_VERIFY_MODES:
            raise ValueError(f"unknown ckpt_verify mode {verify!r} "
                             f"(want one of {CKPT_VERIFY_MODES})")
        self._max_to_keep = int(max_to_keep)
        self.directory = os.path.abspath(model_file) + ".ckpt"
        self._retry = retry or RetryPolicy(retries=0)
        self.verify = verify
        # (step, epoch, vocab) of the newest ASYNC save whose manifest
        # is still owed: the manifest can only describe a finalized
        # (atomically renamed) step dir, so it's written at the next
        # point the commit is certain — wait_until_finished, the next
        # save (orbax back-pressures there anyway), or close.
        self._pending_manifest: Optional[Tuple[int, int, int]] = None
        # Background manifest writer (the periodic-save path): hashing
        # a committed step is a full sequential re-read — at real table
        # scale that must overlap the train loop, not block it.
        self._manifest_thread: Optional[threading.Thread] = None
        # (step, wall clock at dispatch, bytes) of the write in flight:
        # counted into checkpoint/commit_seconds where its commit is
        # first known (_note_commit).
        self._dispatched: Optional[Tuple[int, float, int]] = None
        os.makedirs(self.directory, exist_ok=True)
        multi_process = jax.process_count() > 1
        if multi_process:
            # Align orbax's history-dependent barrier counters across
            # the membership: a grown cluster mixes incumbents (many
            # checkpointers created) with fresh joiners (none), and
            # mismatched counters mean mismatched barrier keys — see
            # align_orbax_barrier_counters.
            align_orbax_barrier_counters()
        self._mngr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(max_to_keep=max_to_keep,
                                                 create=True))

    def save(self, step: int, table: jax.Array, acc: jax.Array,
             vocabulary_size: int, force: bool = False,
             wait: bool = False, epoch: int = 0,
             rewrite_stale_metadata: bool = False,
             stream_state: Optional[dict] = None,
             vocab_state: Optional[dict] = None) -> None:
        """``vocabulary_size`` is stored alongside the arrays: the
        4096-aligned row layout means a changed vocab inside the same
        bucket would otherwise restore shape-compatibly but silently
        scramble the pad-row invariant (callers verify on restore).

        Saves are ASYNC by default: orbax snapshots the arrays to host
        and serializes in a background thread, so the train loop resumes
        after the snapshot instead of stalling for the full write (the
        reference's Saver writes synchronously; SURVEY §5 — this is the
        orbax upgrade that survey section calls for). A save issued
        while the previous one is still writing waits for it first
        (orbax's own back-pressure), bounding in-flight state to one
        snapshot. ``wait=True`` — the final/preemption save — blocks
        until the bytes are durably committed before returning."""
        # Timeline spans (obs/trace; no-op without an active tracing
        # run): checkpoint pauses are a classic silent stall. Inside
        # checkpoint/save, checkpoint/settle is the wait for the write
        # before this one and checkpoint/snapshot the part the loop
        # waits for of this one; `wait=True` saves show the full write.
        with span("checkpoint/save", seconds="checkpoint/save_seconds",
                  step=int(step), wait=wait):
            # Callers that snapshot the state themselves (ckpt_state's
            # host copy of a one-device state) settle BEFORE they do,
            # so that one snapshot is in flight at a time; anyone else
            # settles here.
            if self._pending_manifest is not None:
                self.settle()
            # Plain python ints for the scalar leaves: orbax's
            # StandardSave supported types are (int, float, np.ndarray,
            # jax.Array) — numpy SCALARS (np.int64) are rejected outright
            # by its save-state validation.
            payload = {"table": table, "acc": acc,
                       "step": int(step),
                       # COMPLETED epochs at save time: lets a restarted
                       # run resume an interrupted epoch schedule instead
                       # of rerunning it from zero (train.resume_start_epoch)
                       "epoch": int(epoch),
                       "vocab": int(vocabulary_size)}
            try:
                # No retry here (class docstring): re-dispatching a
                # save whose first attempt half-created the step dir
                # would surface as the benign StepAlreadyExists path
                # below and silently skip the save.
                nbytes = int(table.nbytes) + int(acc.nbytes)
                # From the call into orbax until the loop may go on: a
                # device array's copy to the host; for a host snapshot
                # (ckpt_state made the copy, under the same name) the
                # hand-over alone.
                with span("checkpoint/snapshot",
                          seconds="checkpoint/snapshot_seconds"):
                    self._mngr.save(step,
                                    args=ocp.args.StandardSave(payload),
                                    force=force)
                self._dispatched = (int(step), time.time(), nbytes)
                self._pending_manifest = (int(step), int(epoch),
                                          int(vocabulary_size))
                # A FRESH save at this step carries authoritative metadata:
                # drop any leftover same-step sidecar (a cleared-and-reused
                # directory) and any sidecars orphaned by max_to_keep GC —
                # CheckpointManager doesn't know about them.
                if jax.process_index() == 0:
                    self._prune_sidecars(fresh_step=step)
                    # Counted INSIDE the dispatch path and on process
                    # 0 only (like the fallback counters — every
                    # process's shard file merges by SUM in fmstat):
                    # the same-step collision below is an orbax no-op,
                    # and "checkpoint saves" means global saves that
                    # wrote state.
                    tel = _tel()
                    if tel is not None:
                        tel.count("checkpoint/saves")
                        tel.count("checkpoint/snapshot_bytes", nbytes)
            except ocp.checkpoint_manager.StepAlreadyExistsError:
                # The final/preemption save can land on the same step as the
                # last periodic save (save_steps divides the step count).
                # The ARRAY state at a given step is unique, so that part is
                # a no-op — but the colliding periodic save recorded the
                # epoch count as of MID-epoch, while this save may carry the
                # completed count; without a correction a successfully
                # completed run restores as "interrupted" and silently
                # retrains an epoch. The CALLER decides via
                # rewrite_stale_metadata — train() knows deterministically
                # (from its own last periodic save) whether the metadata
                # differs, and a deterministic flag keeps every process of a
                # multi-host job on the same side of this path (a
                # per-process disk read here could diverge on one host's
                # transient error and deadlock the final save). The
                # correction is a tiny atomically-renamed sidecar holding
                # the true epoch — restore() overlays it — NOT a
                # delete+resave of the step: a hard kill here leaves either
                # the old sidecar state (epoch stale, exactly the status
                # quo ante — the run retrains one epoch) or the new one;
                # the step's arrays are never at risk (advisor finding r4).
                if rewrite_stale_metadata and jax.process_index() == 0:
                    _atomic_write_text(self._epoch_sidecar(step),
                                       str(int(epoch)))
            # Stream-mode durable position (run_mode = stream): the
            # watermark sidecar pairs with the step exactly like the
            # epoch sidecar — written AFTER the fresh-step prune above
            # (which clears any stale same-step watermark), on BOTH the
            # fresh-save and same-step-collision paths (the collision's
            # array state is identical, and so is the watermark: it
            # only advances with global steps).
            if stream_state is not None and jax.process_index() == 0:
                write_watermark(self.directory, int(step), stream_state)
            # Vocab-admission sidecar (vocab_mode = admit): pairs with
            # the step exactly like the watermark — written after the
            # fresh-step prune, on both the fresh-save and same-step-
            # collision paths. The collision path's payload IS
            # identical to the colliding save's: the slot map only
            # moves at barriers, and every barrier-adjacent save
            # (publish, final) passes force=True precisely so a
            # post-barrier sidecar is never paired with skipped
            # pre-barrier arrays.
            if vocab_state is not None and jax.process_index() == 0:
                write_vocab_sidecar(self.directory, int(step),
                                    vocab_state)
            if wait:
                self.wait_until_finished()

    def settle(self) -> None:
        """Wait for the write before the save about to be made, and
        start its owed manifest. The hash runs on a background thread:
        it re-reads the whole step dir, which must overlap the next
        save interval, not stall it. orbax back-pressures a new save on
        the in-flight write anyway, so the wait costs nothing extra and
        guarantees the manifest describes a finalized step dir."""
        with span("checkpoint/settle", seconds="checkpoint/settle_seconds"):
            if self._pending_manifest is not None:
                self._mngr.wait_until_finished()
                self._note_commit()
                self._flush_pending_manifest(background=True)

    def wait_until_finished(self) -> None:
        self._mngr.wait_until_finished()
        self._note_commit()
        self._flush_pending_manifest()

    def _note_commit(self) -> None:
        """Count the write in flight as committed, the first time its
        commit is known (call after the manager's wait): dispatch to
        finalized, by the mtime orbax's last write left on the step dir
        (a periodic save's commit is first KNOWN at the next save, an
        interval later)."""
        sent, self._dispatched = self._dispatched, None
        tel = _tel()
        if sent is None or tel is None or jax.process_index() != 0:
            return
        step, t_sent, nbytes = sent
        try:
            done = os.stat(os.path.join(self.directory, str(step))).st_mtime
        except OSError:
            done = time.time()
        tel.count("checkpoint/commit_seconds", max(done - t_sent, 0.0))
        tel.count("checkpoint/committed_bytes", nbytes)

    def _flush_pending_manifest(self, background: bool = False) -> None:
        """Write the manifest for the last committed save. Call only
        after ``wait_until_finished`` — the step dir must be finalized.
        Process 0 only (one writer, like the epoch sidecar); a failed
        manifest write downgrades the step to unverifiable (it stays
        restorable, like a pre-manifest checkpoint) rather than failing
        a save that already committed. ``background=True`` (the
        periodic-save path) runs the hash on a daemon thread — any
        earlier writer is joined first, so at most one manifest write
        is ever in flight and they never reorder. Synchronous callers
        (wait=True saves, wait_until_finished, close) join it too, so
        after any of those the manifest is durably on disk."""
        self._join_manifest_thread()
        pend, self._pending_manifest = self._pending_manifest, None
        if pend is None or jax.process_index() != 0:
            return
        if background:
            t = threading.Thread(target=self._write_manifest_for,
                                 args=pend, name="ckpt-manifest",
                                 daemon=True)
            self._manifest_thread = t
            t.start()
        else:
            self._write_manifest_for(*pend)

    def _join_manifest_thread(self) -> None:
        t, self._manifest_thread = self._manifest_thread, None
        if t is not None:
            t.join()

    def _write_manifest_for(self, step: int, epoch: int,
                            vocab: int) -> None:
        try:
            man = compute_manifest(self.directory, step,
                                   payload={"epoch": epoch,
                                            "vocab": vocab})
            write_manifest(self.directory, step, man)
        except OSError:
            get_logger().warning(
                "manifest write for checkpoint step %d failed; the step "
                "stays restorable but unverifiable", step, exc_info=True)

    def _epoch_sidecar(self, step: int) -> str:
        return os.path.join(self.directory, f"epoch_override-{step}")

    def _prune_sidecars(self, fresh_step: Optional[int] = None) -> None:
        """Remove epoch sidecars AND manifests that no longer describe
        anything.

        Two legs with DIFFERENT failure contracts: removing the
        fresh-step's stale sidecar/manifest is correctness-bearing (a
        surviving sidecar would overlay the wrong epoch on the step
        just written, a surviving manifest would describe the OLD bytes
        and brand the fresh step corrupt — cleared-and-reused dir
        case), so anything but "not there" raises and fails the save
        loudly; the orphan scan for GC-deleted steps is purely cosmetic
        (a leftover orphan costs bytes and can never overlay or
        verify: its step no longer restores), so no flake in
        listdir/all_steps may fail an already-committed save."""
        if fresh_step is not None:
            mp = manifest_path(self.directory, fresh_step)
            wp = watermark_path(self.directory, fresh_step)
            vp = vocab_sidecar_path(self.directory, fresh_step)
            # The watermark is correctness-bearing like the epoch
            # sidecar: a surviving stale one (cleared-and-reused dir,
            # or an epoch-mode save landing on an old stream step)
            # would resume a later stream at positions THIS state
            # never trained. The vocab sidecar equally so: a stale
            # slot map would remap ids onto rows THIS table never
            # assigned them.
            for stale in (self._epoch_sidecar(fresh_step), mp,
                          mp + ".tmp", wp, wp + ".tmp", vp,
                          vp + ".tmp"):
                try:
                    os.remove(stale)
                except FileNotFoundError:
                    pass  # the common case: nothing to correct
        try:
            kept = set(self._mngr.all_steps())
            names = os.listdir(self.directory)
        except Exception:  # noqa: BLE001 - cosmetic scan only
            return
        for name in names:
            s = sidecar_step(name)
            if s is None:
                continue
            if s == fresh_step or s not in kept:
                try:
                    os.remove(os.path.join(self.directory, name))
                except OSError:
                    pass

    def _apply_epoch_override(self, step: int, restored):
        """Overlay a same-step epoch-correction sidecar (see save())
        onto a restored tree, when both exist. Multi-process: only
        process 0 reads the file and the value is broadcast, so a
        transient read error (or non-shared storage) on one host can
        never give processes different epochs — divergent resume
        schedules deadlock the lockstep collectives."""
        if restored is None or "epoch" not in restored:
            return restored
        override = -1
        if jax.process_index() == 0:
            # Shared reader (fmckpt uses it too); any unreadable/
            # garbled sidecar -> step's own metadata stands.
            ov = read_epoch_override(self.directory, step)
            if ov is not None:
                override = ov
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            from fast_tffm_tpu.parallel.liveness import guarded_collective
            override = int(guarded_collective(
                multihost_utils.broadcast_one_to_all,
                np.int64(override), label="checkpoint/epoch_override"))
        if override >= 0:
            restored["epoch"] = np.int64(override)
        return restored

    def _attach_vocab(self, step: int, restored):
        """Overlay the step's vocab-admission sidecar (vocab_mode =
        admit) onto a restored tree as ``restored["vocab_admission"]``
        (None when absent — every fixed-mode checkpoint). Same
        process-0-reads + broadcast protocol as the stream watermark,
        and for the same reason: divergent admission state across
        hosts would remap the same id onto different rows."""
        if restored is None:
            return restored
        payload = None
        if jax.process_index() == 0:
            payload = read_vocab_sidecar(self.directory, step)
        payload = self._broadcast_json(payload, "checkpoint/vocab")
        restored["vocab_admission"] = payload
        return restored

    def _attach_stream(self, step: int, restored):
        """Overlay the step's stream-watermark sidecar (run_mode =
        stream) onto a restored tree as ``restored["stream"]`` (None
        when absent — every epoch-mode checkpoint). Multi-process:
        process 0 reads, the JSON is broadcast (two fixed-shape
        collectives), so a transient read error on one host can never
        resume workers at different stream positions."""
        if restored is None:
            return restored
        wm = None
        if jax.process_index() == 0:
            wm = read_watermark(self.directory, step)
        # identity when single-process; the agreed (chief) value else
        wm = self._broadcast_json(wm, "checkpoint/watermark")
        restored["stream"] = wm
        return restored

    def _broadcast_json(self, obj, label: str):
        """Process 0's JSON-serializable value on every process: the
        variable-size companion of ``_broadcast_int``. ONE
        implementation — data/stream.broadcast_blob (the length-then-
        padded-payload chief broadcast, with its transport dtype
        handling) — so the protocol can't fork between the stream
        discovery and the restore-side watermark attach. stream.py
        imports nothing from this module, so no cycle."""
        from fast_tffm_tpu.data.stream import broadcast_blob
        return broadcast_blob(obj, label)

    # -- stream-mode publishing ------------------------------------------

    def publish_step(self, step: int) -> Optional[str]:
        """Atomically repoint the ``published`` pointer file at a
        manifest-VERIFIED committed step — the hot-reload signal a
        serving process watches (``fmckpt ls`` shows it). The caller
        must have settled the step's save + manifest first (a
        ``wait=True`` save does). Verification runs at the instance's
        ``ckpt_verify`` mode (minimum ``size`` — a publish is a promise
        to a scorer, so ``off`` still size-checks); on failure the
        pointer is NOT moved (the previous published step stays live),
        a warning names the reason, and None returns. Process 0 only;
        multi-host callers gate on it like the manifest writer."""
        if jax.process_index() != 0:
            return None
        mode = self.verify if self.verify != "off" else "size"
        reason = verify_step_dir(self.directory, step, mode)
        if reason is not None:
            get_logger().warning(
                "publish of checkpoint step %d skipped: %s — the "
                "previous published pointer stays in place", step,
                reason)
            tel = _tel()
            if tel is not None:
                tel.count("stream/publish_failures")
            return None
        path = write_published(self.directory, step)
        tel = _tel()
        if tel is not None:
            tel.count("stream/publishes")
        get_logger().info(
            "published checkpoint step %d (%s-verified) -> %s", step,
            mode, path)
        return path

    def published_at_risk(self, margin: int = 1) -> bool:
        """Whether retention is about to lap the ``published`` pointer:
        True when the pointed-at step is gone already, or ``margin``
        more saves would GC it (max_to_keep newest-N eviction). The
        stream driver republishes FIRST when this fires, so the
        pointer a scorer resolves never names a deleted step — frequent
        ``save_steps`` saves under a long ``publish_interval_seconds``
        would otherwise delete the published checkpoint out from under
        the serving fleet mid-interval. ``margin=2`` is the publish
        gate's retention-pause threshold: while a hold blocks
        republishing, periodic saves stop one slot EARLY so the
        mandatory final/preemption save can still land without
        evicting the last-good step."""
        pub = read_published(self.directory)
        if pub is None:
            return False
        steps = list_step_dirs(self.directory)
        if pub not in steps:
            return True  # already dangling: republish immediately
        newer = sum(1 for s in steps if s > pub)
        return newer >= self._max_to_keep - margin

    # -- integrity: verify / quarantine / step decision -----------------

    def verify_step(self, step: int,
                    mode: Optional[str] = None) -> Optional[str]:
        """Integrity verdict for one committed step against its
        manifest: None when it passes (or carries no manifest —
        pre-manifest checkpoints stay restorable), else a failure
        reason. ``mode`` defaults to the instance's ``ckpt_verify``."""
        return verify_step_dir(self.directory, step, mode or self.verify)

    def quarantine_step(self, step: int, reason: str) -> str:
        """Move a bad step out of the restore path WITHOUT deleting it:
        the step dir is renamed ``corrupt-<step>`` and its
        manifest/epoch sidecars move inside it (forensics travel with
        the evidence; nothing can overlay or verify a quarantined
        step). Emits the ``health: ckpt_fallback`` event + counters on
        the active run telemetry. Returns the quarantine dir path.
        Process 0 only in multi-host jobs — callers broadcast the
        resulting step decision."""
        src = os.path.join(self.directory, str(step))
        dst = os.path.join(self.directory, f"{QUARANTINE_PREFIX}{step}")
        k = 0
        while os.path.exists(dst):
            k += 1
            dst = os.path.join(self.directory,
                               f"{QUARANTINE_PREFIX}{step}.{k}")
        os.rename(src, dst)
        for name in (f"manifest-{step}.json", f"epoch_override-{step}",
                     f"watermark-{step}.json", f"vocab-{step}.json.gz"):
            try:
                os.replace(os.path.join(self.directory, name),
                           os.path.join(dst, name))
            except OSError:
                pass  # sidecar absent (or unshared storage): forensics
                # are best-effort, the rename above is the invariant
        try:
            with open(os.path.join(dst, "QUARANTINE"), "w",
                      encoding="utf-8") as fh:
                fh.write(f"step {step} quarantined at {time.time():.3f}: "
                         f"{reason}\n")
        except OSError:
            pass
        try:
            # Drop the manager's cached step list: latest_step()/
            # all_steps() must stop offering the quarantined step.
            self._mngr.reload()
        except Exception:  # noqa: BLE001 - cache refresh is advisory;
            pass           # list_step_dirs() reads the directory fresh
        from fast_tffm_tpu.obs.health import emit_ckpt_fallback
        emit_ckpt_fallback(step, reason, dst)
        get_logger().warning(
            "checkpoint step %d failed integrity (%s); quarantined to %s "
            "— falling back to an older step", step, reason, dst)
        return dst

    def _broadcast_int(self, value: int) -> int:
        """Process 0's value on every process (the same broadcast
        protocol as ``_apply_epoch_override``); identity when
        single-process. Every step decision goes through this so
        multi-host processes can't diverge onto different steps and
        deadlock the collectives."""
        if jax.process_count() <= 1:
            return int(value)
        from jax.experimental import multihost_utils
        from fast_tffm_tpu.parallel.liveness import guarded_collective
        # Deadline-guarded (parallel/liveness.py): a peer that dies
        # mid-restore must raise WorkerLostError on the survivors, not
        # park them in the step-decision broadcast forever.
        return int(guarded_collective(
            multihost_utils.broadcast_one_to_all, np.int64(value),
            label="checkpoint/step_decision"))

    def _all_agree(self, flag: bool) -> bool:
        """True only when EVERY process reports ``flag`` true (tiny
        allgather; identity single-process). The restore walk-back
        branches on restore success/failure — a per-process local
        condition (one host's shard read can fail transiently while
        the others succeed), so without this agreement the processes
        would take different branches of the broadcast protocol and
        pair mismatched collectives — the exact deadlock the broadcast
        design exists to prevent."""
        if jax.process_count() <= 1:
            return bool(flag)
        from jax.experimental import multihost_utils
        from fast_tffm_tpu.parallel.liveness import guarded_collective
        flags = guarded_collective(
            multihost_utils.process_allgather,
            np.asarray([bool(flag)]), label="checkpoint/restore_agree")
        return bool(np.asarray(flags).all())

    def _pick_intact_step(self) -> Tuple[int, int]:
        """Newest step that passes verification, quarantining every
        newer step that doesn't. Returns (step, n_quarantined), step -1
        when no step survives. Process 0 only — callers broadcast."""
        n = 0
        while True:
            steps = list_step_dirs(self.directory)
            if not steps:
                return -1, n
            s = steps[-1]
            reason = self.verify_step(s)
            if reason is None:
                return s, n
            self.quarantine_step(s, reason)
            n += 1

    def restore_partial(self, template: Dict[str, Any],
                        step: Optional[int] = None
                        ) -> Optional[Dict[str, Any]]:
        """Restore only the leaves named in ``template`` (a subtree of
        what was saved). The offload predict path uses this to load the
        table WITHOUT the same-sized Adagrad accumulator — at config-#5
        scale the accumulator is half the state, and materializing it
        just to drop it doubles peak host RSS. Uses a read-only
        PyTree-handler manager (StandardSave's on-disk format is the
        PyTree format; partial restore is a PyTreeRestore feature).
        Latest-step selection goes through the same verify + quarantine
        + broadcast decision as restore()."""
        with span("checkpoint/restore",
                  seconds="checkpoint/restore_seconds", partial=True):
            self.wait_until_finished()
            s = step
            if s is None:
                cand = (self._pick_intact_step()[0]
                        if jax.process_index() == 0 else -1)
                s = self._broadcast_int(cand)
                if s < 0:
                    return None
            reader = ocp.CheckpointManager(
                self.directory,
                item_handlers=ocp.PyTreeCheckpointHandler())
            try:
                restored, err = _restore_tolerating_legacy_epoch(
                    template,
                    lambda t: retry_io(
                        reader.restore, s,
                        args=ocp.args.PyTreeRestore(
                            item=t, partial_restore=True),
                        policy=self._retry, op="checkpoint_restore"))
                if err is not None:
                    raise err
                return _count_restored(
                    self._apply_epoch_override(s, restored))
            finally:
                reader.close()

    def latest_step(self) -> Optional[int]:
        return self._mngr.latest_step()

    def restore(self, step: Optional[int] = None,
                template: Optional[Dict[str, Any]] = None
                ) -> Optional[Dict[str, Any]]:
        """Returns {"table", "acc", "step"} as host arrays, or None if no
        checkpoint exists yet (fresh start). ``template`` is an abstract
        pytree (jax.ShapeDtypeStruct leaves) matching what was saved;
        required by orbax to reconstruct arrays.

        With ``step=None`` the newest INTACT checkpoint wins: every
        candidate is verified against its manifest before orbax touches
        it, and a candidate that fails verification — or raises during
        the restore itself — is quarantined (``corrupt-<step>``, never
        deleted) while restore walks back to the next older step. An
        EXPLICIT step is verified but never quarantined or walked past:
        the caller asked for those exact bytes."""
        with span("checkpoint/restore",
                  seconds="checkpoint/restore_seconds"):
            self.wait_until_finished()  # in-flight async save first
            if step is not None:
                reason = self.verify_step(step)
                if reason is not None:
                    raise ValueError(
                        f"checkpoint step {step} at {self.directory} "
                        f"failed integrity verification: {reason}. An "
                        "explicitly requested step is never quarantined "
                        "automatically — inspect it with `python -m "
                        "tools.fmckpt verify`.")
                restored, err = self._attempt_restore(step, template)
                if err is not None:
                    self._raise_restore_error(step, err)
                return _count_restored(self._attach_vocab(
                    step, self._attach_stream(
                        step, self._apply_epoch_override(step, restored))))
            return _count_restored(self._restore_newest_intact(template))

    def _restore_newest_intact(self, template
                               ) -> Optional[Dict[str, Any]]:
        """The self-healing walk-back (class docstring): process 0
        picks + verifies + quarantines, every decision is broadcast,
        all processes restore the agreed step together."""
        proc0 = jax.process_index() == 0
        quarantined = 0
        first_err: Optional[Tuple[int, BaseException]] = None
        while True:
            cand = -1
            if proc0:
                cand, nq = self._pick_intact_step()
                quarantined += nq
            cand = self._broadcast_int(cand)
            if cand < 0:
                if first_err is not None:
                    # Every remaining candidate failed to LOAD (the
                    # verify-failures are already quarantined): surface
                    # the original, newest-step error — on a config
                    # mismatch that is the diagnosis for every step.
                    self._raise_restore_error(*first_err)
                had_quarantine = self._broadcast_int(
                    1 if quarantined else 0)
                if had_quarantine:
                    # Never silently convert "all checkpoints failed
                    # integrity" into a fresh start: a fresh run would
                    # quietly retrain from zero on top of hours of
                    # quarantined-but-recoverable optimizer state.
                    raise ValueError(
                        f"every checkpoint step at {self.directory} "
                        "failed integrity verification and was "
                        "quarantined (corrupt-*). Inspect with `python "
                        "-m tools.fmckpt ls` / `verify`; rename an "
                        "intact corrupt-<step> back to <step> to "
                        "recover it, or point model_file elsewhere to "
                        "start fresh.")
                return None
            restored, err = self._attempt_restore(cand, template)
            # Success/failure is a PER-PROCESS condition (one host's
            # shard read can fail while the others succeed): agree on
            # it before branching, or the processes would pair
            # mismatched collectives and deadlock.
            if self._all_agree(err is None):
                if quarantined:
                    tel = _tel()
                    if tel is not None:  # process 0 only: quarantined
                        # is always 0 elsewhere, so the count is global
                        tel.count("checkpoint/fallbacks")
                return self._attach_vocab(cand, self._attach_stream(
                    cand, self._apply_epoch_override(cand, restored)))
            if err is None:
                # This process succeeded but a peer didn't: walk back
                # with everyone (the restored tree may hold
                # non-addressable shards of a step the job as a whole
                # cannot load).
                err = RuntimeError(
                    f"restore of step {cand} failed on another process")
            if first_err is None:
                first_err = (cand, err)
            # Walk past a restore-time failure only when an OLDER step
            # remains: quarantining the last loadable-looking step on
            # (say) a config mismatch would turn a loud, actionable
            # error into a silent fresh start.
            has_more = 0
            if proc0 and any(t != cand
                             for t in list_step_dirs(self.directory)):
                has_more = 1
            has_more = self._broadcast_int(has_more)
            if not has_more:
                self._raise_restore_error(cand, err)
            if proc0:
                self.quarantine_step(
                    cand, f"restore failed: {type(err).__name__}: {err}")
                quarantined += 1

    def _attempt_restore(self, s: int, template
                         ) -> Tuple[Optional[Dict[str, Any]],
                                    Optional[BaseException]]:
        """One orbax restore attempt at step ``s`` (transient-IO
        retries + legacy-epoch tolerance included). Returns
        (restored, None) or (None, error) — the fallback loop owns
        deciding what an error means. OSError is caught alongside the
        semantic classes: after retry_io gives up, a persistently
        unreadable file IS the torn-write signature for steps too old
        to carry a manifest."""
        try:
            if template is None:
                return retry_io(self._mngr.restore, s,
                                policy=self._retry,
                                op="checkpoint_restore"), None
            multi_process = jax.process_count() > 1
            to_host = any(isinstance(v, jax.ShapeDtypeStruct)
                          and v.sharding is None for v in template.values())
            if multi_process or to_host:
                # A template whose leaves name no sharding asks for
                # HOST arrays (the offload backend's, a one-device
                # job's): the reader is told so leaf by leaf, because
                # orbax otherwise hands a step a MESH saved back as
                # device arrays under the saved sharding.
                # Multi-process restores stage through HOST RAM: orbax's
                # direct-to-device deserialization in the multi-process
                # restore-then-step shape hits a known jaxlib defect
                # (intermittent SIGSEGV, or SILENT buffer garbage —
                # negative Adagrad accumulators, 1e37 magnitudes —
                # observed reproducibly on the elastic-grow reformed
                # cluster's first restore). Deserializing to numpy and
                # placing shards via make_array_from_callback uses only
                # the transfer path every train step already exercises.
                # Cost: each process transiently materializes the full
                # arrays on host — the same peak the offload backend's
                # load already accepts.
                return _restore_tolerating_legacy_epoch(
                    template,
                    lambda t: retry_io(
                        self._restore_host_staged, s, t,
                        policy=self._retry, op="checkpoint_restore"))
            return _restore_tolerating_legacy_epoch(
                template,
                lambda t: retry_io(
                    self._mngr.restore, s,
                    args=ocp.args.StandardRestore(t),
                    policy=self._retry, op="checkpoint_restore"))
        except (ValueError, KeyError, OSError) as e:
            return None, e
        except Exception as e:
            # orbax 0.11.32 re-raises a tensorstore read failure as a
            # bare Exception chained from the ValueError: the same
            # torn-step signature one link down. Anything else (a
            # programming error) propagates.
            if _caused_by(e, (ValueError, KeyError, OSError)):
                return None, e
            raise

    def _restore_host_staged(self, s: int, template):
        """Restore step ``s`` with array leaves deserialized to host
        numpy — ``RestoreArgs(restore_type=np.ndarray)`` through a
        read-only PyTree reader (StandardSave's on-disk format IS the
        PyTree format; restore_partial uses the same reader shape) —
        then placed onto each leaf's target sharding with
        make_array_from_callback. A plain sharding-free template is
        not enough here: multi-process orbax repopulates the SAVED
        sharding from the step's metadata and hands back a
        non-addressable global array. See _attempt_restore for why
        this path must not let orbax deserialize straight into device
        buffers."""
        host_template = {
            k: (jax.ShapeDtypeStruct(v.shape, v.dtype)
                if isinstance(v, jax.ShapeDtypeStruct) else v)
            for k, v in template.items()}
        restore_args = {
            k: (ocp.RestoreArgs(restore_type=np.ndarray)
                if isinstance(v, jax.ShapeDtypeStruct)
                else ocp.RestoreArgs())
            for k, v in template.items()}
        reader = ocp.CheckpointManager(
            self.directory, item_handlers=ocp.PyTreeCheckpointHandler())
        try:
            restored = reader.restore(
                s, args=ocp.args.PyTreeRestore(
                    item=host_template, restore_args=restore_args))
        finally:
            reader.close()
        out = dict(restored)
        for k, v in template.items():
            sharding = (v.sharding if isinstance(v, jax.ShapeDtypeStruct)
                        else None)
            if sharding is None:
                continue
            arr = np.asarray(restored[k])
            out[k] = jax.make_array_from_callback(
                v.shape, sharding, lambda idx, a=arr: a[idx])
        return out

    def _raise_restore_error(self, s, e) -> None:
        # Orbax surfaces config-mismatch as a shape ValueError (whose
        # advice — enable truncation — is wrong here) or, for a
        # checkpoint predating a template key such as 'vocab', as a
        # tree-structure error. The same exception classes can also
        # mean a corrupt/partial step directory (killed writer), so
        # the advice names both causes rather than steering a user
        # toward discarding a recoverable checkpoint.
        raise ValueError(
            f"checkpoint at {self.directory} step {s} could not be "
            "restored against this config's layout. Most likely the "
            "checkpoint was written under a different config "
            "(vocabulary_size / factor_num / model_type) or an older "
            "storage layout — fix the config or point model_file at "
            "the matching checkpoint. If the config is right, this "
            "step directory may be corrupt/partially written (killed "
            "save): newer bad steps are quarantined automatically as "
            "corrupt-<step>; inspect the directory with `python -m "
            f"tools.fmckpt ls`. Underlying error: {e}") from e

    def close(self) -> None:
        """Settle any in-flight async save (and its owed manifest)
        before releasing the manager — close is the last point a
        crashed-out driver can make the newest step verifiable."""
        try:
            self.wait_until_finished()
        finally:
            self._mngr.close()


# Rows a block of a one-device state's snapshot and of its placement
# after a restore; one block is on its way while the one before it
# lands. A block of 2^16 rows of a k=16 table is 6.3 MB on the device (a
# row of 17 floats tiles to 24) and 4.5 MB flat: two alive raise the
# v5e's peak 20 MB over the resident state, under the 27.6 MB of the
# train step's own temporaries at fm-k16-criteo1tb's size, so a save or
# a resume leaves the device's peak where training put it (a second
# block on its way: 25 MB and 5.1 GB/s where 3.7; 2^17 rows: 43 MB).
# PERF.md, PR 52.
STATE_BLOCK_ROWS = 1 << 16


class HostSnapshot(np.ndarray):
    """A host array that a save owns from the moment it is made: nothing
    writes to it again, so orbax's defensive ``copy.deepcopy`` of a
    NumPy leaf (type_handlers.NumpyHandler.serialize, on the caller's
    thread) would only double the pause and the host's memory."""

    def __deepcopy__(self, memo):
        return self


def _block_starts(rows: int, block: int):
    """Starts of ``block``-row blocks that cover ``rows`` rows; the last
    is moved back to end on the last row (it overlaps the one before),
    so that one program serves every block."""
    return [min(a, rows - block) for a in range(0, rows, block)]


def _rows_to_host(arr: jax.Array, out: np.ndarray) -> None:
    """``out[:] = arr`` in row blocks: the next block's slice and copy
    to the host are under way while this one lands in ``out``."""
    rows = int(arr.shape[0])
    block = min(STATE_BLOCK_ROWS, rows)
    landing = None
    for a in _block_starts(rows, block) + [None]:
        ahead = None
        if a is not None:
            ahead = (a, _take_rows(arr, a, block=block))
            ahead[1].copy_to_host_async()
        if landing is not None:
            at, blk = landing
            out[at:at + block] = np.asarray(blk).reshape(block, -1)
        landing = ahead


@functools.partial(jax.jit, static_argnames="block")
def _take_rows(arr, start, *, block: int):
    """``arr[start:start + block]`` flattened: one row after another is
    what the copy to the host moves at the link's pace, whatever the
    tiling of a narrow table on the device."""
    return jax.lax.dynamic_slice_in_dim(arr, start, block).reshape(-1)


@functools.partial(jax.jit, donate_argnums=0)
def _put_rows(dst, blk, start):
    return jax.lax.dynamic_update_slice_in_dim(dst, blk, start, 0)


def snapshot_buffers(cfg):
    """The host side of a one-device state's save: a [ckpt_rows, D]
    pair whose dead tail is the contract's (zero for the table,
    adagrad_init for the accumulator); the rows before it are the
    snapshot's to fill."""
    pair = []
    for tail in (0.0, cfg.adagrad_init):
        host = np.empty((cfg.ckpt_rows, cfg.row_dim),
                        np.float32).view(HostSnapshot)
        host[cfg.num_rows:] = tail
        pair.append(host)
    return tuple(pair)


def saver_buffers(cfg, table: jax.Array, acc: jax.Array):
    """What a one-device job that saves periodically makes at its
    start and keeps for its life: the host pair every save is taken
    into, filled once by a snapshot of the state it starts from. On the
    v5e's host a fresh page costs more than the copy that fills it (4.6
    s of faults a 4.6 GB array where the copy takes 1.2), and a
    process's first pass over the copy's path 3.3 s more than a later
    one (PERF.md, PR 52): paid here, a save's pause is the copy alone
    and compiles nothing. A save sees a train step's results, which are
    committed to their device, and jax keeps a program apart for a
    fresh array that is not: the same buffers go in under a committed
    label (no copy is made). The write before a save is settled before
    the save overwrites the pair."""
    pair = snapshot_buffers(cfg)
    for arr, host in zip((table, acc), pair):
        _rows_to_host(jax.device_put(arr, arr.sharding),
                      host[:cfg.num_rows])
    return pair


def ckpt_state(cfg, table: jax.Array, acc: jax.Array, into=None):
    """Checkpoint contract: always store [ckpt_rows, D] — the fixed
    4096-aligned row layout (FmConfig.ckpt_rows) every topology shares,
    so a checkpoint saved by any mesh restores row-sharded on any other
    without assembling the table on one host. Mesh tables are already
    this shape (orbax saves them sharded — each host writes only its
    rows, and snapshots them itself). A one-device state is
    [num_rows, D]: its snapshot is taken HERE, in row blocks into the
    host pair ``into`` (``snapshot_buffers``; a pair of this save's own
    where none is given), so that the device holds no second table
    while it is saved (a padded copy on the device was 6.4 GB beside a
    resident 12.9 at fm-k16-criteo1tb's size on a 16 GB chip). What
    comes back is the state after the steps dispatched so far, whole:
    the caller's next step may donate ``table`` and ``acc``. Whoever
    passes ``into`` has settled the save that last used it
    (``CheckpointState.settle``)."""
    n = int(table.shape[0])
    if n == cfg.ckpt_rows:
        return table, acc
    with span("checkpoint/snapshot", seconds="checkpoint/snapshot_seconds"):
        if into is None:
            into = snapshot_buffers(cfg)
        # The step that makes the state has run before a block is cut:
        # its temporaries are gone when the blocks' buffers are made.
        jax.block_until_ready((table, acc))
        for arr, host in zip((table, acc), into):
            _rows_to_host(arr, host[:n])
    return into


def place_restored(restored: Dict[str, Any], rows: int):
    """``(table, acc)`` on the default device from a restore that
    landed on the host (``checkpoint_template(host=True)``): the first
    ``rows`` rows of each, placed in blocks, so that the device never
    holds the [ckpt_rows, D] pair beside the state (a second table,
    which does not fit at fm-k16-criteo1tb's size). Each host copy is
    let go as soon as it is placed."""
    out = []
    with span("checkpoint/place", seconds="checkpoint/place_seconds"):
        for name in ("table", "acc"):
            out.append(device_rows(restored[name], rows))
            restored[name] = None
    return tuple(out)


def device_rows(host: np.ndarray, rows: int) -> jax.Array:
    """The first ``rows`` rows of a restored host array as a new array
    on the default device, placed in row blocks into a donated buffer:
    the device never holds more than the array and two blocks (a
    [ckpt_rows, D] restore sliced on the device was a second table)."""
    block = min(STATE_BLOCK_ROWS, rows)
    dst = jax.numpy.zeros((rows,) + host.shape[1:], host.dtype)
    for i, a in enumerate(_block_starts(rows, block)):
        dst = _put_rows(dst, host[a:a + block], a)
        if i % 2:       # the host runs no further ahead than two blocks
            dst.block_until_ready()
    return dst


def checkpoint_template(cfg, mesh=None, host: bool = False):
    """Abstract pytree matching CheckpointState.save's layout — orbax
    needs it to restore from a process that didn't do the saving.

    The explicit sharding makes restore topology-portable: orbax places
    the arrays per THIS run's layout instead of repopulating whatever
    sharding the saving topology recorded (which, for a multi-host save
    restored elsewhere, would yield non-addressable arrays).

    ``host`` leaves the leaves sharding-free, which makes orbax restore
    plain np.ndarrays into host RAM — the offload-backend path, where
    the table must never land on a device."""
    shape = (cfg.ckpt_rows, cfg.row_dim)
    if host:
        return {"table": jax.ShapeDtypeStruct(shape, np.float32),
                "acc": jax.ShapeDtypeStruct(shape, np.float32),
                "step": 0, "epoch": 0, "vocab": 0}
    if mesh is not None:
        from jax.sharding import NamedSharding
        from fast_tffm_tpu.parallel.sharded import ROW_SPEC
        sh = NamedSharding(mesh, ROW_SPEC)
    else:
        sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return {"table": jax.ShapeDtypeStruct(shape, np.float32, sharding=sh),
            "acc": jax.ShapeDtypeStruct(shape, np.float32, sharding=sh),
            "step": 0, "epoch": 0, "vocab": 0}


def resume_start_epoch(stored_epoch: int, epoch_num: int) -> int:
    """Where a restarted run's epoch loop begins.

    An INTERRUPTED schedule (0 < stored < epoch_num) resumes at the
    first incomplete epoch — restarting from zero would revisit the
    same data under the same per-epoch seeds and, under preemptions
    recurring faster than a full schedule, never terminate. A COMPLETED
    checkpoint (stored >= epoch_num, or a smaller epoch_num configured
    since) keeps the reference's semantics: invoking train again runs a
    fresh epoch_num-epoch schedule on top of the restored weights (the
    reference's TF1 queue epoch counters were process-local and never
    checkpointed, so it behaved exactly this way)."""
    return stored_epoch if 0 < stored_epoch < epoch_num else 0


def check_restored_vocab(cfg, restored) -> None:
    """The 4096-aligned storage shape can't distinguish vocabularies in
    the same bucket, so the stored vocab is verified explicitly — a
    mismatch would silently turn a trained row into the pad row."""
    v = int(restored["vocab"])
    if v != cfg.vocabulary_size:
        raise ValueError(
            f"checkpoint was written with vocabulary_size={v}, but this "
            f"config has vocabulary_size={cfg.vocabulary_size}; restoring "
            "would misalign the pad row and feature ids. Retrain, or fix "
            "the config.")


def _caused_by(e: BaseException, classes) -> bool:
    """Whether any link of ``e``'s explicit ``raise ... from`` chain is
    one of ``classes``."""
    seen = set()
    while e is not None and id(e) not in seen:
        if isinstance(e, classes):
            return True
        seen.add(id(e))
        e = e.__cause__
    return False


def _restore_tolerating_legacy_epoch(template, do_restore):
    """Run ``do_restore(template)``; on tree/shape errors retry ONCE
    without the 'epoch' leaf (checkpoints written before that leaf
    existed must stay restorable — an upgraded binary has to resume a
    preempted job's old checkpoint), defaulting the leaf to 0. Returns
    (restored, None) on success or (None, original_error) when both
    attempts fail — the caller owns the diagnostic. The one
    implementation for restore() and restore_partial(); a genuine
    config mismatch pays one wasted retry on this already-failing
    path, the price of not needing a metadata side-channel."""
    try:
        return do_restore(template), None
    except (ValueError, KeyError) as e:
        if "epoch" not in template:
            return None, e
        legacy = {k: v for k, v in template.items() if k != "epoch"}
        try:
            restored = do_restore(legacy)
        except (ValueError, KeyError):
            return None, e
        restored["epoch"] = 0
        return restored, None


def export_npz(table, path: str,
               vocabulary_size: Optional[int] = None) -> None:
    """Dense export of the parameter table for parity checks / external
    consumers. Pass ``vocabulary_size`` to slice off dead rows exactly:
    the pad row at index ``vocabulary_size`` plus any divisibility pad
    rows a mesh-sharded table carries (parallel/sharded.padded_num_rows).
    Without it, only the single trailing pad row is dropped (valid for
    unsharded tables only)."""
    arr = np.asarray(table)
    arr = arr[:vocabulary_size] if vocabulary_size is not None else arr[:-1]
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    np.savez_compressed(path, table=arr)
