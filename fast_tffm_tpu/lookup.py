"""Pluggable embedding-lookup backends — the SparseCore/offload seam.

The reference keeps its embedding behind TF's parameter-server variable
machinery (SURVEY.md §2 "Model parameters", §3.2): workers gather only
the batch-active rows and push sparse Adagrad updates; the table's
storage (how many PS tasks, where the blocks live) is invisible to the
training math. This module makes that seam explicit for the TPU rebuild
(BASELINE config #5: 10^9 hashed features need the table OUTSIDE device
HBM):

- the jitted compute owns everything between ``gathered rows in`` and
  ``row gradients out`` (models/fm.py ``grad_body``/``rows_score_body``);
- a backend owns storage, ``gather`` and the sparse-Adagrad ``apply``.

Backends (selected by ``FmConfig.lookup``):

- **device** (default): table + accumulator live as jax arrays —
  single-device or mesh row-sharded — with gather/update fused into the
  train-step jit (models/fm.py train_step_body, parallel/sharded.py).
  Fastest when the table fits device memory; the mesh scales it the way
  adding PS tasks did.
- **host** (``make_offload_backend`` picks the best implementation):

  - ``PinnedHostLookup`` — table + accumulator are jax arrays placed in
    the accelerator host's memory (``memory_kind="pinned_host"``
    shardings) and the WHOLE step stays inside jitted programs: the
    gather/scatter run in host memory space (``compute_on
    "device_host"``), the FM math on the chip, and nothing ever blocks
    Python — the async dispatch stream the device path enjoys, with the
    state outside HBM. This is the device-resident offload mechanism
    BASELINE config #5 names (SparseCore being the other; no
    jax-tpu-embedding in this environment). On backends whose "device"
    memory IS host RAM (cpu), the same programs run without the
    memory-kind annotations (``mode="plain"``) — identical structure,
    trivially-true placement — which is what the hermetic CPU tests
    exercise.
  - ``HostOffloadLookup`` — table + accumulator in local numpy; the
    device only holds the batch's ``[U, D]`` gathered rows and their
    gradients. Pays a blocking device->host gradient fetch per step
    (inherent: the host update needs the bytes), so it is the fallback
    when the backend can't compile host-memory-space programs
    (``probe_placement_mode`` decides once, with a warning).

Storage layout is the checkpoint layout ([ckpt_rows, D], 4096-aligned —
config.FmConfig.ckpt_rows) so save/restore is allocation-free.
``tools/offload_smoke.py`` runs the at-scale accounting check.

**The adapter contract (e.g. a SparseCore backend).** A new storage
engine plugs in by implementing the three-method seam both existing
backends share — nothing else in the framework knows where rows live:

- ``gather(uniq_ids) -> [U, D] rows`` (device-consumable; padding
  slots, ``uniq_ids == pad_id``, may return anything — their gradients
  come back masked to zero);
- ``apply_grad(uniq_ids, grad_rows, lr)`` — sparse Adagrad on exactly
  those rows (duplicate pad slots are zero-gradient no-ops);
- ``state() -> (table, acc)`` in the [ckpt_rows, D] checkpoint layout,
  host-fetchable, for CheckpointState save/restore.

Wire-up is two switch points: ``make_offload_backend`` (train) and
``make_score_backend`` (predict; scoring needs only ``gather`` +
``table``). In an environment WITH jax-tpu-embedding, a SparseCore
adapter maps ``gather``/``apply_grad`` onto its embedding-table
lookup/update primitives and keeps ``state()`` as the HBM/host fetch of
its shards — the train loop, checkpointing, and predict then work
unchanged, exactly as they do for the two backends here.

**Wire format (README "Wire format").** The offload SCORE path rides
``wire_format = packed``: the encoder withholds ``uniq_ids`` for the
host-side ``gather`` (``WireBatch.host_uniq``) and only the gathered
``[U, D]`` rows plus the flat CSR cross the wall — the rectangles are
rebuilt on-device inside ``models/fm.packed_rows_score_body``, whose
pad slot is the gathered block's last row (the same contract
``rows_score_body`` inherits from the padded wire). The offload TRAIN
step stays on the padded wire (``wire.resolve_wire`` downgrades with a
warning): its host gather and host scatter consume the numpy batch
arrays directly, so there is no device-side unpack to fold them into.
"""

from __future__ import annotations

import contextlib
import functools
import warnings
from typing import Optional

import numpy as np

from fast_tffm_tpu.config import FmConfig


class HostOffloadLookup:
    """Host-RAM embedding store with vectorized sparse Adagrad.

    ``uniq_ids`` rows are unique by the pipeline's host-side dedup
    (padding slots repeat ``pad_id``, but their gradients are masked to
    zero, so plain fancy-indexed updates are exact — no ``np.add.at``
    slow path needed).
    """

    # Above this many rows, initialization happens host-side (numpy) in
    # place; below it, we mirror models.fm.init_table exactly (same jax
    # PRNG stream) so backends are interchangeable in tests.
    _DEVICE_INIT_MAX_ROWS = 1 << 24

    def __init__(self, cfg: FmConfig, seed: int = 0,
                 _init: bool = True):
        self.cfg = cfg
        self.rows = cfg.ckpt_rows
        self.dim = cfg.row_dim
        if not _init:
            # Restore path: allocate nothing — load()/from_checkpoint
            # assign the restored arrays directly, so peak host memory is
            # one copy of the state, not two (a config-#5 table is tens
            # of GB; a transient second copy is an OOM).
            self.table: Optional[np.ndarray] = None
            self.acc: Optional[np.ndarray] = None
            return
        if cfg.num_rows <= self._DEVICE_INIT_MAX_ROWS:
            from fast_tffm_tpu.models.fm import init_table
            self.table = np.zeros((self.rows, self.dim), np.float32)
            self.table[:cfg.num_rows] = np.asarray(init_table(cfg, seed))
        else:
            # Huge tables never touch a device: host-side init with the
            # same distribution (PRNG stream differs from the device
            # init — irrelevant at this scale, documented).
            rng = np.random.default_rng(seed)
            self.table = np.zeros((self.rows, self.dim), np.float32)
            r = cfg.init_value_range
            chunk = 1 << 22
            for a in range(0, cfg.num_rows - 1, chunk):
                b = min(a + chunk, cfg.num_rows - 1)
                self.table[a:b] = rng.uniform(
                    -r, r, size=(b - a, self.dim)).astype(np.float32)
        self.acc = np.full((self.rows, self.dim), cfg.adagrad_init,
                           np.float32)

    # --- the three seam methods -------------------------------------

    def gather(self, uniq_ids: np.ndarray) -> np.ndarray:
        """[U] ids -> [U, D] rows (pad ids hit the dead zero row)."""
        return self.table[uniq_ids]

    def apply_grad(self, uniq_ids: np.ndarray, grad_rows: np.ndarray,
                   lr: float) -> None:
        """Sparse Adagrad on the touched rows: acc += g^2;
        table -= lr * g / sqrt(acc). The same maths as
        models.fm.sparse_adagrad_apply, in NumPy on the host; that
        one's two-operand scatter (PR 38) is the device backend's
        alone."""
        g = np.asarray(grad_rows, dtype=np.float32)
        ids = np.asarray(uniq_ids)
        a = self.acc[ids] + np.square(g)
        self.acc[ids] = a
        self.table[ids] -= lr * g / np.sqrt(a)

    def state(self):
        """(table, acc) in the checkpoint layout — zero-copy."""
        return self.table, self.acc

    def reset_rows(self, rows: np.ndarray,
                   adagrad_init: float = 0.1) -> None:
        """Cold-start the given physical rows: zero embeddings,
        re-initialized accumulator. The vocab-admission barrier's
        eviction hook (vocab/table.py) — an evicted id's old row must
        not leak its trained embedding to the row's next owner. Part
        of the slot-indirection seam every backend implements (the
        device path uses vocab.table.reset_table_rows)."""
        self.table[rows] = 0.0
        if self.acc is not None:
            self.acc[rows] = np.float32(adagrad_init)

    # --- persistence -------------------------------------------------

    def load(self, table: np.ndarray,
             acc: Optional[np.ndarray] = None) -> None:
        """``acc=None`` leaves the accumulator unset — valid for
        gather/score-only use (predict); ``apply_grad`` would fault."""
        expect = (self.rows, self.dim)
        if tuple(table.shape) != expect:
            raise ValueError(f"restored table shape {table.shape} != "
                             f"{expect}")
        # No-copy when the restored arrays are already f32 numpy (the
        # orbax restore path): at offload scale a dtype-converting copy
        # here would double peak memory.
        self.table = np.asarray(table, np.float32)
        self.acc = None if acc is None else np.asarray(acc, np.float32)

    @classmethod
    def for_table(cls, cfg: FmConfig, table) -> "HostOffloadLookup":
        """Score-only backend around an existing host table — the
        predict path for a caller-held table (e.g. train()'s offload
        return value). Accepts the logical [num_rows, D] or checkpoint
        [ckpt_rows, D] layout; gather only ever indexes rows <= pad_id,
        so either suffices. No accumulator, no copy for f32 numpy
        input."""
        arr = np.asarray(table, np.float32)
        if (arr.shape[0] not in (cfg.num_rows, cfg.ckpt_rows)
                or arr.shape[1] != cfg.row_dim):
            raise ValueError(
                f"table shape {arr.shape} matches neither the logical "
                f"[{cfg.num_rows}, {cfg.row_dim}] nor the checkpoint "
                f"[{cfg.ckpt_rows}, {cfg.row_dim}] layout")
        self = cls(cfg, _init=False)
        self.table = arr
        return self

    @classmethod
    def from_checkpoint(cls, cfg: FmConfig,
                        with_acc: bool = True) -> "HostOffloadLookup":
        """Restore straight into host RAM. The template's abstract
        sharding-free leaves make orbax materialize plain np.ndarrays —
        nothing lands on a device (a config-#5 table would not fit
        there) and no throwaway template arrays are allocated.

        ``with_acc=False`` (the predict path) restores the table leaf
        only: inference never touches the Adagrad accumulator, and at
        offload scale materializing it would double peak host RSS."""
        from fast_tffm_tpu.checkpoint import (CheckpointState,
                                              check_restored_vocab,
                                              checkpoint_template)
        from fast_tffm_tpu.utils.retry import RetryPolicy
        ckpt = CheckpointState(cfg.model_file,
                               retry=RetryPolicy.from_config(cfg),
                               verify=getattr(cfg, "ckpt_verify", "size"))
        template = checkpoint_template(cfg, host=True)
        if with_acc:
            restored = ckpt.restore(template=template)
        else:
            template.pop("acc")
            restored = ckpt.restore_partial(template)
        ckpt.close()
        if restored is None:
            raise FileNotFoundError(
                f"no checkpoint found under {cfg.model_file}.ckpt")
        check_restored_vocab(cfg, restored)
        self = cls(cfg, _init=False)
        self.load(np.asarray(restored["table"]),
                  np.asarray(restored["acc"]) if with_acc else None)
        self.step = int(restored["step"])
        return self


# ---------------------------------------------------------------------------
# Device-resident offload: pinned-host jax state, fully in-jit step.
# ---------------------------------------------------------------------------

_PLACEMENT_MODE: Optional[list] = None  # [None | "plain" | "pinned"]


def probe_placement_mode() -> Optional[str]:
    """Which in-jit host-memory placement this backend supports, probed
    once per process by COMPILING AND RUNNING a tiny program with the
    exact structure the real step uses (host-space gather + scatter,
    device math, donated pinned state):

    - ``"pinned"``: real ``memory_kind="pinned_host"`` shardings with
      the host segments under ``compute_on("device_host")`` (TPU).
    - ``"plain"``: same program, no memory-space annotations — only on
      backends whose device memory IS host RAM (cpu), where the
      annotation machinery doesn't exist but the placement claim is
      trivially true.
    - ``None``: neither compiles/runs; callers fall back to the numpy
      backend.
    """
    global _PLACEMENT_MODE
    if _PLACEMENT_MODE is not None:
        return _PLACEMENT_MODE[0]
    import jax
    import jax.numpy as jnp
    if jax.default_backend() == "cpu":
        _PLACEMENT_MODE = ["plain"]
        return "plain"
    try:
        from jax.experimental.compute_on import compute_on
        from jax.sharding import SingleDeviceSharding
        dev = jax.devices()[0]
        s_host = SingleDeviceSharding(dev, memory_kind="pinned_host")
        s_dev = SingleDeviceSharding(dev, memory_kind="device")

        # The probe mirrors the real programs' structure exactly:
        # spaceless avals throughout (state created by jit out_shardings,
        # NOT device_put — a device_put-created pinned array carries a
        # memory-space-annotated aval that poisons later traces), host
        # segments as bare compute_on blocks, XLA inserting transfers.
        @functools.partial(jax.jit, out_shardings=s_host)
        def alloc():
            return jnp.zeros((8, 4), jnp.float32)

        @functools.partial(jax.jit, donate_argnums=(0,),
                           out_shardings=(s_host, s_dev))
        def step(tab, ids, upd):
            with compute_on("device_host"):
                rows = tab[ids]
            new_rows = rows + upd
            with compute_on("device_host"):
                tab2 = tab.at[ids].set(new_rows)
            return tab2, new_rows.sum()

        tab = alloc()
        tab, total = step(tab, jnp.array([1, 3]), jnp.ones((2, 4)))
        jax.block_until_ready((tab, total))
        ok = (float(total) == 8.0
              and tab.sharding.memory_kind == "pinned_host")
        _PLACEMENT_MODE = ["pinned" if ok else None]
    except Exception as e:  # compile or runtime rejection -> fallback
        warnings.warn(
            f"pinned-host offload probe failed on this backend "
            f"({type(e).__name__}: {str(e)[:200]}); lookup = host uses "
            "the numpy fallback with a blocking per-step gradient fetch")
        _PLACEMENT_MODE = [None]
    return _PLACEMENT_MODE[0]


@functools.lru_cache(maxsize=None)
def _placement(pinned: bool):
    """(host_sharding, device_sharding, host_ctx) — the placement hooks
    every pinned program shares. Ops inside ``host_ctx`` are scheduled
    on the accelerator host (XLA inserts the transfers); avals stay
    memory-space-free throughout (see probe_placement_mode). In plain
    mode both shardings are the plain device placement and the ctx is a
    no-op."""
    import jax
    from jax.sharding import SingleDeviceSharding
    dev = jax.devices()[0]
    if not pinned:
        s = SingleDeviceSharding(dev)
        return s, s, contextlib.nullcontext
    from jax.experimental.compute_on import compute_on
    s_host = SingleDeviceSharding(dev, memory_kind="pinned_host")
    s_dev = SingleDeviceSharding(dev, memory_kind="device")
    return s_host, s_dev, lambda: compute_on("device_host")


@functools.lru_cache(maxsize=None)
def _commit_fn(pinned: bool):
    """jit identity placing a host/numpy array into the state sharding —
    the ONLY way state enters the backend (a device_put with a memory
    kind would stamp the array's aval with a memory space and poison
    every later trace against spaceless-aval programs)."""
    import jax
    s_host, _, _ = _placement(pinned)
    return jax.jit(lambda x: x, out_shardings=s_host)


@functools.lru_cache(maxsize=None)
def _reset_rows_fn(pinned: bool, dim: int, adagrad_init: float):
    """jit: zero the given table rows / re-init the acc rows, in the
    state placement — the pinned backend's half of the vocab eviction
    seam (fixed RESET_CHUNK-wide index array: one compile ever)."""
    import jax
    from fast_tffm_tpu.vocab.table import reset_body
    s_host, _, ctx = _placement(pinned)

    @functools.partial(jax.jit, donate_argnums=(0, 1),
                       out_shardings=(s_host, s_host))
    def reset(table, acc, rows):
        with ctx():
            return reset_body(table, acc, rows, adagrad_init)

    return reset


@functools.lru_cache(maxsize=None)
def _gather_fn(pinned: bool):
    """jit: (table_host [R, D], ids [U]) -> device rows [U, D]."""
    import jax
    s_host, s_dev, ctx = _placement(pinned)

    @functools.partial(jax.jit, out_shardings=s_dev)
    def gather(table, ids):
        with ctx():
            rows = table[ids]
        return rows

    return gather


@functools.lru_cache(maxsize=None)
def _apply_fn(pinned: bool):
    """jit: sparse Adagrad on host-resident state, gradients already on
    device. Same math as models.fm.sparse_adagrad_apply (uniq ids;
    padding rows carry zero grads, so duplicate pad-slot writes all
    store identical values), but its own program: gathers and
    scatter-sets in host memory space, where that one gathers the
    accumulator and adds to both arrays in one two-operand scatter
    (PR 38)."""
    import jax
    from jax import lax
    s_host, s_dev, ctx = _placement(pinned)

    @functools.partial(jax.jit, donate_argnums=(0, 1),
                       out_shardings=(s_host, s_host))
    def apply(table, acc, ids, grad, lr):
        with ctx():
            acc_rows = acc[ids]
            rows = table[ids]
        new_acc = acc_rows + jax.numpy.square(grad)
        new_rows = rows - lr * grad * lax.rsqrt(new_acc)
        with ctx():
            acc2 = acc.at[ids].set(new_acc)
            table2 = table.at[ids].set(new_rows)
        return table2, acc2

    return apply


@functools.lru_cache(maxsize=None)
def _fused_step_fn(spec, pinned: bool):
    """jit: ONE program for the whole offload train step — host-space
    gathers, device FM forward/backward (models.fm.grad_body: the same
    middle the device and numpy backends use), host-space Adagrad
    writes. Donated state, nothing returned to Python but device
    scalars; the dispatch stream never blocks."""
    import jax
    from jax import lax
    from fast_tffm_tpu.models.fm import grad_body
    s_host, s_dev, ctx = _placement(pinned)

    @functools.partial(
        jax.jit, donate_argnums=(0, 1),
        out_shardings=(s_host, s_host, s_dev, s_dev))
    def step(table, acc, labels, weights, uniq_ids, local_idx, vals,
             fields=None, *, lr):
        with ctx():
            gathered = table[uniq_ids]
            acc_rows = acc[uniq_ids]
        loss, scores, grad = grad_body(spec, gathered, labels, weights,
                                       uniq_ids, local_idx, vals, fields)
        new_acc = acc_rows + jax.numpy.square(grad)
        new_rows = gathered - lr * grad * lax.rsqrt(new_acc)
        with ctx():
            acc2 = acc.at[uniq_ids].set(new_acc)
            table2 = table.at[uniq_ids].set(new_rows)
        return table2, acc2, loss, scores

    return step


class PinnedHostLookup:
    """Accelerator-host-memory embedding store, fully in-jit.

    Same three seam methods as ``HostOffloadLookup`` (gather /
    apply_grad / state) plus a fused per-step program
    (``make_offload_train_step``). The state lives in the accelerator
    host's pinned memory (``mode="pinned"``) or, on cpu backends, as
    plain arrays (``mode="plain"`` — device memory is host RAM there);
    HBM only ever holds the per-batch [U, D] row blocks either way.
    """

    def __init__(self, cfg: FmConfig, seed: int = 0, _init: bool = True,
                 mode: Optional[str] = None):
        import jax.numpy as jnp
        self.cfg = cfg
        self.rows = cfg.ckpt_rows
        self.dim = cfg.row_dim
        self.mode = mode or probe_placement_mode()
        if self.mode is None:
            raise RuntimeError(
                "this backend supports no in-jit host placement "
                "(probe_placement_mode); use HostOffloadLookup")
        self._pinned = self.mode == "pinned"
        self._s_state = _placement(self._pinned)[0]
        if not _init:
            self.table = None
            self.acc = None
            return
        if cfg.num_rows <= HostOffloadLookup._DEVICE_INIT_MAX_ROWS:
            # Mirror the device backend's init exactly (same PRNG
            # stream) so backends are interchangeable in tests.
            from fast_tffm_tpu.models.fm import init_table
            t = jnp.zeros((self.rows, self.dim), jnp.float32)
            t = t.at[:cfg.num_rows].set(init_table(cfg, seed))
            self.table = _commit_fn(self._pinned)(t)
        else:
            self.table = self._init_big(seed)
        self.acc = self._alloc_full(cfg.adagrad_init)

    # Largest constant-fill HBM temporary we allow: XLA materializes a
    # jitted full()'s broadcast output in HBM even with pinned
    # out_shardings (and compute_on doesn't cover constant fills), so a
    # one-shot alloc caps the state at HBM size — measured failing at
    # 4e8 rows (25.6 GB broadcast vs 17.2 GB HBM) on the v5e chip.
    _ALLOC_SLAB_BYTES = 2 << 30

    def _alloc_full(self, value: float):
        """A [ckpt_rows, D] constant array allocated into the state
        placement. Beyond _ALLOC_SLAB_BYTES (pinned mode), it is built
        as one HBM-bounded seed slab grown to full size by a HOST-space
        constant pad — HBM high-water stays one slab and host-memory
        transient stays ~1x the array (a full-array concatenate would
        transiently hold 2x, which is exactly what broke the SECOND
        array's alloc at 4e8 rows with the first one resident)."""
        import jax
        import jax.numpy as jnp

        from fast_tffm_tpu.obs.memory import table_bytes
        nbytes = table_bytes(rows=self.rows, dim=self.dim)
        if not self._pinned or nbytes <= self._ALLOC_SLAB_BYTES:
            @functools.partial(jax.jit, out_shardings=self._s_state)
            def full():
                return jnp.full((self.rows, self.dim), np.float32(value),
                                jnp.float32)

            return full()
        _, _, ctx = _placement(self._pinned)
        n_seed = min(self.rows,
                     self._ALLOC_SLAB_BYTES // (self.dim * 4))

        @functools.partial(jax.jit, out_shardings=self._s_state)
        def seed():
            return jnp.full((n_seed, self.dim), np.float32(value),
                            jnp.float32)

        @functools.partial(jax.jit, out_shardings=self._s_state)
        def grow(x):
            with ctx():
                return jnp.pad(x, ((0, self.rows - n_seed), (0, 0)),
                               constant_values=np.float32(value))

        out = grow(seed())
        out.block_until_ready()  # free the seed slab before returning
        return out

    def _init_big(self, seed: int):
        """Chunked at-scale init: uniform chunks generated ON DEVICE and
        scatter-written into the host-resident table — the bulk bytes
        never cross the Python/driver boundary (a device_put of the
        whole table would)."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        s_host, s_dev, ctx = _placement(self._pinned)
        chunk = 1 << 22

        def make_fill(n):
            @functools.partial(jax.jit, donate_argnums=(0,),
                               out_shardings=s_host)
            def fill(table, key, start):
                vals = jax.random.uniform(
                    key, (n, self.dim), dtype=jnp.float32,
                    minval=-cfg.init_value_range,
                    maxval=cfg.init_value_range)
                idx = start + jnp.arange(n, dtype=jnp.int32)
                with ctx():
                    return table.at[idx].set(vals)
            return fill

        table = self._alloc_full(0.0)
        key = jax.random.PRNGKey(seed)
        live = cfg.num_rows - 1  # pad row and ckpt tail stay zero
        fill_full = make_fill(chunk)
        for a in range(0, live, chunk):
            key, sub = jax.random.split(key)
            n = min(chunk, live - a)
            fill = fill_full if n == chunk else make_fill(n)
            table = fill(table, sub, jnp.int32(a))
        return table

    # --- the three seam methods -------------------------------------

    def gather(self, uniq_ids):
        """[U] ids -> [U, D] device rows (host-space gather in-jit)."""
        return _gather_fn(self._pinned)(self.table, uniq_ids)

    def apply_grad(self, uniq_ids, grad_rows, lr: float) -> None:
        """Sparse Adagrad on the touched rows, fully in-jit; accepts the
        device gradient array without materializing it to Python."""
        import jax.numpy as jnp
        self.table, self.acc = _apply_fn(self._pinned)(
            self.table, self.acc, uniq_ids, grad_rows, jnp.float32(lr))

    def state(self):
        """(table, acc) jax arrays in the checkpoint layout. They live
        in accelerator-host memory; checkpointing fetches their bytes
        (unavoidable for any durable save)."""
        return self.table, self.acc

    def reset_rows(self, rows, adagrad_init: float = 0.1) -> None:
        """Cold-start the given physical rows in place (the vocab
        eviction hook — see HostOffloadLookup.reset_rows): a jitted
        fixed-width scatter in the state placement, so barriers never
        add a compile per eviction count and the state never leaves
        host memory space."""
        from fast_tffm_tpu.vocab.table import reset_chunks
        fn = _reset_rows_fn(self._pinned, self.dim,
                            float(adagrad_init))
        pad_row = self.rows - 1  # dead ckpt-alignment tail row
        if self.acc is None:
            raise RuntimeError(
                "reset_rows needs the accumulator: eviction resets are "
                "a training-side operation (score-only backends never "
                "see a barrier)")
        for chunk in reset_chunks(rows, pad_row):
            self.table, self.acc = fn(self.table, self.acc, chunk)

    # --- persistence (mirrors HostOffloadLookup) ---------------------

    def load(self, table, acc=None) -> None:
        expect = (self.rows, self.dim)
        if tuple(table.shape) != expect:
            raise ValueError(f"restored table shape {table.shape} != "
                             f"{expect}")
        commit = _commit_fn(self._pinned)
        self.table = commit(np.asarray(table, np.float32))
        self.acc = (None if acc is None else
                    commit(np.asarray(acc, np.float32)))

    @classmethod
    def for_table(cls, cfg: FmConfig, table,
                  mode: Optional[str] = None) -> "PinnedHostLookup":
        """Score-only backend around an existing table (logical or
        checkpoint layout) — the predict path for a caller-held table."""
        arr = np.asarray(table, np.float32)
        if (arr.shape[0] not in (cfg.num_rows, cfg.ckpt_rows)
                or arr.shape[1] != cfg.row_dim):
            raise ValueError(
                f"table shape {arr.shape} matches neither the logical "
                f"[{cfg.num_rows}, {cfg.row_dim}] nor the checkpoint "
                f"[{cfg.ckpt_rows}, {cfg.row_dim}] layout")
        self = cls(cfg, _init=False, mode=mode)
        self.table = _commit_fn(self._pinned)(arr)
        return self

    @classmethod
    def from_checkpoint(cls, cfg: FmConfig, with_acc: bool = True,
                        mode: Optional[str] = None) -> "PinnedHostLookup":
        """Restore into accelerator-host memory (via the host-numpy
        restore path, then one placement copy). The local numpy copy is
        TRANSIENT — ``host`` dies at return, so steady state is one
        copy in accelerator-host memory; the peak overlaps local RAM
        (reading the checkpoint requires it) with the remote placement,
        not 2x of either."""
        host = HostOffloadLookup.from_checkpoint(cfg, with_acc=with_acc)
        self = cls(cfg, _init=False, mode=mode)
        self.load(host.table, host.acc)
        self.step = host.step
        return self


def make_offload_backend(cfg: FmConfig, seed: int = 0, restored=None):
    """The ``lookup = host`` backend chooser: the in-jit pinned-host
    implementation where the backend supports it (probe_placement_mode),
    else the numpy fallback — warned, because the fallback pays a
    blocking device->host gradient fetch every step.

    ``restored``: an already-restored checkpoint dict (train.py's
    restore-on-start); passed through ``load`` so no backend re-reads
    the checkpoint."""
    mode = probe_placement_mode()
    if mode is not None:
        lk = PinnedHostLookup(cfg, seed, _init=restored is None, mode=mode)
    else:
        lk = HostOffloadLookup(cfg, seed, _init=restored is None)
    if restored is not None:
        lk.load(np.asarray(restored["table"]), np.asarray(restored["acc"]))
    return lk


def make_score_backend(cfg: FmConfig, table=None):
    """The ``lookup = host`` predict-side chooser: restore (or wrap a
    caller-held table) into the best available offload backend —
    score-only, so the Adagrad accumulator never materializes."""
    cls_ = (PinnedHostLookup if probe_placement_mode() is not None
            else HostOffloadLookup)
    if table is None:
        return cls_.from_checkpoint(cfg, with_acc=False)
    return cls_.for_table(cfg, table)


def make_offload_train_step(spec, lk, lr: float):
    """One train-step callable over a lookup backend:
    ``step(labels, weights, uniq_ids, local_idx, vals, fields=None) ->
    (loss, scores)`` (device scalars/arrays), updating the backend's
    state in place. The pinned backend runs ONE fused jitted program;
    the numpy backend composes gather -> grad_fn -> apply_grad (its
    apply inherently blocks on the gradient bytes)."""
    import jax.numpy as jnp
    if isinstance(lk, PinnedHostLookup):
        fused = _fused_step_fn(spec, lk.mode == "pinned")
        lr_arr = jnp.float32(lr)

        def step(labels, weights, uniq_ids, local_idx, vals, fields=None):
            lk.table, lk.acc, loss, scores = fused(
                lk.table, lk.acc, labels, weights, uniq_ids, local_idx,
                vals, fields, lr=lr_arr)
            return loss, scores

        return step

    from fast_tffm_tpu.models.fm import make_grad_fn
    grad_fn = make_grad_fn(spec)

    def step(labels, weights, uniq_ids, local_idx, vals, fields=None):
        gathered = lk.gather(uniq_ids)
        loss, scores, grad = grad_fn(gathered, labels, weights, uniq_ids,
                                     local_idx, vals, fields)
        lk.apply_grad(uniq_ids, np.asarray(grad), lr)
        return loss, scores

    return step


def memory_report() -> dict:
    """Host RSS and device memory stats, for the offload smoke's
    accounting (tools/offload_smoke.py).

    ``host_rss_mb`` is CURRENT RSS (/proc/self/status VmRSS) — peak
    RSS is monotone and would bill every freed transient (e.g. the
    synth corpus) to whatever is measured after it; the lifetime peak
    is reported separately. Device stats are ``None`` (absent) when
    the runtime reports none — a 0 here must mean a MEASURED zero, not
    "couldn't measure" (a leak assert passing on an unmeasured 0 is
    vacuous)."""
    import resource
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
    rss = peak  # fallback when /proc is unavailable
    try:
        with open("/proc/self/status") as fh:
            for ln in fh:
                if ln.startswith("VmRSS:"):
                    rss = int(ln.split()[1]) // 1024
                    break
    except OSError:
        pass
    out = {"host_rss_mb": rss, "host_peak_rss_mb": peak}
    # Through the one memory seam (obs/memory.py; fmlint R018): same
    # unmeasured-is-None contract, plus the FM_FAKE_HBM_BYTES test
    # injection for free.
    from fast_tffm_tpu.obs.memory import device_memory_stats
    stats = device_memory_stats()
    def mb(key):  # missing key = UNMEASURED (None), never a fake 0
        if not stats or key not in stats:
            return None
        return stats[key] >> 20
    out["device_in_use_mb"] = mb("bytes_in_use")
    out["device_limit_mb"] = mb("bytes_limit")
    return out
