"""Pallas TPU kernel for the fused FM interaction — the native-op core.

The reference's hot ops are C++ TF kernels: ``fm_scorer`` (forward) and
``fm_grad`` (backward) over a CSR batch (SURVEY.md §2, Appendix B). The
TPU-native analogue is this Pallas pair: one fused VMEM pass computes the
linear + (Σv)²−Σv² interaction per example without materialising any of
the [B, L, K] intermediates (z, z², their squares) in HBM, and a
``jax.custom_vjp`` routes autodiff into the matching hand-written
backward kernel — exactly how the reference hooks ``fm_grad`` in via
``RegisterGradient`` (SURVEY §2 "Op wrappers").

Layout: the caller gathers rows ``[B, L, K+1]`` (XLA's dynamic gather is
already optimal for that part) and hands the kernel ``v`` TRANSPOSED to
``[B, K, L]`` — lanes carry L (a bucket size, typically 64+), sublanes
carry K. With K minor instead, Mosaic pads K (often 8) up to the 128
lanes, a 16x VMEM blowup that OOMs scoped vmem at real batch sizes.
``w [B, L]`` and values ``x [B, L]`` ride along; blocked over B. Padded
slots carry ``x == 0`` so they contribute exactly zero to every term
(same invariant as ops/interaction.py).

Backward math (per example, g = dL/dscore):
    dw[l]    = g * x[l]
    dv[l, f] = g * x[l] * (s[f] - z[l, f]),   s = Σ_l z,  z = v * x
    dx[l]    = g * (w[l] + Σ_f v[l, f] * (s[f] - z[l, f]))
The backward kernel recomputes ``s`` from inputs instead of saving
residuals — one extra VMEM reduction in exchange for zero HBM residual
traffic (the rematerialisation trade SURVEY §7 calls for).

Lowering mode is decided from the backend, never inferred from "not
tpu": Mosaic on ``tpu``, the Pallas interpreter on ``cpu`` (the test
path; tests/test_pallas_fm.py pins parity vs the XLA path), and an
error anywhere else. The mode is logged once per process.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from fast_tffm_tpu.utils.logging import get_logger


@functools.lru_cache(maxsize=None)
def _interpret_on(backend: str) -> bool:
    """Whether the kernel runs in the Pallas interpreter on
    ``backend``. Cached, so each mode is logged once per process."""
    if backend == "tpu":
        get_logger().info("pallas FM kernel: lowering through Mosaic "
                          "(backend tpu)")
        return False
    if backend == "cpu":
        get_logger().warning(
            "pallas FM kernel: INTERPRET mode on the cpu backend — a "
            "correctness path for tests, never a fast path")
        return True
    raise RuntimeError(
        f"the Pallas FM kernel lowers through Mosaic on tpu and runs "
        f"interpreted on cpu; backend {backend!r} has neither — set "
        "kernel = xla")


def _interpret() -> bool:
    return _interpret_on(jax.default_backend())


def _block_b(B: int, K: int, L: int) -> int:
    """Rows of B per grid step: the largest divisor of B that keeps one
    v block (lane-padded to 128) within a ~2 MB VMEM budget. The 2-D
    w/x/g/out blocks carry bB on the sublane axis, where Mosaic takes a
    multiple of 8 or the whole array, so the divisor is a multiple of 8
    unless all of B fits one block."""
    lanes = -(-L // 128) * 128
    # K rounds UP to the 8-sublane tile (not max(K, 8)): Mosaic pads the
    # sublane axis, so e.g. K=9 occupies 16 sublanes — counting 9 would
    # understate the real block by up to ~78% and blow the budget for
    # K in 9..15 at large L.
    sublanes = -(-K // 8) * 8
    rows = max(1, (2 << 20) // (sublanes * lanes * 4))
    if B <= rows:
        return B
    for b in range(rows - rows % 8, 0, -8):
        if B % b == 0:
            return b
    raise ValueError(
        f"kernel = pallas cannot block batch_size {B} at K={K}, L={L}: "
        f"no divisor of {B} up to {rows} rows is a multiple of 8; use a "
        "batch_size divisible by 8, or kernel = xla")


def _fwd_kernel(v_ref, w_ref, x_ref, out_ref):
    # keepdims throughout: Mosaic has no layout for the rank-1 [bB]
    # intermediates a plain sum leaves (B = 1 failed to compile).
    v = v_ref[...]                                  # [bB, K, L]
    w = w_ref[...]                                  # [bB, L]
    x = x_ref[...]                                  # [bB, L]
    z = v * x[:, None, :]
    s = jnp.sum(z, axis=-1, keepdims=True)          # [bB, K, 1]
    q = jnp.sum(z * z, axis=-1, keepdims=True)      # [bB, K, 1]
    pair = 0.5 * jnp.sum(s * s - q, axis=1)         # [bB, 1]
    linear = jnp.sum(w * x, axis=-1, keepdims=True)  # [bB, 1]
    out_ref[...] = linear + pair


def _bwd_kernel(v_ref, w_ref, x_ref, g_ref, dv_ref, dw_ref, dx_ref):
    v = v_ref[...]                      # [bB, K, L]
    w = w_ref[...]
    x = x_ref[...]
    g = g_ref[...]                      # [bB, 1]
    z = v * x[:, None, :]
    s = jnp.sum(z, axis=-1, keepdims=True)  # [bB, K, 1]
    sv = s - z                              # [bB, K, L]
    dv_ref[...] = g[:, :, None] * x[:, None, :] * sv
    dw_ref[...] = g * x
    dx_ref[...] = g * (w + jnp.sum(v * sv, axis=1))


def _fm_pallas_raw(v: jax.Array, w: jax.Array, x: jax.Array) -> jax.Array:
    B, K, L = v.shape
    bB = _block_b(B, K, L)
    out = pl.pallas_call(
        _fwd_kernel,
        grid=(B // bB,),
        in_specs=[
            pl.BlockSpec((bB, K, L), lambda i: (i, 0, 0)),
            pl.BlockSpec((bB, L), lambda i: (i, 0)),
            pl.BlockSpec((bB, L), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bB, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), v.dtype),
        interpret=_interpret(),
    )(v, w, x)
    return out[:, 0]


@jax.custom_vjp
def fm_scores_pallas(v: jax.Array, w: jax.Array, x: jax.Array) -> jax.Array:
    """Fused FM forward: scores[B] from v[B,K,L], w[B,L], x[B,L]."""
    return _fm_pallas_raw(v, w, x)


def _fm_fwd(v, w, x):
    return _fm_pallas_raw(v, w, x), (v, w, x)


def _fm_bwd(res, g):
    v, w, x = res
    B, K, L = v.shape
    bB = _block_b(B, K, L)
    dv, dw, dx = pl.pallas_call(
        _bwd_kernel,
        grid=(B // bB,),
        in_specs=[
            pl.BlockSpec((bB, K, L), lambda i: (i, 0, 0)),
            pl.BlockSpec((bB, L), lambda i: (i, 0)),
            pl.BlockSpec((bB, L), lambda i: (i, 0)),
            pl.BlockSpec((bB, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bB, K, L), lambda i: (i, 0, 0)),
            pl.BlockSpec((bB, L), lambda i: (i, 0)),
            pl.BlockSpec((bB, L), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, L), v.dtype),
            jax.ShapeDtypeStruct((B, L), w.dtype),
            jax.ShapeDtypeStruct((B, L), x.dtype),
        ],
        interpret=_interpret(),
    )(v, w, x, g[:, None])
    return dv, dw, dx


fm_scores_pallas.defvjp(_fm_fwd, _fm_bwd)


def fm_batch_scores_pallas(params: jax.Array, local_idx: jax.Array,
                           vals: jax.Array, mesh=None) -> jax.Array:
    """Drop-in for ops.interaction.fm_batch_scores (order=2) with the
    interaction fused in Pallas. The [U, K+1] -> [B, L, K+1] gather (and
    its scatter-add transpose in the VJP) stays in XLA, which lowers
    both optimally; the kernel owns everything after the gather, in the
    lane-friendly [B, K, L] layout.

    ``mesh``: GSPMD has no partitioning rule for a ``pallas_call``, so
    under a sharded jit the kernel is wrapped in ``shard_map`` over the
    batch ("data") axis — each device runs the kernel on its batch
    shard, zero collectives inside (the interaction is per-example).
    The gather stays outside in GSPMD-land, which owns the row-shard
    collectives. This is how kernel='pallas' survives the mesh paths
    (parallel/sharded.py binds the mesh)."""
    from fast_tffm_tpu.ops.interaction import expand_rows
    rows = expand_rows(params, local_idx)
    with jax.named_scope("interaction"):
        v = jnp.swapaxes(rows[..., :-1], 1, 2)   # [B, K, L]
        w = rows[..., -1]
        if mesh is None:
            return fm_scores_pallas(v, w, vals)
        from jax.sharding import PartitionSpec as P
        # check_vma=False: pallas_call declares no varying-mesh-axes
        # rule; the body is per-example with zero collectives, so the
        # manual specs are the whole contract.
        fn = jax.shard_map(
            fm_scores_pallas, mesh=mesh,
            in_specs=(P("data", None, None), P("data", None),
                      P("data", None)),
            out_specs=P("data"), check_vma=False)
        return fn(v, w, vals)
