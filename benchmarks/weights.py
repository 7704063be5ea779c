"""Seeded weights the benchmark owns: a counter-based hash of
(seed, row, column), computed bit for bit the same by one jitted call
on the device (the table the program trains or scores) and by NumPy on
any subset of rows (the reference's copy). The reference therefore
takes no weight from the program.

Values are uniform in [-value_range, value_range), the program's own
initial distribution (models/fm.init_table), with the final row (the
dead padding row) zero."""

from __future__ import annotations

import numpy as np

_C1, _C2, _GOLD = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B1
_M32 = 0xFFFFFFFF


def _keys(seed: int):
    seed = int(seed)
    k0 = (seed ^ 0x5BD1E995) & _M32
    k1 = ((seed >> 32) * _GOLD + 0x27D4EB2F + seed) & _M32
    return k0, k1


def _fmix(x, u32):
    x = x ^ (x >> u32(16))
    x = x * u32(_C1)
    x = x ^ (x >> u32(13))
    x = x * u32(_C2)
    return x ^ (x >> u32(16))


def _unit(rows, cols, seed, u32):
    """uint32 rows/cols (broadcastable) -> uint32 in [0, 2^24)."""
    k0, k1 = _keys(seed)
    h = _fmix(rows ^ u32(k0), u32)
    h = _fmix((h + cols * u32(_GOLD)) ^ u32(k1), u32)
    return h >> u32(8)


def table_rows_numpy(row_ids, dim: int, seed: int, value_range: float,
                     num_rows: int) -> np.ndarray:
    """float32 [len(row_ids), dim]: the rows ``make_table`` puts on the
    device, computed on the host."""
    rows = np.asarray(row_ids, dtype=np.int64)
    with np.errstate(over="ignore"):
        h = _unit(rows.astype(np.uint32)[:, None],
                  np.arange(dim, dtype=np.uint32)[None, :], seed,
                  np.uint32)
    v = (h.astype(np.float32) * np.float32(2.0 ** -23)
         - np.float32(1.0)) * np.float32(value_range)
    v[rows >= num_rows - 1] = 0.0
    return v


def make_table(num_rows: int, dim: int, seed: int, value_range: float,
               total_rows: int = 0, sharding=None):
    """The whole table on the device in one jitted call. ``total_rows``
    > num_rows appends zero rows (the mesh's padded layout);
    ``sharding`` row-shards the result as it is made."""
    import jax
    import jax.numpy as jnp
    total = max(int(total_rows), num_rows)

    def build():
        r = jax.lax.broadcasted_iota(jnp.uint32, (total, dim), 0)
        c = jax.lax.broadcasted_iota(jnp.uint32, (total, dim), 1)
        h = _unit(r, c, seed, jnp.uint32)
        v = (h.astype(jnp.float32) * jnp.float32(2.0 ** -23)
             - jnp.float32(1.0)) * jnp.float32(value_range)
        return jnp.where(r >= jnp.uint32(num_rows - 1),
                         jnp.float32(0.0), v)

    build.__name__ = "bench_make_table"
    return jax.jit(build, out_shardings=sharding)()
