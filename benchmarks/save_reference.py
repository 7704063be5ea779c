"""The reference of a save's guarantee (cell ``fm16-train-save``,
``drivers/train_save.py``, SAVE.md): a committed step IS the state
after exactly its step's steps, in the checkpoint's contract on disk.

Nothing here comes from ``fast_tffm_tpu/checkpoint.py``: the step
directory is read by orbax's plain restore into host NumPy, and what it
holds is held against what the driver fetched from the device at the
save (the rows the corpus touches), against ``weights.py``'s own rows
and the configuration's ``adagrad_init`` (every row the corpus cannot
have touched) and against the contract's shape and tail:
``[ckpt_rows, D]`` float32 for table and accumulator, ``ckpt_rows`` the
table's rows rounded up to 4,096, the tail past the last row zero
(table) and ``adagrad_init`` (accumulator). Every comparison is of
bits; every count's limit is 0."""

from __future__ import annotations

import os

import numpy as np

from benchmarks import weights

CONTRACT_ROW_MULTIPLE = 4096
SCALARS = ("step", "epoch", "vocab")


def contract_rows(num_rows: int) -> int:
    return -(-int(num_rows) // CONTRACT_ROW_MULTIPLE) * CONTRACT_ROW_MULTIPLE


def read_step(directory: str, step: int) -> dict:
    """The committed step as it lies on disk, arrays as host NumPy:
    ``{"table", "acc", "step", "epoch", "vocab"}``. Raises what orbax
    raises on a directory it cannot read (the driver counts that as
    every row off)."""
    import orbax.checkpoint as ocp
    as_numpy = ocp.RestoreArgs(restore_type=np.ndarray)
    args = {"table": as_numpy, "acc": as_numpy}
    args.update({k: ocp.RestoreArgs() for k in SCALARS})
    reader = ocp.PyTreeCheckpointer()
    try:
        saved = reader.restore(
            os.path.join(directory, str(int(step)), "default"),
            args=ocp.args.PyTreeRestore(restore_args=args))
    finally:
        reader.close()
    return {k: saved[k] for k in args}


def _bits(a) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)


def rows_differ(a, b) -> np.ndarray:
    """bool [n]: rows of two float32 [n, D] arrays that differ in a bit."""
    return (_bits(a) != _bits(b)).any(axis=1)


def rows_not_of_step(saved, ids, table_rows, acc_rows) -> int:
    """Rows among ``ids`` whose saved table or accumulator row is not,
    bit for bit, the device's at the save. ``saved`` None (the step
    could not be read): every row."""
    if saved is None:
        return len(ids)
    return int((rows_differ(saved["table"][ids], table_rows)
                | rows_differ(saved["acc"][ids], acc_rows)).sum())


def untouched_sample(touched, num_rows: int, n: int, seed: int) -> np.ndarray:
    """``n`` seeded rows of [0, num_rows - 1) that the corpus never
    touches, sorted."""
    rng = np.random.default_rng([int(seed), 0x5A7E])
    draw = np.unique(rng.integers(0, num_rows - 1, size=int(n),
                                  dtype=np.int64))
    return np.setdiff1d(draw, touched, assume_unique=True)


def untouched_rows_off(saved, touched, num_rows: int, dim: int, seed: int,
                       value_range: float, adagrad_init: float,
                       sample: int) -> int:
    """What no step can have written: a seeded sample of rows the
    corpus never touches (table: the seeded initial row; accumulator:
    ``adagrad_init``), the padding row ``num_rows - 1`` and every row
    of the tail past it (table zero, accumulator ``adagrad_init``),
    plus one for each array whose shape or dtype is not the
    contract's. ``saved`` None: the whole sample."""
    ids = untouched_sample(touched, num_rows, sample, seed)
    if saved is None:
        return len(ids)
    rows = contract_rows(num_rows)
    off = 0
    for name in ("table", "acc"):
        a = saved[name]
        if a.shape != (rows, dim) or a.dtype != np.float32:
            off += 1
    if off:
        return off + len(ids)
    init = np.full((1, dim), adagrad_init, np.float32)
    off += int(rows_differ(saved["table"][ids], weights.table_rows_numpy(
        ids, dim, seed, value_range, num_rows)).sum())
    off += int(rows_differ(saved["acc"][ids],
                           np.broadcast_to(init, (len(ids), dim))).sum())
    tail = slice(num_rows - 1, rows)
    n_tail = rows - (num_rows - 1)
    off += int(rows_differ(saved["table"][tail],
                           np.zeros((n_tail, dim), np.float32)).sum())
    off += int(rows_differ(saved["acc"][tail],
                           np.broadcast_to(init, (n_tail, dim))).sum())
    return off


def scalars_off(saved, at_save: dict) -> int:
    """Scalars of the saved step that are not the loop's own at the
    save (``at_save``: step, epoch, vocab)."""
    if saved is None:
        return len(SCALARS)
    return sum(int(saved[k]) != int(at_save[k]) for k in SCALARS)
