"""Two arrays' rows added to under ONE index list, in one scatter.

XLA's scatter takes several operands under one index list, and the
TPU's emitter then visits a slot's row in each operand in the same
loop iteration. jax's ``.at[].add`` reaches only the one-operand form,
so a sparse optimizer that updates a table and its accumulator at the
same rows walked the slots once per array, each walk one row's
read-modify-write after another (PERF.md, PR 38: 113 ns a slot apiece
on the v5e; the pair in one loop 160).

The primitive has an abstract evaluation, an eager implementation (a
``jax.jit`` of itself) and a lowering to ``stablehlo.scatter``. It has
NO autodiff and NO batching rule: it is an optimizer's update, nothing
differentiates or vmaps through it, and a caller that tries fails with
jax's own error, which names the primitive. That name is
``scatter-add``, as jax calls its one-operand primitive: an
operation's op path ends in its primitive's name, and the trace's
readers and the benchmark's scope tests know the update's as
``.../adagrad/scatter-add`` (tests/benchmarks/test_scope_metrics.py).
"""

import numpy as np

import jax
from jax.extend.core import Primitive
from jax.extend.mlir import ir
from jax.extend.mlir.dialects import stablehlo as hlo
from jax.interpreters import mlir

pair_scatter_add_p = Primitive("scatter-add")
pair_scatter_add_p.multiple_results = True


def pair_scatter_add(a: jax.Array, b: jax.Array, ids: jax.Array,
                     a_rows: jax.Array, b_rows: jax.Array):
    """``(a', b')`` with ``a'[ids[u]] = a[ids[u]] + a_rows[u]`` and
    ``b'[ids[u]] = b[ids[u]] + b_rows[u]`` for every slot ``u``.

    ``a`` and ``b`` are ``[R, D]`` of one type, ``ids`` ``int32[U]``,
    ``a_rows`` and ``b_rows`` ``[U, D]``. An index outside ``[0, R)``
    is DROPPED (XLA's scatter semantics, what jax's default
    ``.at[].add`` lowers to; nothing clamps or wraps it, a negative
    index included). Slots that repeat a row all add to it, in an
    unspecified order."""
    return tuple(pair_scatter_add_p.bind(a, b, ids, a_rows, b_rows))


@pair_scatter_add_p.def_abstract_eval
def _abstract_eval(a, b, ids, a_rows, b_rows):
    if not (a.ndim == 2 and a.shape == b.shape
            and a.dtype == b.dtype == a_rows.dtype == b_rows.dtype):
        raise TypeError(f"pair_scatter_add: {a.str_short()} and "
                        f"{b.str_short()} must be one [R, D] type, and "
                        f"the rows' ({a_rows.str_short()}, "
                        f"{b_rows.str_short()})")
    if ids.ndim != 1 or ids.dtype != np.int32:
        raise TypeError(f"pair_scatter_add: ids must be int32[U], got "
                        f"{ids.str_short()}")
    want = (ids.shape[0], a.shape[1])
    if a_rows.shape != want or b_rows.shape != want:
        raise TypeError(f"pair_scatter_add: rows must be {want}, got "
                        f"{a_rows.str_short()} and {b_rows.str_short()}")
    # results typed like the operands (what varies under a shard_map
    # included)
    return a, b


pair_scatter_add_p.def_impl(jax.jit(pair_scatter_add_p.bind))


def _lower(ctx, a, b, ids, a_rows, b_rows):
    a_aval, _, ids_aval, _, _ = ctx.avals_in
    dnums = hlo.ScatterDimensionNumbers.get(
        update_window_dims=[1], inserted_window_dims=[0],
        input_batching_dims=[], scatter_indices_batching_dims=[],
        scattered_dims_to_operand_dims=[0], index_vector_dim=1)
    ids = hlo.reshape(
        mlir.aval_to_ir_type(ids_aval.update(shape=ids_aval.shape + (1,))),
        ids)
    # Neither hint changes the TPU's program (ISSUE 38: the same code
    # with and without), and the callers' pad slots do repeat a row.
    op = hlo.ScatterOp(
        [mlir.aval_to_ir_type(x) for x in ctx.avals_out], [a, b], ids,
        [a_rows, b_rows], dnums,
        indices_are_sorted=ir.BoolAttr.get(False),
        unique_indices=ir.BoolAttr.get(False))
    scalar = mlir.aval_to_ir_type(a_aval.update(shape=()))
    add = op.update_computation.blocks.append(scalar, scalar, scalar, scalar)
    with ir.InsertionPoint(add):
        x, y, dx, dy = add.arguments    # the operands', then the updates'
        hlo.return_([hlo.add(x, dx), hlo.add(y, dy)])
    return op.results


mlir.register_lowering(pair_scatter_add_p, _lower)
