"""Pallas TPU kernels: gather and scatter rows of a column-major table.

A TPU keeps an f32 (N, 64) embedding table column-major by default
(``{0,1:T(8,128)}``: 64 columns fill the (8, 128) tile without padding).
XLA's row gather and row scatter want it row-major, so a step that pulls
and pushes a few thousand rows through them copies the whole table to
row-major and back. These kernels read and write the rows where they lie.

They work on the table's transpose ``table_t`` (D, N), which in the default
row-major tiled layout is the same bytes as the column-major (N, D) table,
so ``table.T`` costs XLA nothing. Row ``i`` of the table is column ``i`` of
``table_t``, inside the (D, 128) lane block ``i // 128``.

Ids come as the trainer's unique buckets do: sorted, each real id once,
PAD (< 0) anywhere. Each grid step takes 128 ids and skips at once when
none of them is real. Otherwise it starts one DMA per lane block its real
ids touch (all in flight together), then moves each id's column between
its block and the step's (D, 128) tile of ``cols_t`` with a select. The
scatter writes each block back after the last of its ids in the step, and
the grid runs in order, so ids of one block that straddle two steps see
each other's writes. Only whole lane blocks move: ids in the last, partial
block of a table whose N is not a multiple of 128 are left to the caller
(``main_rows``), as are PAD ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # ids per grid step, and table rows per lane block


def main_rows(num_rows: int) -> int:
    """Rows of an (N, D) table that lie in whole lane blocks."""
    return num_rows // LANES * LANES


def _block(ids_ref, j, n_main):
    """Lane block of the step's j-th id, or -1 for PAD and tail ids."""
    i = ids_ref[0, 0, j]
    return jnp.where((i >= 0) & (i < n_main), i // LANES, -1)


def _window(table_ref, b):
    return table_ref.at[:, pl.ds(pl.multiple_of(b * LANES, LANES), LANES)]


def _column(tile, lane, j):
    """Column ``j`` of a (D, 128) tile as (D, 1), bit for bit."""
    return jnp.max(jnp.where(lane == j, tile, -jnp.inf), axis=1, keepdims=True)


def _read(table_ref, buf, sems, b, j):
    """The DMA of lane block ``b`` into buffer slot ``j``."""
    return pltpu.make_async_copy(_window(table_ref, b), buf.at[j], sems.at[j])


def _each_id(ids_ref, n_main, fn):
    """Run ``fn(j, b, new, slot)`` over the step's ids in order: ``b`` the
    id's block (-1 if skipped), ``new`` whether it is the first id of its
    block, ``slot`` the buffer slot that holds the block."""

    def body(j, carry):
        prev, slot = carry
        b = _block(ids_ref, j, n_main)
        new = (b >= 0) & (b != prev)
        slot = jnp.where(new, j, slot)
        fn(j, b, new, slot)
        return jnp.where(b >= 0, b, prev), slot

    lax.fori_loop(0, LANES, body, (jnp.int32(-1), jnp.int32(0)))


def _start_reads(ids_ref, table_ref, buf, sems, n_main):
    """One DMA per lane block, into the slot of its first id, all in
    flight together."""

    def start(j, b, new, slot):
        @pl.when(new)
        def _():
            _read(table_ref, buf, sems, b, j).start()

    _each_id(ids_ref, n_main, start)


def _gather_kernel(live_ref, ids_ref, table_ref, out_ref, buf, sems, *,
                   n_main):
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(live_ref[pl.program_id(0)] > 0)
    def _():
        _start_reads(ids_ref, table_ref, buf, sems, n_main)
        lane = lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)

        def move(j, b, new, slot):
            @pl.when(new)
            def _():
                _read(table_ref, buf, sems, b, j).wait()

            @pl.when(b >= 0)
            def _():
                col = _column(buf[slot], lane, ids_ref[0, 0, j] % LANES)
                out_ref[...] = jnp.where(lane == j, col, out_ref[...])

        _each_id(ids_ref, n_main, move)


def _scatter_kernel(live_ref, ids_ref, cols_ref, table_in, table_ref, buf,
                    sems_in, sems_out, *, n_main):
    del table_in  # the same buffer as table_ref (aliased)

    def last_of_block(j, b):
        nxt = _block(ids_ref, jnp.minimum(j + 1, LANES - 1), n_main)
        return (b >= 0) & ((j == LANES - 1) | (nxt != b))

    def write_back(b, slot):
        return pltpu.make_async_copy(buf.at[slot], _window(table_ref, b),
                                     sems_out.at[slot])

    @pl.when(live_ref[pl.program_id(0)] > 0)
    def _():
        _start_reads(ids_ref, table_ref, buf, sems_in, n_main)
        lane = lax.broadcasted_iota(jnp.int32, cols_ref.shape, 1)

        def move(j, b, new, slot):
            @pl.when(new)
            def _():
                _read(table_ref, buf, sems_in, b, j).wait()

            @pl.when(b >= 0)
            def _():
                col = _column(cols_ref[...], lane, j)
                buf[slot] = jnp.where(lane == ids_ref[0, 0, j] % LANES, col,
                                      buf[slot])

            @pl.when(last_of_block(j, b))
            def _():
                write_back(b, slot).start()

        def wait(j, b, new, slot):
            @pl.when(last_of_block(j, b))
            def _():
                write_back(b, slot).wait()

        _each_id(ids_ref, n_main, move)
        _each_id(ids_ref, n_main, wait)


def _pad_ids(ids):
    """Ids padded to whole steps; PAD at the end keeps each block's ids
    together."""
    return jnp.pad(ids.astype(jnp.int32), (0, -ids.shape[0] % LANES),
                   constant_values=-1)


def _run(kernel, n, idx, operands, in_specs, out_specs, out_shape, sems,
         interpret, **kw):
    """``kernel`` over the padded ids ``idx`` of an N-row table, one grid
    step per 128 ids; a step none of whose ids is real is skipped."""
    n_main = main_rows(n)
    steps = idx.shape[0] // LANES
    live = jnp.any(((idx >= 0) & (idx < n_main)).reshape(steps, LANES),
                   axis=1).astype(jnp.int32)
    d, dtype = out_shape.shape[0], out_shape.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps,),
        in_specs=[pl.BlockSpec((1, 1, LANES), lambda c, live: (c, 0, 0),
                               memory_space=pltpu.SMEM), *in_specs],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((LANES, d, LANES), dtype),
                        *[pltpu.SemaphoreType.DMA((LANES,))] * sems],
    )
    return pl.pallas_call(
        functools.partial(kernel, n_main=n_main),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        **kw,
    )(live, idx.reshape(steps, 1, LANES), *operands)


def gather_cols_pallas(table_t, ids, *, interpret=False):
    """(D, N) ``table_t``, (B,) ids -> (D, B): column j holds row ``ids[j]``
    of the table; zeros for PAD ids and ids past ``main_rows``."""
    (d, n), idx = table_t.shape, _pad_ids(ids)
    out = _run(
        _gather_kernel, n, idx, (table_t,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((d, LANES), lambda c, live: (0, c)),
        out_shape=jax.ShapeDtypeStruct((d, idx.shape[0]), table_t.dtype),
        sems=1, interpret=interpret,
    )
    return out[:, : ids.shape[0]]


def scatter_cols_pallas(table_t, ids, cols_t, *, interpret=False):
    """``table_t`` (D, N) with column ``ids[j]`` set to column j of (D, B)
    ``cols_t``; PAD ids and ids past ``main_rows`` are skipped. The output
    aliases ``table_t``: donate it and the write is in place."""
    (d, n), idx = table_t.shape, _pad_ids(ids)
    cols = jnp.pad(cols_t, ((0, 0), (0, idx.shape[0] - ids.shape[0])))
    return _run(
        _scatter_kernel, n, idx, (cols, table_t),
        in_specs=[pl.BlockSpec((d, LANES), lambda c, live: (0, c)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(table_t.shape, table_t.dtype),
        sems=2, interpret=interpret,
        input_output_aliases={3: 0},
    )
