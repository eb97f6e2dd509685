"""Grouped matrix multiplication for dropless expert layers: rows sorted
by expert, each group of rows against its own expert's matrix, cost
proportional to the rows that exist (cf. PAPERS.md "MegaBlocks").

``grouped_matmul(lhs [M, K], rhs [E, K, N], group_sizes [E]) -> [M, N]``
with ``out[r] = lhs[r] @ rhs[g(r)]`` for the ``sum(group_sizes)`` leading
rows and zero after them.

Kernel design: the work list is every (group, row tile) pair that holds
at least one row — at most ``M / tm + E - 1`` of them, a static bound —
computed outside the kernel and handed to it in scalar-prefetch SMEM, so
each grid step's index maps pick the row tile, the expert's matrix block
and the output tile.  Grid ``(N tiles, work items, K tiles)``, K
innermost with a float32 accumulator in VMEM; consecutive work items of
one row tile revisit the same output block, each storing only its own
group's rows.  Work items past the real count repeat the block indices
of the last real step (group, row tile and K block: no copy is issued)
and skip their compute, so an expert that received no row is never read:
bytes follow the experts touched, operations the row tiles occupied.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret


def _tile(dim: int, want: int) -> int:
    """The largest of ``want``, ``want/2`` .. 128 that divides ``dim``,
    else the whole of it."""
    t = want
    while t >= 128:
        if dim % t == 0:
            return t
        t //= 2
    return dim


def work_list(group_sizes, m: int, tm: int):
    """(group of item, row tile of item, number of items, group starts,
    group ends): every (group, row tile) pair holding a row, in order."""
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)   # [E]
    item_end = jnp.cumsum(tiles)
    n_items = item_end[-1]
    w_max = m // tm + e - 1
    w = jnp.arange(w_max, dtype=jnp.int32)
    # item w belongs to the first group whose cumulative count passes it
    group = jnp.minimum(
        jnp.sum((w[:, None] >= item_end[None, :]).astype(jnp.int32), axis=1),
        e - 1)
    tile = first[group] + (w - (item_end[group] - tiles[group]))
    # items past the real count repeat the last real one
    last = jnp.maximum(n_items - 1, 0)
    group = jnp.where(w < n_items, group, group[last])
    tile = jnp.where(w < n_items, tile, tile[last])
    tile = jnp.clip(tile, 0, m // tm - 1)
    return (group.astype(jnp.int32), tile.astype(jnp.int32),
            n_items.astype(jnp.int32).reshape(1), starts, ends)


def _kernel(group_ref, tile_ref, count_ref, start_ref, end_ref,
            lhs_ref, rhs_ref, o_ref, acc_ref, *, tm, k_steps):
    w = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(w < count_ref[0])
    def _():
        @pl.when(k == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += jnp.dot(lhs_ref[:], rhs_ref[0],
                              preferred_element_type=jnp.float32)

        @pl.when(k == k_steps - 1)
        def _():
            g = group_ref[w]
            row = tile_ref[w] * tm + jax.lax.broadcasted_iota(
                jnp.int32, acc_ref.shape, 0)
            mine = jnp.logical_and(row >= start_ref[g], row < end_ref[g])
            o_ref[:] = jnp.where(mine, acc_ref[:].astype(o_ref.dtype),
                                 o_ref[:])


def grouped_matmul(lhs, rhs, group_sizes, tm=128, tk=1024, tn=1024,
                   interpret=None):
    """See the module docstring.  ``M`` must be a multiple of ``tm`` (or
    smaller than it); tile sizes shrink to divisors of K and N."""
    interpret = _interpret() if interpret is None else interpret
    m, kdim = lhs.shape
    e, k2, n = rhs.shape
    assert k2 == kdim and group_sizes.shape == (e,), (lhs.shape, rhs.shape)
    tm = min(tm, m)
    assert m % tm == 0, (m, tm)
    tk, tn = _tile(kdim, tk), _tile(n, tn)
    k_steps = kdim // tk
    group, tile, count, starts, ends = work_list(group_sizes, m, tm)
    w_max = group.shape[0]

    # a work item past the real count keeps the block indices of the step
    # before it (the last real item's group and tile, and its last K
    # block), so the pipeline issues no copy for it
    def k_of(w_, k_, count_s):
        return jnp.where(w_ < count_s[0], k_, k_steps - 1)

    def lhs_map(n_, w_, k_, group_s, tile_s, count_s, *_):
        return (tile_s[w_], k_of(w_, k_, count_s))

    def rhs_map(n_, w_, k_, group_s, tile_s, count_s, *_):
        return (group_s[w_], k_of(w_, k_, count_s), n_)

    def out_map(n_, w_, k_, group_s, tile_s, *_):
        return (tile_s[w_], n_)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // tn, w_max, k_steps),
        in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                  pl.BlockSpec((1, tk, tn), rhs_map)],
        out_specs=pl.BlockSpec((tm, tn), out_map),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, k_steps=k_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="moe_grouped_matmul",
    )(group, tile, count, starts, ends, lhs, rhs.astype(lhs.dtype))
    # tiles no work item visited, and rows past the last group, hold
    # whatever the buffer held: they are zero by contract
    rows = jnp.arange(m, dtype=jnp.int32)[:, None]
    return jnp.where(rows < ends[-1], out, jnp.zeros_like(out))
