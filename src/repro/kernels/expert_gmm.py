"""Grouped matmul over the experts one chip holds — Pallas TPU kernel.

A dropless expert layer sorts its (token, expert) pairs by expert, so
the rows of each held expert lie together in one (M, K) operand, in the
order of the experts, with ``group_sizes`` rows each; rows past the last
group belong to no held expert.  :func:`expert_gmm` multiplies each
group by its own expert's (K, N) matrix.

At serving sizes every expert sees far fewer rows than the MXU needs to
be compute-bound, so the kernel is laid out for weight bytes: the grid
walks *visits*, each one (expert, row tile) pair that holds rows of
that expert, and an expert's weight block is fetched once however many
row tiles it spans (consecutive visits of one expert keep its block).
An expert with no rows is never visited, so its weights are never read.
A row tile shared by several experts stays resident across their
visits; each visit writes only its own expert's rows.  The visit list
has a static length (row tiles + experts - 1 bounds it); the visits past
the real ones repeat the last, so they move no data and compute
nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hw

ROW_TILE = 128
_W_BLOCK_BYTES = 12 << 20     # one weight block; the pipeline holds two


def visits(group_sizes: jax.Array, rows: int, tm: int) -> tuple:
    """(expert ids, row-tile ids, count) of the grid's visits: for each
    expert with rows, every ``tm``-row tile its rows touch, in order.
    Entries past ``count`` repeat the last real visit."""
    e = group_sizes.shape[0]
    n_visits = -(-rows // tm) + e - 1
    gs = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(gs)
    starts = ends - gs
    first_tile = starts // tm
    n_tiles = jnp.where(gs > 0, (ends - 1) // tm - first_tile + 1, 0)
    v_end = jnp.cumsum(n_tiles)
    count = v_end[-1]
    i = jnp.arange(n_visits, dtype=jnp.int32)
    i = jnp.minimum(i, jnp.maximum(count - 1, 0))
    g = jnp.minimum(jnp.searchsorted(v_end, i, side="right"), e - 1)
    tile = first_tile[g] + i - (v_end[g] - n_tiles[g])
    return g.astype(jnp.int32), tile.astype(jnp.int32), count.reshape(1)


def _gmm_kernel(gid_ref, tid_ref, count_ref, ends_ref, x_ref, w_ref,
                o_ref, *, tm: int):
    i = pl.program_id(1)

    @pl.when(i < count_ref[0])
    def _visit():
        g = gid_ref[i]
        t = tid_ref[i]
        prev = tid_ref[jnp.maximum(i - 1, 0)]

        @pl.when((i == 0) | (prev != t))
        def _first_visit_of_tile():
            o_ref[...] = jnp.zeros_like(o_ref)

        y = jnp.dot(x_ref[...], w_ref[0], preferred_element_type=jnp.float32)
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        lo = jnp.where(g > 0, ends_ref[jnp.maximum(g - 1, 0)], 0)
        mine = (row >= lo) & (row < ends_ref[g])
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def _n_tile(k: int, n: int, itemsize: int) -> int:
    """The widest lane-aligned divisor of ``n`` whose (K, tn) weight
    block fits ``_W_BLOCK_BYTES``; ``n`` itself where it fits."""
    if k * n * itemsize <= _W_BLOCK_BYTES or n % 128:
        return n
    tn = n
    while tn % 256 == 0 and k * tn * itemsize > _W_BLOCK_BYTES:
        tn //= 2
    return tn


def expert_gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
               tm: int = ROW_TILE, interpret: bool = False) -> jax.Array:
    """x: (M, K) rows grouped by expert; w: (E, K, N); group_sizes: (E,)
    int32 rows per expert.  Returns (M, N) in ``x``'s dtype; rows past
    the last group are left unwritten (callers mask them).  ``M`` must
    be a multiple of ``tm``."""
    m, k = x.shape
    e, _, n = w.shape
    if m % tm:
        raise ValueError(f"rows {m} are not a multiple of the tile {tm}")
    gid, tid, count = visits(group_sizes, m, tm)
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    tn = _n_tile(k, n, w.dtype.itemsize)
    grid = (n // tn, gid.shape[0])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, i, g, t, c, en: (t[i], 0)),
                pl.BlockSpec((1, k, tn),
                             lambda j, i, g, t, c, en: (g[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, g, t, c, en: (t[i], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=hw.TPU_V5E.vmem_limit),
        interpret=interpret,
        name="gama_expert_gmm",
    )(gid, tid, count, ends, x, w)
