"""Cascade parallelism — the pack (Fig. 3/4) as explicit TPU collectives.

A GAMA pack chains G engines over the K dimension; partial sums stream
through the cascade and only the last engine owns the output.  On a TPU
mesh the same dataflow is a K-sharded GEMM whose partial sums are combined
by a reduce-scatter over a *subgroup* of G devices of the model axis
(``axis_index_groups``), then a combine across the remaining X = W/G
subgroups — the hierarchical (G, X) factoring of Section IV-C.  On a 2D
torus the two phases ride different link dimensions.

Device numbering on the model axis: m = x * G + j, where j in [0, G) is
the cascade position (K slice) and x in [0, X) the subgroup (N slice).

This module is the explicit shard_map implementation (used by examples,
benchmarks and the cascade-equivalence tests); the pjit model path gets
the same dataflow from GSPMD via ShardingPolicy's row-parallel specs.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def cascade_groups(w: int, g: int):
    """w/g contiguous subgroups of size g: [[0..g-1], [g..2g-1], ...].

    >>> cascade_groups(8, 4)
    [[0, 1, 2, 3], [4, 5, 6, 7]]
    """
    return [list(map(int, row)) for row in np.arange(w).reshape(w // g, g)]


def cross_groups(w: int, g: int):
    """g strided groups of size w/g linking equal cascade positions:
    [[j, j+g, j+2g, ...] for j in range(g)].

    >>> cross_groups(8, 4)
    [[0, 4], [1, 5], [2, 6], [3, 7]]
    """
    return [list(map(int, row)) for row in np.arange(w).reshape(w // g, g).T]


def cascade_matmul(
    x: jax.Array,            # (T, K)
    w: jax.Array,            # (K, N)
    mesh: Mesh,
    *,
    g: Optional[int] = None,
    model_axis: str = "model",
) -> jax.Array:
    """C = x @ w with K sharded over G-subgroup members and N over X.

    Device m = x*G + j holds w[K_j, N_x]; partial sums combine via
    psum_scatter within the subgroup (the cascade stream) and the row
    shards are re-gathered for composability.
    """
    wsize = mesh.shape[model_axis]
    g = g or wsize
    xdim = wsize // g
    t, k = x.shape
    _, n = w.shape
    assert k % g == 0 and n % xdim == 0 and t % g == 0
    groups = cascade_groups(wsize, g)

    # Per-device operand slices, stacked along the model axis (m = x*G+j).
    xg = x.reshape(t, g, k // g).transpose(1, 0, 2)          # (G, T, K/G)
    xg = jnp.broadcast_to(xg[None], (xdim, g, t, k // g))
    xg = xg.reshape(wsize, t, k // g)
    wgrid = w.reshape(g, k // g, xdim, n // xdim)            # (j, :, x, :)
    wgrid = wgrid.transpose(2, 0, 1, 3).reshape(wsize, k // g, n // xdim)

    def local(x_l, w_l):
        partial = x_l @ w_l                                   # (T, N/X)
        out = jax.lax.psum_scatter(
            partial, model_axis, scatter_dimension=0, tiled=True,
            axis_index_groups=groups)                         # (T/G, N/X)
        return jax.lax.all_gather(
            out, model_axis, axis=0, tiled=True,
            axis_index_groups=groups)                         # (T, N/X)

    fn = jax.shard_map(
        lambda xs, ws: local(xs[0], ws[0])[None],
        mesh=mesh,
        in_specs=(P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=P(model_axis, None, None),
        check_vma=False,
    )
    out = fn(xg, wgrid)                                       # (W, T, N/X)
    out = out.reshape(xdim, g, t, n // xdim)[:, 0]            # (X, T, N/X)
    return out.transpose(1, 0, 2).reshape(t, n)


def cascade_ffn_reference(x: jax.Array, wg: jax.Array, wu: jax.Array,
                          wd: jax.Array) -> jax.Array:
    """Unsharded reference for the cascade FFN (swiglu)."""
    h = jax.nn.silu(x @ wg) * (x @ wu)
    return h @ wd


def cascade_ffn(
    x: jax.Array,            # (T, d)
    wg: jax.Array,           # (d, f)
    wu: jax.Array,           # (d, f)
    wd: jax.Array,           # (f, d)
    mesh: Mesh,
    *,
    g: Optional[int] = None,
    model_axis: str = "model",
) -> jax.Array:
    """Megatron-style FFN with a hierarchical (G, X) cascade combine.

    gate/up are column-parallel over the full model axis W; down is
    row-parallel.  The down-projection partial sums combine in two phases:
    psum_scatter within each G subgroup (cascade), then psum across the X
    subgroups, then an all-gather of the row shards.
    """
    wsize = mesh.shape[model_axis]
    g = g or wsize
    t, d = x.shape
    assert t % g == 0
    groups = cascade_groups(wsize, g)
    xg_groups = cross_groups(wsize, g)

    def local(x_l, wg_l, wu_l, wd_l):
        h = jax.nn.silu(x_l @ wg_l) * (x_l @ wu_l)            # (T, f/W)
        partial = h @ wd_l                                    # (T, d)
        out = jax.lax.psum_scatter(
            partial, model_axis, scatter_dimension=0, tiled=True,
            axis_index_groups=groups)                         # (T/G, d)
        out = jax.lax.psum(out, model_axis,
                           axis_index_groups=xg_groups)       # all X combine
        return jax.lax.all_gather(
            out, model_axis, axis=0, tiled=True,
            axis_index_groups=groups)                         # (T, d)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None), P(None, model_axis), P(None, model_axis),
                  P(model_axis, None)),
        out_specs=P(None, None),
        check_vma=False,
    )
    return fn(x, wg, wu, wd)
