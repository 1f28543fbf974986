"""Pipeline parallelism over a mesh axis (default: the multi-pod `pod`
axis) — GPipe-style microbatching with ``ppermute`` stage hand-off.

Each device along the pipe axis owns one *stage* (a slice of the layer
stack).  Microbatches march through stages; stage s processes microbatch
(t - s) at step t, activations hop stage->stage over ICI/DCN via
collective-permute.  Bubbles are computed-and-masked (standard for a
static-schedule SPMD pipeline).

The framework uses the pod axis as outer data parallelism by default
(sharding.py); this module is the alternative mapping, exercised by tests
and selectable in launch/train.py via --pod_strategy=pipeline.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Params = Any


def pipeline_apply(
    stage_fn: Callable[[Params, jax.Array], jax.Array],
    stage_params: Params,      # leaves with leading (n_stages, ...) axis
    x: jax.Array,              # (n_micro, mb, ...) microbatched input
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run x through n_stages stages; returns (n_micro, mb, ...) outputs.

    ``stage_fn(params_for_stage, microbatch) -> microbatch`` must preserve
    the microbatch shape (a residual-stream stage).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro >= 1
    perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]

    def local(params_l, x_l):
        # params_l leaves: (1, ...) — this stage's slice; x_l: (n_micro,...)
        params_me = jax.tree.map(lambda p: p[0], params_l)
        sid = jax.lax.axis_index(axis)
        outs = jnp.zeros_like(x_l)
        carry_in = jnp.zeros_like(x_l[0])

        def step(t, state):
            outs, carry_in = state
            mb = t - sid
            valid = jnp.logical_and(mb >= 0, mb < n_micro)
            x_first = x_l[jnp.clip(mb, 0, n_micro - 1)]
            x_in = jnp.where(sid == 0, x_first, carry_in)
            y = stage_fn(params_me, x_in)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # Record output on the last stage.
            write = jnp.logical_and(valid, sid == n_stages - 1)
            idx = jnp.clip(mb, 0, n_micro - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, y, outs[idx]), idx, 0)
            # Hand off to the next stage.
            nxt = jax.lax.ppermute(y, axis, perm_fwd)
            return outs, nxt

        outs, _ = jax.lax.fori_loop(0, n_micro + n_stages - 1, step,
                                    (outs, carry_in))
        # Broadcast the last stage's outputs to every stage.
        mask = (sid == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    fn = jax.shard_map(
        local, mesh=mesh,
        # Every param leaf is sharded on its leading (stage) axis; the
        # microbatched input is replicated along the pipe axis.
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)
