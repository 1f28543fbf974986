"""WKV6 — the RWKV-6 linear-recurrence kernel (Pallas TPU).

Per head, per timestep (all vectors length N):

    a_t = k_t^T v_t                       (N x N outer product)
    y_t = r_t (S_{t-1} + diag(u) a_t)
    S_t = diag(w_t) S_{t-1} + a_t

The recurrence is O(N^2) state per (batch, head) — far too branchy for
the MXU as a scan of XLA ops (4096 tiny HLO loop iterations).  The GAMA
treatment: grid = (B*H, T/chunk) with the time axis innermost
("arbitrary"), the (N, N) state living in a VMEM scratch across chunk
steps (the cascade-style accumulator), and a fori_loop inside the kernel
stepping through the chunk at VMEM latency.

Validated in interpret mode against the pure-jnp oracle (ref.ref_wkv);
rwkv6-3b's time_mix uses it on TPU via kernels.ops.wkv.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref, *,
                chunk: int):
    tchunk = pl.program_id(2)

    @pl.when(tchunk == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0].astype(jnp.float32)                  # (N,)

    def step(i, state):
        r = r_ref[0, 0, i].astype(jnp.float32)        # (N,)
        k = k_ref[0, 0, i].astype(jnp.float32)
        v = v_ref[0, 0, i].astype(jnp.float32)
        w = w_ref[0, 0, i].astype(jnp.float32)
        a = k[:, None] * v[None, :]                   # (N, N)
        y = r @ (state + u[:, None] * a)              # (N,)
        o_ref[0, 0, i] = y.astype(o_ref.dtype)
        return w[:, None] * state + a

    state_ref[...] = jax.lax.fori_loop(0, chunk, step, state_ref[...])


def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
         u: jax.Array, *, chunk: int = 128,
         interpret: bool = False) -> jax.Array:
    """r/k/v/w: (B, H, T, N); u: (H, N).

    Returns y: (B, H, T, N).  T % chunk == 0 (ops.py pads).  B and H stay
    separate grid dims so GSPMD keeps the batch axis sharded (merging
    them into B*H forces an all-gather when H doesn't divide the model
    axis — observed 6x per-device memory blow-up on rwkv6 train).
    """
    b, h, t, n = r.shape
    assert t % chunk == 0, (t, chunk)
    grid = (b, h, t // chunk)
    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    spec = pl.BlockSpec((1, 1, chunk, n), lambda bb, hh, tc: (bb, hh, tc, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, n), lambda bb, hh, tc: (hh, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, n), r.dtype),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gama_wkv6",
    )(r, k, v, w, u)
