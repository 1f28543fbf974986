"""Flash attention (fwd) for train/prefill — Pallas TPU kernel.

Online-softmax blocked attention with GQA head mapping, causal masking
against an absolute ``q_offset`` (chunked prefill), and KV-length masking
for padded caches.  Follows the GAMA structure: the KV axis is the
innermost "arbitrary" grid dimension; running (m, l, acc) state lives in
VMEM scratch and partial results never leave the core — the same
cascade-style accumulation as the GEMM kernel, applied to the softmax
reduction.

Scratch follows the TPU-friendly (block, 128) lane-replicated layout for
the running max/denominator, as in jax's reference fused attention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hw

_LANES = 128
_NEG_INF = -1e30

# Block-size defaults; the autotuner (repro.tuning) searches around these
# and ops.attention consults its cache before falling back here.
DEFAULT_BQ = 128
DEFAULT_BK = 128


def attention_vmem_bytes(bq: int, bk: int, d: int, in_bytes: int) -> int:
    """VMEM working set of one grid step, used by the tuner's analytic
    pruner to reject over-budget (bq, bk) blocks before measuring.

    Inputs (q, k, v blocks) are double-buffered by the Pallas pipeline;
    the f32 running-softmax state (m, l lane-replicated + output
    accumulator) persists across the KV loop; the output block is
    written once.
    """
    q = bq * d * in_bytes
    kv = 2 * bk * d * in_bytes
    state = bq * _LANES * 4 * 2 + bq * d * 4
    out = bq * d * in_bytes
    return 2 * (q + kv) + state + out


def _flash_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, k_steps: int, bq: int, bk: int, scale: float,
                  causal: bool, kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_offset = off_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_block_start = q_offset + qi * bq
    k_block_start = ki * bk

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)        # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        k_pos = k_block_start + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        valid = k_pos < kv_len
        if causal:
            q_pos = q_block_start + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[...][:, :1]                  # (bq, 1)
        l_prev = l_ref[...][:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)   # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                      # (bq, bk)
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)             # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # Skip KV blocks entirely in the future of every q row in the block.
        pl.when(q_block_start + bq - 1 >= k_block_start)(_body)
    else:
        _body()

    @pl.when(ki == k_steps - 1)
    def _done():
        l = l_ref[...][:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    scale: Optional[float] = None,
    causal: bool = True,
    q_offset=0,
    kv_len: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D); Sq % bq == Sk % bk == 0.

    GQA mapping is done by the kv index_map (q head h reads kv head
    h // (Hq // Hkv)) — no KV replication in HBM.  ``q_offset`` may be
    traced (a chunked prefill's cursor): it reaches the kernel as a
    scalar-prefetch operand, so one compiled kernel serves every cursor.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    if scale is None:
        scale = d ** -0.5
    if kv_len is None:
        kv_len = sk
    k_steps = sk // bk
    grid = (b, hq, sq // bq, k_steps)

    kernel = functools.partial(
        _flash_kernel, k_steps=k_steps, bq=bq, bk=bk, scale=scale,
        causal=causal, kv_len=kv_len)
    offset = jnp.asarray(q_offset, jnp.int32).reshape(1)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda bb, h, qi, ki, off: (bb, h, qi, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda bb, h, qi, ki, off, g=group:
                             (bb, h // g, ki, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda bb, h, qi, ki, off, g=group:
                             (bb, h // g, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d),
                                   lambda bb, h, qi, ki, off: (bb, h, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, _LANES), jnp.float32),   # running max
                pltpu.VMEM((bq, _LANES), jnp.float32),   # running denom
                pltpu.VMEM((bq, d), jnp.float32),        # output acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=hw.TPU_V5E.vmem_limit),
        interpret=interpret,
        name="gama_flash_attention",
    )(offset, q, k, v)
