"""GAMA GEMM — the paper's kernel re-targeted to the TPU MXU via Pallas.

Structure (DESIGN.md §2):

* grid = (M/tm, N/tn, K/tk) with the K axis innermost and marked
  "arbitrary": partial sums accumulate across K steps in an f32/int32 VMEM
  scratch and never round-trip HBM — the in-kernel analogue of the AIE2
  cascade stream (partial sums flow engine-to-engine without touching
  memory);
* the Pallas pipeline double-buffers the A/B input blocks automatically —
  the ping-pong buffering that Algorithm 1 places by hand on AIE2;
* BlockSpec tile sizes come from :func:`repro.core.tile_search.
  search_tpu_tiles`, the VMEM-budget analogue of the paper's Eq. 6 search;
* multi-precision, as in the paper: bf16 x bf16 -> bf16 (f32 accumulate)
  and int8 x int8 -> {int32, int16, int8} with a saturating requantize
  epilogue (scale applied on the final K step only).

The pure-jnp oracle lives in ref.py; ops.py wraps this in jit with padding
and CPU interpret-mode fallback.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hw

# Integer output ranges for the saturating epilogue.
_INT_RANGE = {
    jnp.int8.dtype: (-128, 127),
    jnp.int16.dtype: (-32768, 32767),
}


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int,
                 out_dtype, scale: float):
    """One (tm, tn) output block; K accumulation across grid steps."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    acc_dtype = acc_ref.dtype
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=acc_dtype)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        acc = acc_ref[...]
        if acc_dtype == jnp.int32.dtype and out_dtype in _INT_RANGE:
            # Requantize: scale in f32, round-to-nearest-even, saturate.
            lo, hi = _INT_RANGE[jnp.dtype(out_dtype)]
            scaled = acc.astype(jnp.float32) * scale
            o_ref[...] = jnp.clip(jnp.round(scaled), lo, hi).astype(out_dtype)
        else:
            o_ref[...] = acc.astype(out_dtype)


def gama_gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    tm: int,
    tk: int,
    tn: int,
    out_dtype=None,
    scale: float = 1.0,
    order: str = "mn",
    interpret: bool = False,
) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N] with GAMA tiling.  Shapes must be tile-
    aligned (ops.py pads); int8 inputs accumulate in int32, floats in f32.

    ``order`` picks the grid traversal: "mn" walks M outermost (B tile
    columns are re-streamed per M row — the seed behavior), "nm" walks N
    outermost (A tile rows re-streamed).  K stays innermost either way;
    the choice only changes which operand enjoys pipeline-level reuse, a
    tunable the autotuner (repro.tuning) measures per shape.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % tm == 0 and k % tk == 0 and n % tn == 0, (
        f"({m},{k},{n}) not aligned to ({tm},{tk},{tn})")
    assert order in ("mn", "nm"), order

    integer = jnp.issubdtype(a.dtype, jnp.integer)
    acc_dtype = jnp.int32 if integer else jnp.float32
    if out_dtype is None:
        out_dtype = jnp.int32 if integer else a.dtype
    out_dtype = jnp.dtype(out_dtype)

    k_steps = k // tk
    if order == "mn":
        grid = (m // tm, n // tn, k_steps)
        a_map = lambda i, j, kk: (i, kk)
        b_map = lambda i, j, kk: (kk, j)
        o_map = lambda i, j, kk: (i, j)
    else:
        grid = (n // tn, m // tm, k_steps)
        a_map = lambda j, i, kk: (i, kk)
        b_map = lambda j, i, kk: (kk, j)
        o_map = lambda j, i, kk: (i, j)

    kernel = functools.partial(_gemm_kernel, k_steps=k_steps,
                               out_dtype=out_dtype, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), a_map),
            pl.BlockSpec((tk, tn), b_map),
        ],
        out_specs=pl.BlockSpec((tm, tn), o_map),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=hw.TPU_V5E.vmem_limit),
        interpret=interpret,
        name="gama_gemm",
    )(a, b)
