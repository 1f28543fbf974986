"""Shared layers: norms, rotary embeddings (incl. M-RoPE), MLPs, embeddings.

All layers are pure functions over param dicts (pytrees).  Every matmul
routes through :func:`gemm`, i.e. ``kernels.ops.matmul``: the GAMA Pallas
kernel on TPU, the mathematically identical jnp oracle elsewhere (CPU
smoke tests and the dry-run) — see kernels/ops.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.serving.quant import maybe_dequant

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# GEMM indirection — the GAMA integration point
# ---------------------------------------------------------------------------

# Activation-sharding hook: the launcher installs a policy callback
# (ShardingPolicy.act) and model code marks tensors with semantic kinds
# ("residual", "heads", "channels", ...).  Identity when unset (smoke
# tests, single-device runs).  GSPMD needs these hints at the points
# where reshapes make propagation ambiguous (e.g. head splits that do
# not divide the model axis) — without them it falls back to replication.
_SHARD_HOOK = None


def set_shard_hook(fn) -> None:
    global _SHARD_HOOK
    _SHARD_HOOK = fn


def shard_hint(x: jax.Array, kind: str) -> jax.Array:
    if _SHARD_HOOK is None:
        return x
    return _SHARD_HOOK(x, kind)


def gemm(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: (..., K) @ w: (K, N) -> (..., N) through ``kernels.ops.matmul``
    (mode="auto": the GAMA kernel on TPU, the jnp oracle elsewhere, or
    whatever ``ops.force_mode`` says).

    With a pack context installed (``repro.distributed.pack_gemm``),
    GEMMs above its FLOP threshold — in practice the lm head and the
    ffn projections — route through the pack-level collective matmul,
    whose per-device GEMMs resolve "auto" the same way.  To isolate the
    pure single-process oracle, clear the context
    (``pack_gemm.clear_pack_context()`` / ``engine.close()``) and trace
    under ``ops.force_mode("ref")``.
    """
    lead = x.shape[:-1]
    out = kops.matmul(x.reshape(-1, x.shape[-1]), w)
    return out.reshape(*lead, w.shape[-1])


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(rng, d_in: int, d_out: int, dtype=jnp.float32,
               scale: Optional[float] = None) -> Params:
    scale = (1.0 / d_in) ** 0.5 if scale is None else scale
    return {"w": jax.random.normal(rng, (d_in, d_out), dtype) * scale}


def dense(p: Params, x: jax.Array) -> jax.Array:
    y = gemm(x, maybe_dequant(p["w"], x.dtype))
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def norm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(dt)


def layernorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(dt)


def groupnorm(x: jax.Array, n_groups: int, scale: jax.Array,
              bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    """GroupNorm over the channel dim (used by RWKV's wkv output)."""
    dt = x.dtype
    *lead, d = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, n_groups, d // n_groups)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(*lead, d)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------


def rope_angles(positions: jax.Array, d_head: int,
                theta: float = 10000.0) -> jax.Array:
    """positions: (..., S) -> angles (..., S, d_head//2)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d_head, 2,
                                           dtype=jnp.float32) / d_head))
    return positions[..., None].astype(jnp.float32) * inv_freq


def mrope_angles(positions: jax.Array, d_head: int,
                 sections: Sequence[int],
                 theta: float = 10000.0) -> jax.Array:
    """M-RoPE (Qwen2-VL): positions (..., S, 3) = (t, h, w) coordinates.

    The d_head//2 frequency slots are split into `sections` (t, h, w
    section sizes, summing to d_head//2); each section rotates by its own
    coordinate.  Text tokens use t == h == w, recovering standard RoPE.
    """
    half = d_head // 2
    assert sum(sections) == half, (sections, d_head)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d_head, 2,
                                           dtype=jnp.float32) / d_head))
    # Which coordinate (0=t, 1=h, 2=w) each frequency slot rotates by.
    select = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                        total_repeat_length=half)
    pos_sel = positions[..., select]            # (..., S, half)
    return pos_sel.astype(jnp.float32) * inv_freq


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling, as DeepseekV2YarnRotaryEmbedding defines it
    (the published ``rope_scaling`` of type "yarn")."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """0.1 * mscale * ln(factor) + 1 (1 for factor <= 1).

    >>> round(yarn_mscale(40, 0.707), 5)
    1.2608
    """
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(y: YarnScaling, dim: int,
                          theta: float) -> Tuple[int, int]:
    """The rotary dims between which YaRN ramps from extrapolation
    (fast rotations) to interpolation.

    >>> yarn_correction_range(YarnScaling(40, 4096), 64, 10000.0)
    (10, 23)
    """
    def dim_of(rotations):
        return (dim * math.log(y.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim_of(y.beta_fast)), 0),
            min(math.ceil(dim_of(y.beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, theta: float, y: YarnScaling) -> jax.Array:
    """(dim // 2,) inverse frequencies: the interpolated ones (divided
    by ``factor``) below the correction range, the original ones above
    it, a linear ramp between."""
    base = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    extra, inter = 1.0 / base, 1.0 / (y.factor * base)
    low, high = yarn_correction_range(y, dim, theta)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def yarn_softmax_scale(y: Optional[YarnScaling]) -> float:
    """The factor YaRN puts on the softmax scale: mscale(factor,
    mscale_all_dim) squared, where ``mscale_all_dim`` is set."""
    if y is None or not y.mscale_all_dim:
        return 1.0
    return yarn_mscale(y.factor, y.mscale_all_dim) ** 2


def yarn_cos_scale(y: YarnScaling) -> float:
    """The factor on cos and sin: mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)."""
    return (yarn_mscale(y.factor, y.mscale)
            / yarn_mscale(y.factor, y.mscale_all_dim))


# RoPE application dtype: "float32" (default, max accuracy) or "compute"
# (multiply in the activation dtype — halves the bytes of any collective
# XLA hoists across the rotation; angles/sin/cos stay f32).  §Perf lever.
_ROPE_DTYPE = "float32"


def set_rope_dtype(mode: str) -> None:
    global _ROPE_DTYPE
    assert mode in ("float32", "compute")
    _ROPE_DTYPE = mode


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: (B, S, H, D); angles: (B, S, D//2) (broadcast over heads)."""
    dt = x.dtype
    wdt = jnp.float32 if _ROPE_DTYPE == "float32" else dt
    xf = x.astype(wdt)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    cos = jnp.cos(angles).astype(wdt)[..., None, :]
    sin = jnp.sin(angles).astype(wdt)[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(rng, d_model: int, d_ff: int, kind: str = "swiglu",
             dtype=jnp.float32) -> Params:
    r1, r2, r3 = jax.random.split(rng, 3)
    p = {"down": dense_init(r2, d_ff, d_model, dtype)}
    if kind == "swiglu":
        p["gate"] = dense_init(r1, d_model, d_ff, dtype)
        p["up"] = dense_init(r3, d_model, d_ff, dtype)
    else:
        p["up"] = dense_init(r1, d_model, d_ff, dtype)
    return p


def mlp(p: Params, x: jax.Array, kind: str = "swiglu") -> jax.Array:
    if kind == "swiglu":
        h = jax.nn.silu(dense(p["gate"], x)) * dense(p["up"], x)
    else:
        h = jax.nn.gelu(dense(p["up"], x))
    h = shard_hint(h, "channels")
    return dense(p["down"], h)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embedding_init(rng, vocab: int, d_model: int, dtype=jnp.float32) -> Params:
    return {"table": jax.random.normal(rng, (vocab, d_model), dtype) * 0.02}


def embed(p: Params, tokens: jax.Array, dtype) -> jax.Array:
    return maybe_dequant(p["table"], dtype)[tokens]


def logits(p: Params, x: jax.Array, head: Optional[Params]) -> jax.Array:
    """Tied (embed.T) or separate head; returns f32 logits."""
    if head is not None:
        out = dense(head, x).astype(jnp.float32)
    else:
        out = gemm(x, maybe_dequant(p["table"], x.dtype).T).astype(
            jnp.float32)
    return shard_hint(out, "logits")


def cross_entropy(logits_: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy; logits (B,S,V) f32, labels (B,S) int."""
    logp = jax.nn.log_softmax(logits_, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
