"""Multi-head latent attention (MLA), as DeepseekV2Attention defines it
without a query compression (``q_lora_rank`` null).

Per token, the KV is one latent row: ``[c, k_pe]`` with
``c = RMSNorm((x W_kva)[:r])`` (r = ``kv_lora_rank``) and ``k_pe`` the
rope part of ``x W_kva``, which every head shares.  Heads expand it:
``[k_nope, v] = c W_kvb`` per head.  Queries are ``[q_nope, q_pe] =
x W_q`` per head, and the scores are ``(q_nope . k_nope + q_pe . k_pe)``
times ``(nope + rope)^-0.5`` and YaRN's softmax factor.  Rope rotates
the lane pairs (2i, 2i+1) of ``q_pe`` and ``k_pe``: the published code
de-interleaves them and applies rotate-half, which this module does too.

Two forms of the same attention:

* expanded (``mla.expand``): every cached latent row is expanded into
  per-head keys and values, then ordinary causal attention.  Prefill
  chunks and the uncached forward use it.
* absorbed (``mla.absorb``): ``W_UK`` (the k_nope half of ``W_kvb``)
  folds into the query, ``q_lat = q_nope W_UK^T`` (r lanes), and the
  keys are the latent rows themselves, so decode is a multi-query
  attention of all heads over one row per key; the values are the rows'
  first r lanes, and ``W_UV`` (the v half) folds into the output.
  Decode uses it, over a dense latent cache or the paged latent pool
  (``gama_latent_paged_decode``).

Caches: dense ``{"lat": (B, 1, L, r + rope)}``; paged ``{"lat_pages":
(P, 1, page_size, R)}`` with R = ``page_row_dim(r + rope)`` lanes, the
pad lanes zero.  There are no int8 latent pages.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.decode_attention import page_row_dim
from repro.models import layers as L
from repro.serving.quant import maybe_dequant

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128

    @property
    def latent_dim(self) -> int:
        """Lanes of one latent row: ``c`` then ``k_pe``."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


def init_mla(rng, d_model: int, n_heads: int, m: MLAConfig,
             dtype=jnp.float32) -> Params:
    rq, ra, rb, ro = jax.random.split(rng, 4)
    return {
        "wq": L.dense_init(rq, d_model, n_heads * m.qk_dim, dtype),
        "wkv_a": L.dense_init(ra, d_model, m.latent_dim, dtype),
        "kv_norm": L.norm_init(m.kv_lora_rank, dtype),
        "wkv_b": L.dense_init(rb, m.kv_lora_rank,
                              n_heads * (m.qk_nope_dim + m.v_head_dim),
                              dtype),
        "wo": L.dense_init(ro, n_heads * m.v_head_dim, d_model, dtype),
    }


def init_mla_cache(batch: int, max_len: int, m: MLAConfig,
                   dtype=jnp.bfloat16) -> Params:
    return {"lat": jnp.zeros((batch, 1, max_len, m.latent_dim), dtype)}


def init_paged_mla_cache(num_pages: int, page_size: int, m: MLAConfig,
                         dtype=jnp.bfloat16,
                         kv_dtype: Optional[str] = None) -> Params:
    """The latent page pool: ``num_pages`` (null sink included) pages of
    ``page_size`` latent rows of ``page_row_dim(latent_dim)`` lanes (the
    TPU compiler refuses a DMA of a row that is not lane-aligned)."""
    if kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8:
        raise ValueError("a latent KV pool has no int8 pages: its rows "
                         "carry no per-head scale layout")
    dt = jnp.dtype(kv_dtype) if kv_dtype else jnp.dtype(dtype)
    return {"lat_pages": jnp.zeros(
        (num_pages, 1, page_size, page_row_dim(m.latent_dim)), dt)}


def _rope(x: jax.Array, positions: jax.Array, m: MLAConfig, theta: float,
          yarn: Optional[L.YarnScaling]) -> jax.Array:
    """x: (B, S, H, rope); the lane pairs (2i, 2i+1) rotated by
    frequency i, returned de-interleaved ([evens', odds']), as the
    published code leaves them."""
    d = m.qk_rope_dim
    if yarn is None:
        angles = L.rope_angles(positions, d, theta)
    else:
        angles = positions[..., None].astype(jnp.float32) \
            * L.yarn_inv_freq(d, theta, yarn)
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    out = L.apply_rope(x, angles)
    if yarn is not None and L.yarn_cos_scale(yarn) != 1.0:
        out = (out.astype(jnp.float32)
               * L.yarn_cos_scale(yarn)).astype(x.dtype)
    return out


def softmax_scale(cfg) -> float:
    m = cfg.mla
    return m.qk_dim ** -0.5 * L.yarn_softmax_scale(cfg.rope_scaling)


def _expanded(p: Params, q_nope, q_pe, lat, cfg, q_offset, causal: bool,
              mode: str) -> jax.Array:
    """Attention of the queries (B, S, H, ·) over the latent rows
    ``lat`` (B, Sk, r + rope), each expanded into per-head keys and
    values.  Returns (B, S, H * v_head_dim)."""
    m, h = cfg.mla, cfg.n_heads
    r, nope, vd = m.kv_lora_rank, m.qk_nope_dim, m.v_head_dim
    b, s = q_nope.shape[:2]
    sk = lat.shape[1]
    with jax.named_scope("mla.expand"):
        kvb = L.dense(p["wkv_b"], lat[..., :r]).reshape(b, sk, h, nope + vd)
        k_pe = jnp.broadcast_to(lat[:, :, None, r:], (b, sk, h, m.qk_rope_dim))
        k = jnp.concatenate([kvb[..., :nope], k_pe.astype(kvb.dtype)], -1)
        # The attention kernels take one head dim: the values ride in
        # the first v_head_dim lanes of rows as wide as the keys.
        v = kops.to_page_rows(kvb[..., nope:], m.qk_dim)
        q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], -1)
        out = kops.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=causal,
                             scale=softmax_scale(cfg), q_offset=q_offset,
                             mode=mode)
    out = out[..., :vd].transpose(0, 2, 1, 3)
    return out.reshape(b, s, h * vd)


def _absorbed_query(p: Params, q_nope, q_pe, cfg) -> Tuple[jax.Array,
                                                           jax.Array]:
    """(B, H, r + rope) absorbed queries from one token's (B, H, ·)
    q_nope and q_pe, and W_UV (r, H, v_head_dim)."""
    m, h = cfg.mla, cfg.n_heads
    w = maybe_dequant(p["wkv_b"]["w"], q_nope.dtype).reshape(
        m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w[..., :m.qk_nope_dim],
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_lat, q_pe.astype(jnp.float32)], -1)
    return q.astype(q_nope.dtype), w[..., m.qk_nope_dim:]


def _absorbed_out(out_lat, w_uv, cfg) -> jax.Array:
    """(B, H, r) attention over the latent values -> (B, 1, H * v)."""
    o = jnp.einsum("bhr,rhv->bhv", out_lat, w_uv.astype(out_lat.dtype),
                   preferred_element_type=jnp.float32)
    b = o.shape[0]
    return o.astype(out_lat.dtype).reshape(b, 1, cfg.n_heads
                                           * cfg.mla.v_head_dim)


def mla_attention(
    p: Params,
    x: jax.Array,                       # (B, S, d_model)
    cfg,
    *,
    positions: jax.Array,               # (B, S)
    cache: Optional[Params] = None,
    cache_pos: Optional[jax.Array] = None,
    block_tables: Optional[jax.Array] = None,
    attn_mode: str = "auto",
) -> Tuple[jax.Array, Optional[Params]]:
    """Returns (output (B, S, d_model), updated cache).  The shapes are
    those of ``attention.attention``: no cache (full causal forward),
    a dense cache at a scalar offset (prefill or a prompt chunk,
    expanded; S == 1 decodes absorbed), per-slot offsets with S == 1
    (decode, absorbed), and the paged pool with ``block_tables``."""
    m, h = cfg.mla, cfg.n_heads
    r, nope = m.kv_lora_rank, m.qk_nope_dim
    b, s, _ = x.shape
    q = L.dense(p["wq"], x).reshape(b, s, h, m.qk_dim)
    kv = L.dense(p["wkv_a"], x)
    c = L.rmsnorm(p["kv_norm"], kv[..., :r], cfg.norm_eps)
    q_pe = _rope(q[..., nope:], positions, m, cfg.rope_theta,
                 cfg.rope_scaling)
    k_pe = _rope(kv[..., None, r:], positions, m, cfg.rope_theta,
                 cfg.rope_scaling)[:, :, 0]
    lat = jnp.concatenate([c, k_pe.astype(c.dtype)], -1)   # (B, S, r+rope)
    q_nope = q[..., :nope]

    if cache is None:
        out = _expanded(p, q_nope, q_pe, lat, cfg, 0, cfg.causal, attn_mode)
        return L.dense(p["wo"], out), None

    ragged = getattr(cache_pos, "ndim", 0) == 1
    if ragged and s != 1:
        raise NotImplementedError(
            "per-slot cache_pos is a decode-only shape (S == 1)")
    if "lat_pages" in cache:
        if not ragged or block_tables is None:
            raise NotImplementedError(
                "the paged latent pool needs per-slot cache_pos and "
                "block_tables (the continuous-batching decode shape)")
        pool = cache["lat_pages"]
        page_size = pool.shape[2]
        pos = jnp.asarray(cache_pos, jnp.int32)
        page_ids = block_tables[jnp.arange(b), pos // page_size]
        pool = pool.at[page_ids, 0, pos % page_size, :].set(
            kops.to_page_rows(lat[:, 0], pool.shape[-1]).astype(pool.dtype))
        with jax.named_scope("mla.absorb"):
            qa, w_uv = _absorbed_query(p, q_nope[:, 0], q_pe[:, 0], cfg)
            out_lat = kops.decode_latent_paged(
                qa, pool, block_tables=block_tables, length=pos + 1,
                value_dim=r, scale=softmax_scale(cfg), mode=attn_mode)
            out = _absorbed_out(out_lat, w_uv, cfg)
        return L.dense(p["wo"], out), {"lat_pages": pool}

    dense = cache["lat"]
    if ragged:
        def _write(dst, row, at):
            return jax.lax.dynamic_update_slice(dst, row, (0, at, 0))
        dense = jax.vmap(_write)(dense, lat[:, None].astype(dense.dtype),
                                 cache_pos)
    else:
        at = 0 if cache_pos is None else cache_pos
        dense = jax.lax.dynamic_update_slice(
            dense, lat[:, None].astype(dense.dtype), (0, 0, at, 0))
    new_cache = {"lat": dense}
    rows = dense[:, 0].astype(x.dtype)                  # (B, L, r + rope)
    if s == 1:
        length = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32) + 1,
                                  (b,))
        with jax.named_scope("mla.absorb"):
            qa, w_uv = _absorbed_query(p, q_nope[:, 0], q_pe[:, 0], cfg)
            out_lat = kops.decode(qa, rows[:, None], rows[:, None],
                                  length=length, scale=softmax_scale(cfg),
                                  mode=attn_mode)[..., :r]
            out = _absorbed_out(out_lat, w_uv, cfg)
    else:
        out = _expanded(p, q_nope, q_pe, rows, cfg,
                        0 if cache_pos is None else cache_pos, True,
                        attn_mode)
    return L.dense(p["wo"], out), new_cache
