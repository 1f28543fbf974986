"""Grouped-query attention with RoPE / M-RoPE, qk-norm, and KV caching.

Supports the five execution shapes the assignment exercises:
  * train:   full causal self-attention, no cache;
  * prefill: causal self-attention that also writes the KV cache;
  * chunked prefill: a prompt *chunk* at its cursor offset — scalar
    ``cache_pos > 0`` with S > 1 writes the chunk's KV at the offset and
    attends causally over the cache's grown prefix (``q_offset`` keys
    the causal mask to absolute positions, RoPE angles come from the
    caller's offset positions), so a prompt prefilled chunk-by-chunk is
    bit-identical to one monolithic prefill;
  * decode:  one new token against a cached KV prefix (flash-decode path);
  * cross:   encoder-decoder cross attention (cache holds encoder KV).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.decode_attention import page_row_dim
from repro.models import layers as L

Params = Dict[str, Any]


def init_attention(rng, d_model: int, n_heads: int, n_kv_heads: int,
                   d_head: int, qk_norm: bool = False,
                   dtype=jnp.float32) -> Params:
    rq, rk, rv, ro = jax.random.split(rng, 4)
    p = {
        "wq": L.dense_init(rq, d_model, n_heads * d_head, dtype),
        "wk": L.dense_init(rk, d_model, n_kv_heads * d_head, dtype),
        "wv": L.dense_init(rv, d_model, n_kv_heads * d_head, dtype),
        "wo": L.dense_init(ro, n_heads * d_head, d_model, dtype),
    }
    if qk_norm:
        p["q_norm"] = L.norm_init(d_head, dtype)
        p["k_norm"] = L.norm_init(d_head, dtype)
    return p


def init_kv_cache(batch: int, n_kv_heads: int, max_len: int, d_head: int,
                  dtype=jnp.bfloat16) -> Params:
    return {
        "k": jnp.zeros((batch, n_kv_heads, max_len, d_head), dtype),
        "v": jnp.zeros((batch, n_kv_heads, max_len, d_head), dtype),
    }


def init_paged_kv_cache(num_pages: int, n_kv_heads: int, page_size: int,
                        d_head: int, dtype=jnp.bfloat16,
                        kv_dtype: Optional[str] = None) -> Params:
    """Paged pool layout (``repro.serving.kvpool``): ``num_pages`` blocks
    of ``page_size`` tokens shared by every slot, addressed through a
    per-slot block table.  ``num_pages`` must already include the null
    sink page (the engine allocates pool + 1).

    ``kv_dtype`` overrides the page dtype (``ServeConfig.kv_dtype``):
    a float name just retypes the pools; ``"int8"`` adds per-row f32
    scale-row arrays (``k_scale``/``v_scale``, one symmetric scale per
    token row per KV head) — the quantized-page layout the fused-dequant
    decode kernel consumes.  Page rows are ``page_row_dim(d_head)``
    lanes wide, the head dim zero-padded to a lane multiple, so the
    kernel's explicit-DMA page gather compiles for the chip."""
    page_dtype = jnp.dtype(kv_dtype) if kv_dtype else jnp.dtype(dtype)
    shape = (num_pages, n_kv_heads, page_size, page_row_dim(d_head))
    cache = {
        "k_pages": jnp.zeros(shape, page_dtype),
        "v_pages": jnp.zeros(shape, page_dtype),
    }
    if page_dtype == jnp.int8:
        cache["k_scale"] = jnp.zeros((num_pages, n_kv_heads, page_size),
                                     jnp.float32)
        cache["v_scale"] = jnp.zeros((num_pages, n_kv_heads, page_size),
                                     jnp.float32)
    return cache


def attention(
    p: Params,
    x: jax.Array,                       # (B, S, d_model)
    *,
    n_heads: int,
    n_kv_heads: int,
    d_head: int,
    positions: Optional[jax.Array] = None,     # (B, S) or (B, S, 3) M-RoPE
    rope_theta: float = 10000.0,
    mrope_sections: Optional[Tuple[int, ...]] = None,
    qk_norm: bool = False,
    causal: bool = True,
    cache: Optional[Params] = None,
    cache_pos: Optional[jax.Array] = None,      # scalar or (B,) write offset
    block_tables: Optional[jax.Array] = None,   # (B, max_pages) paged KV
    kv_from: Optional[jax.Array] = None,        # encoder states (cross-attn)
    use_cached_kv: bool = False,                # decode-time cross attention
    attn_mode: str = "auto",
) -> Tuple[jax.Array, Optional[Params]]:
    """Returns (output (B, S, d_model), updated cache)."""
    b, s, _ = x.shape
    q = L.dense(p["wq"], x).reshape(b, s, n_heads, d_head)

    if use_cached_kv:
        # Cross-attention after prefill: KV was computed from the encoder
        # once and lives in the cache; no projection, no cache update.
        assert cache is not None
        if qk_norm:
            q = L.rmsnorm(p["q_norm"], q)
        q = q.transpose(0, 2, 1, 3)
        k = cache["k"].astype(x.dtype)
        v = cache["v"].astype(x.dtype)
        if s == 1:
            length = jnp.full((b,), k.shape[2], jnp.int32)
            out = kops.decode(q[:, :, 0], k, v, length=length,
                              mode=attn_mode)[:, :, None]
        else:
            out = kops.attention(q, k, v, causal=False, mode=attn_mode)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, n_heads * d_head)
        return L.dense(p["wo"], out), cache

    kv_src = x if kv_from is None else kv_from
    sk = kv_src.shape[1]
    k = L.dense(p["wk"], kv_src).reshape(b, sk, n_kv_heads, d_head)
    v = L.dense(p["wv"], kv_src).reshape(b, sk, n_kv_heads, d_head)

    if qk_norm:
        q = L.rmsnorm(p["q_norm"], q)
        k = L.rmsnorm(p["k_norm"], k)

    use_rope = kv_from is None and positions is not None
    if use_rope:
        if mrope_sections is not None:
            angles = L.mrope_angles(positions, d_head, mrope_sections,
                                    rope_theta)
        else:
            angles = L.rope_angles(positions, d_head, rope_theta)
        q = L.apply_rope(q, angles)
        k = L.apply_rope(k, angles)

    q = L.shard_hint(q.transpose(0, 2, 1, 3), "heads")    # (B, H, S, D)
    k = L.shard_hint(k.transpose(0, 2, 1, 3), "heads")    # (B, Hkv, Sk, D)
    v = L.shard_hint(v.transpose(0, 2, 1, 3), "heads")

    new_cache = None
    ragged = getattr(cache_pos, "ndim", 0) == 1   # per-slot positions
    if ragged and s != 1:
        raise NotImplementedError(
            "per-slot cache_pos is a decode-only shape (S == 1); prefill "
            "admits one request (or one prompt chunk) at a time at its "
            "own scalar offset")
    paged = cache is not None and "k_pages" in cache
    if paged:
        # Paged KV (kvpool): decode-only — prefill runs against a dense
        # single-slot cache whose pages the engine scatters into the
        # pool.  The new token's KV row lands at row pos % page_size of
        # page block_tables[b, pos // page_size]; page ids are unique
        # per live slot (free slots share the null sink, whose garbage
        # is unreachable: their length masks everything).
        if not ragged or block_tables is None:
            raise NotImplementedError(
                "paged KV attention needs per-slot cache_pos and "
                "block_tables (the continuous-batching decode shape)")
        page_size, row_dim = cache["k_pages"].shape[2:]
        pos = jnp.asarray(cache_pos, jnp.int32)
        page_ids = block_tables[jnp.arange(b), pos // page_size]
        rows = pos % page_size
        length = pos + 1
        if "k_scale" in cache:
            # int8 pages: quantize exactly the row being appended (per-
            # row symmetric scales — no existing row is requantized) and
            # write its scale into the pool's scale rows.  The decode
            # kernel dequantizes inside its split-K page loop.
            from repro.serving.quant import quantize_kv_row
            kq, ksc = quantize_kv_row(kops.to_page_rows(k[:, :, 0], row_dim))
            vq, vsc = quantize_kv_row(kops.to_page_rows(v[:, :, 0], row_dim))
            ck = cache["k_pages"].at[page_ids, :, rows, :].set(kq)
            cv = cache["v_pages"].at[page_ids, :, rows, :].set(vq)
            cks = cache["k_scale"].at[page_ids, :, rows].set(ksc)
            cvs = cache["v_scale"].at[page_ids, :, rows].set(vsc)
            new_cache = {"k_pages": ck, "v_pages": cv,
                         "k_scale": cks, "v_scale": cvs}
            out = kops.decode_paged(q[:, :, 0], ck, cv,
                                    block_tables=block_tables,
                                    length=length, k_scale=cks,
                                    v_scale=cvs, mode=attn_mode)
        else:
            ck = cache["k_pages"].at[page_ids, :, rows, :].set(
                kops.to_page_rows(k[:, :, 0], row_dim).astype(
                    cache["k_pages"].dtype))
            cv = cache["v_pages"].at[page_ids, :, rows, :].set(
                kops.to_page_rows(v[:, :, 0], row_dim).astype(
                    cache["v_pages"].dtype))
            new_cache = {"k_pages": ck, "v_pages": cv}
            out = kops.decode_paged(q[:, :, 0], ck.astype(x.dtype),
                                    cv.astype(x.dtype),
                                    block_tables=block_tables,
                                    length=length, mode=attn_mode)
        out = out[:, :, None].transpose(0, 2, 1, 3)   # (B, 1, H, D)
        out = out.reshape(b, s, n_heads * d_head)
        out = L.shard_hint(out, "channels")
        return L.dense(p["wo"], out), new_cache
    if cache is not None:
        if ragged:
            # Continuous batching: each slot writes its new KV row at its
            # own position.  vmap over the batch axis so the update is a
            # per-slot dynamic_update_slice, not one shared offset.
            def _write(dst, upd, p):
                return jax.lax.dynamic_update_slice(dst, upd, (0, p, 0))
            ck = jax.vmap(_write)(cache["k"], k.astype(cache["k"].dtype),
                                  cache_pos)
            cv = jax.vmap(_write)(cache["v"], v.astype(cache["v"].dtype),
                                  cache_pos)
        else:
            pos = 0 if cache_pos is None else cache_pos
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, pos, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, pos, 0))
        new_cache = {"k": ck, "v": cv}
        k, v = ck.astype(x.dtype), cv.astype(x.dtype)

    if s == 1 and cache is not None:
        # Decode: one token against the cached prefix.  With per-slot
        # positions each slot's valid length differs — the decode kernel
        # masks attention past each slot's own length.
        length = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32) + 1,
                                  (b,))
        out = kops.decode(q[:, :, 0], k, v, length=length, mode=attn_mode)
        out = out[:, :, None]                       # (B, H, 1, D)
    else:
        q_off = 0 if cache_pos is None else cache_pos
        out = kops.attention(q, k, v, causal=causal and kv_from is None,
                             q_offset=q_off, mode=attn_mode)

    out = out.transpose(0, 2, 1, 3).reshape(b, s, n_heads * d_head)
    out = L.shard_hint(out, "channels")
    return L.dense(p["wo"], out), new_cache
