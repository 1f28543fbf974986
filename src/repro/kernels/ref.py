"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the allclose test sweeps and the math used
by the models when kernels are disabled (dry-run / CPU paths).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_INT_RANGE = {
    jnp.int8.dtype: (-128, 127),
    jnp.int16.dtype: (-32768, 32767),
}


def requantize(acc: jax.Array, out_dtype, scale: float = 1.0) -> jax.Array:
    """Canonical accumulator -> output conversion: integer accumulators
    headed for a narrow int dtype are scaled/rounded/clipped; everything
    else is a plain cast (float GEMMs ignore ``scale``).  The single
    definition of the repo's requant semantics — gama_gemm and the
    pack-level GEMM both defer to it so they cannot drift from the
    oracle."""
    out_dtype = jnp.dtype(out_dtype)
    if jnp.issubdtype(acc.dtype, jnp.integer) and out_dtype in _INT_RANGE:
        lo, hi = _INT_RANGE[out_dtype]
        return jnp.clip(jnp.round(acc.astype(jnp.float32) * scale),
                        lo, hi).astype(out_dtype)
    return acc.astype(out_dtype)


def ref_gemm(a: jax.Array, b: jax.Array, *, out_dtype=None,
             scale: float = 1.0) -> jax.Array:
    """Oracle for gama_gemm: int8->int32 accumulate (+requant) / f32."""
    integer = jnp.issubdtype(a.dtype, jnp.integer)
    acc_dtype = jnp.int32 if integer else jnp.float32
    if out_dtype is None:
        out_dtype = jnp.int32 if integer else a.dtype
    acc = jnp.dot(a, b, preferred_element_type=acc_dtype)
    return requantize(acc, out_dtype, scale)


def ref_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True,
                  scale: Optional[float] = None,
                  q_offset: int = 0) -> jax.Array:
    """Oracle for flash attention.  q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D).

    GQA: q head h attends to kv head h // (Hq // Hkv).  ``q_offset`` is the
    absolute position of q[0] for causal masking with a KV cache.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    kq = jnp.repeat(k, group, axis=1)
    vq = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kq.astype(jnp.float32)) * scale
    if causal:
        q_pos = jnp.arange(sq) + q_offset
        k_pos = jnp.arange(sk)
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vq.astype(jnp.float32))
    return out.astype(q.dtype)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True,
                      scale: Optional[float] = None,
                      q_offset: int = 0,
                      q_chunk: int = 1024,
                      kv_chunk: int = 1024) -> jax.Array:
    """Flash-attention *algorithm* in pure XLA ops (online softmax over KV
    chunks, outer map over Q chunks).

    This is what the dry-run lowers instead of the Pallas kernel (which
    targets TPU): peak memory is O(B*H*cq*ck) per step instead of the
    O(S^2) a naive softmax materializes, so the compiled memory analysis
    reflects the deployed kernel's behaviour.  Numerics match
    ref_attention (tested).
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    pad_q = (-sq) % q_chunk
    pad_k = (-sk) % kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v
    nq, nk = qp.shape[2] // q_chunk, kp.shape[2] // kv_chunk

    # (nk, B, Hkv, ck, D) scan elements.
    ks = kp.reshape(b, hkv, nk, kv_chunk, d).transpose(2, 0, 1, 3, 4)
    vs = vp.reshape(b, hkv, nk, kv_chunk, d).transpose(2, 0, 1, 3, 4)
    k_starts = jnp.arange(nk) * kv_chunk

    def one_q_block(args):
        qc, q_start = args                      # (B, Hq, cq, D), scalar
        qf = qc.astype(jnp.float32)

        def kv_step(carry, inputs):
            m, l, acc = carry
            kc, vc, k0 = inputs                 # (B, Hkv, ck, D)
            kf = jnp.repeat(kc, group, axis=1).astype(jnp.float32)
            vf = jnp.repeat(vc, group, axis=1).astype(jnp.float32)
            s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
            k_pos = k0 + jnp.arange(kv_chunk)
            valid = (k_pos < sk)[None, None, None, :]
            if causal:
                q_pos = q_offset + q_start + jnp.arange(q_chunk)
                valid = jnp.logical_and(
                    valid, q_pos[None, None, :, None] >=
                    k_pos[None, None, None, :])
            s = jnp.where(valid, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vf)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, hq, q_chunk), -1e30, jnp.float32),
                jnp.zeros((b, hq, q_chunk), jnp.float32),
                jnp.zeros((b, hq, q_chunk, d), jnp.float32))
        # checkpoint: backward recomputes each (cq, ck) block instead of
        # saving nq*nk stacked logits/mask residuals (flash-style bwd).
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_step), init,
                                      (ks, vs, k_starts))
        safe_l = jnp.where(l > 0, l, 1.0)
        return (acc / safe_l[..., None]).astype(q.dtype)

    qs = qp.reshape(b, hq, nq, q_chunk, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(jax.checkpoint(one_q_block),
                      (qs, jnp.arange(nq) * q_chunk))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, hq, nq * q_chunk, d)
    return out[:, :, :sq]


def ref_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         length: Optional[jax.Array] = None,
                         scale: Optional[float] = None) -> jax.Array:
    """Oracle for flash decode.  q: (B, Hq, D) one token; k/v: (B, Hkv, S, D).

    ``length`` (B,) masks the valid KV prefix (cache may be oversized).
    """
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    kq = jnp.repeat(k, group, axis=1)
    vq = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                        kq.astype(jnp.float32)) * scale
    if length is not None:
        mask = jnp.arange(s)[None, :] < length[:, None]
        logits = jnp.where(mask[:, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhk,bhkd->bhd", w, vq.astype(jnp.float32))
    return out.astype(q.dtype)


def gather_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Materialize a contiguous per-slot cache from a page pool.

    pool: (P, Hkv, page_size, D); block_tables: (B, max_pages) int32 →
    (B, Hkv, max_pages * page_size, D).  This is the *oracle* view of
    the paged layout (the Pallas kernel never builds it): position
    ``t`` of slot ``b`` is row ``t % page_size`` of page
    ``block_tables[b, t // page_size]``.
    """
    _, hkv, ps, d = pool.shape
    b, n_pages = block_tables.shape
    gathered = pool[block_tables]            # (B, max_pages, Hkv, ps, D)
    return gathered.transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, n_pages * ps, d)


def dequantize_pool(pages: jax.Array,
                    page_scale: Optional[jax.Array]) -> jax.Array:
    """Apply per-row scale rows to an int8 page pool: (P, Hkv, ps, D)
    int8 x (P, Hkv, ps) f32 -> f32 values.  With ``page_scale=None`` the
    pool is already full precision and passes through unchanged.  Same
    math as serving.quant.dequantize_kv (kept here so the oracle stays
    dependency-free)."""
    if page_scale is None:
        return pages
    return pages.astype(jnp.float32) * page_scale[..., None]


def ref_paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array,
                               block_tables: jax.Array, *,
                               length: jax.Array,
                               scale: Optional[float] = None,
                               k_scale: Optional[jax.Array] = None,
                               v_scale: Optional[jax.Array] = None
                               ) -> jax.Array:
    """Oracle for flash_paged_decode: dequantize the pools (int8 pages
    carry per-row scale rows), gather pages contiguous, then the dense
    decode oracle.  Unallocated table entries point at the null sink
    page; ``length`` masks them (and the partial tail page) out.  Pool
    rows may be wider than the head dim (lane padding); only the first
    ``D`` lanes are read."""
    d = q.shape[-1]
    kc = gather_pages(dequantize_pool(k_pages, k_scale), block_tables)
    vc = gather_pages(dequantize_pool(v_pages, v_scale), block_tables)
    kc, vc = kc[..., :d], vc[..., :d]
    return ref_decode_attention(q, kc, vc, length=length, scale=scale)


def ref_latent_paged_decode(q: jax.Array, pages: jax.Array,
                            block_tables: jax.Array, *, length: jax.Array,
                            value_dim: int, scale: float) -> jax.Array:
    """Oracle for latent_paged_decode: gather the latent rows
    contiguous, then the dense decode oracle with the rows as keys (all
    the query's lanes) and their first ``value_dim`` lanes as values."""
    rows = gather_pages(pages, block_tables)[..., :q.shape[-1]]
    return ref_decode_attention(q, rows, rows[..., :value_dim],
                                length=length, scale=scale)


def ref_expert_gmm(x: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """Oracle for expert_gmm: rows of ``x`` (M, K) grouped in order by
    ``group_sizes`` (E,), each group times its expert's ``w`` (E, K, N);
    rows past the last group are zero."""
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(x.shape[0])
    group = jnp.searchsorted(ends, row, side="right")
    every = jnp.einsum("mk,ekn->emn", x.astype(jnp.float32),
                       w.astype(jnp.float32))
    pick = group[None, :] == jnp.arange(w.shape[0])[:, None]   # (E, M)
    return jnp.sum(jnp.where(pick[..., None], every, 0.0),
                   axis=0).astype(x.dtype)


def ref_wkv(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
            u: jax.Array,
            state: Optional[jax.Array] = None) -> jax.Array:
    """Oracle for the WKV6 kernel.  r/k/v/w: (B, H, T, N); u: (H, N).

    y_t = r_t (S_{t-1} + diag(u) k_t^T v_t);  S_t = diag(w_t) S_{t-1} + a_t.
    """
    b, h, t, n = r.shape
    if state is None:
        state = jnp.zeros((b, h, n, n), jnp.float32)
    uf = u.astype(jnp.float32)

    def step(s, inputs):
        rt, kt, vt, wt = inputs            # (B, H, N) each
        a = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhn,bhnm->bhm", rt.astype(jnp.float32),
                       s + uf[..., :, None] * a)
        return wt.astype(jnp.float32)[..., :, None] * s + a, y

    xs = tuple(x.astype(jnp.float32).transpose(2, 0, 1, 3)
               for x in (r, k, v, w))
    _, ys = jax.lax.scan(step, state, xs)
    return ys.transpose(1, 2, 0, 3).astype(r.dtype)
