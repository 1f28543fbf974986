"""Public jit'd wrappers around the Pallas kernels.

Handles tile-size planning (via the GAMA planner), padding to tile
alignment, GQA group padding, and backend dispatch:

* mode="auto": Pallas kernel on TPU, jnp reference elsewhere (the CPU
  container validates kernels in interpret mode through tests, but model
  code falls back to the mathematically-identical ref for speed);
* mode="kernel": force the Pallas kernel (interpret=True off-TPU);
* mode="ref": force the jnp oracle.

:func:`force_mode` re-resolves every ``mode="auto"`` call traced inside
it — how a whole model runs through the ``kernels/ref.py`` oracles on
the chip, as the reference its kernel path is checked against.  Each
call counts its route (``ops.<op>.kernel`` / ``.ref`` / ``.pack``) in
``repro.obs`` at trace time.

Above the pack threshold, :func:`matmul` additionally dispatches to the
pack-level sharded GEMM (``repro.distributed.pack_gemm``) when a pack
context is installed — the paper's three-level scaling: single kernel
below the threshold, pack/array collective matmul above it.  ``mode``
then selects the backend of each *local* per-device GEMM.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.decode_attention import (flash_decode, flash_paged_decode,
                                           latent_paged_decode,
                                           paged_pages_per_block)
from repro.kernels.expert_gmm import ROW_TILE, expert_gmm as _expert_gmm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import gama_gemm
from repro.kernels.wkv import wkv6
from repro.obs import count as _obs_count, get_obs as _get_obs

Mode = str  # "auto" | "kernel" | "ref"

_FORCED: Optional[Mode] = None   # what "auto" resolves to in force_mode


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@contextlib.contextmanager
def force_mode(mode: Mode) -> Iterator[None]:
    """Resolve ``mode="auto"`` to ``mode`` ("ref" or "kernel") for every
    op traced inside the block.  Jitted functions must be traced inside
    it (a cached trace keeps the routes it was traced with)."""
    global _FORCED
    if mode not in ("ref", "kernel"):
        raise ValueError(f"force_mode takes 'ref' or 'kernel', got {mode!r}")
    prev, _FORCED = _FORCED, mode
    try:
        yield
    finally:
        _FORCED = prev


def _use_kernel(mode: Mode) -> bool:
    if mode == "auto" and _FORCED is not None:
        mode = _FORCED
    if mode == "kernel":
        return True
    if mode == "ref":
        return False
    return on_tpu()


def _interpret() -> bool:
    return not on_tpu()


def _on_each_device(fn: Callable) -> Callable:
    """XLA cannot partition a Pallas (Mosaic) kernel.  In a program that
    spans an installed multi-device pack mesh — the GEMMs above the
    pack threshold run shard_mapped over it — every other kernel call
    therefore runs whole on each device, replicated in and out, through
    a shard_map of its own.  Inside a shard_map already (the pack's own
    per-device GEMMs), or with no multi-device pack mesh, ``fn`` is
    returned as is."""
    import repro.distributed.pack_gemm as pg
    ctx = pg.get_pack_context()
    if (ctx is None or ctx.mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return fn
    return jax.shard_map(fn, mesh=ctx.mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pick_tiles(m: int, k: int, n: int, dtype) -> tuple[int, int, int, str]:
    """Tuned-or-analytic tiles: the tuning cache's best when one exists
    for this (shape, dtype, backend), else the analytic planner's answer
    (identical to the historical search — see repro.tuning.prior)."""
    from repro.tuning import dispatch
    cfg = dispatch.gemm_config(m, k, n, dtype)
    return cfg.tm, cfg.tk, cfg.tn, cfg.order


def _check_gqa(hq: int, hkv: int) -> None:
    """GQA maps each KV head to hq/hkv query heads; a non-divisible
    head count would silently truncate the group (wrong attention, not
    an error) — reject it up front, on every backend path."""
    if hkv <= 0 or hq % hkv:
        raise ValueError(
            f"GQA needs query heads divisible by KV heads, got "
            f"hq={hq}, hkv={hkv} (hq % hkv = {hq % hkv if hkv else hq})")


def pack_eligible(m: int, k: int, n: int) -> bool:
    """True when a pack context is installed and (M, K, N) clears its
    FLOP threshold — i.e. matmul() would route to the pack-level GEMM."""
    import repro.distributed.pack_gemm as pg
    ctx = pg.get_pack_context()
    return ctx is not None and ctx.eligible(m, k, n)


def matmul(a: jax.Array, b: jax.Array, *, out_dtype=None, scale: float = 1.0,
           tiles: Optional[tuple[int, int, int]] = None,
           order: Optional[str] = None,
           mode: Mode = "auto", allow_pack: bool = True) -> jax.Array:
    """GAMA GEMM with padding + planning.  a: (M, K); b: (K, N).

    With a pack context installed (``distributed.pack_gemm``), problems
    above the context's FLOP threshold run as a pack/array-level
    collective matmul instead of one kernel; ``allow_pack=False`` opts
    out (used by pack_gemm itself for the per-device local GEMM, and by
    callers that must stay single-device).  Explicit ``tiles``/``order``
    overrides also pin the call to the single-kernel path — they
    describe one kernel's grid, which the pack route would ignore.
    ``mode="ref"`` always means the single-process jnp oracle.
    """
    if allow_pack and mode != "ref" and tiles is None and order is None:
        import repro.distributed.pack_gemm as pg
        ctx = pg.get_pack_context()
        if ctx is not None and ctx.eligible(a.shape[0], a.shape[1],
                                            b.shape[1]):
            # Route counters fire at trace time — one tick per compiled
            # program per site, not per executed call.
            _obs_count("ops.matmul.pack")
            return pg.pack_gemm(a, b, ctx.mesh, model_axis=ctx.model_axis,
                                data_axis=ctx.data_axis,
                                out_dtype=out_dtype, scale=scale, mode=mode)
    if not _use_kernel(mode):
        _obs_count("ops.matmul.ref")
        return ref.ref_gemm(a, b, out_dtype=out_dtype, scale=scale)
    _obs_count("ops.matmul.kernel")
    m, k = a.shape
    _, n = b.shape
    if tiles is None:
        tm, tk, tn, plan_order = _pick_tiles(m, k, n, a.dtype)
    else:
        (tm, tk, tn), plan_order = tiles, "mn"
    order = order or plan_order
    tm, tk, tn = min(tm, _round_up(m, 8)), min(tk, _round_up(k, 128)), \
        min(tn, _round_up(n, 128))
    mp, kp, np_ = _round_up(m, tm), _round_up(k, tk), _round_up(n, tn)
    ap = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    bp = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    out = _on_each_device(functools.partial(
        gama_gemm, tm=tm, tk=tk, tn=tn, out_dtype=out_dtype, scale=scale,
        order=order, interpret=_interpret()))(ap, bp)
    return out[:m, :n]


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, scale: Optional[float] = None,
              q_offset: int = 0, bq: Optional[int] = None,
              bk: Optional[int] = None,
              mode: Mode = "auto") -> jax.Array:
    """Flash attention with seq padding.  q: (B,Hq,Sq,D); kv: (B,Hkv,Sk,D).

    ``bq``/``bk`` default to the tuning cache's best blocks for this
    (Sq, Sk, D) shape, falling back to the 128/128 analytic default.
    """
    _check_gqa(q.shape[1], k.shape[1])
    if not _use_kernel(mode):
        # Long sequences lower the chunked (flash-algorithm) form so the
        # dry-run's memory analysis reflects the deployed kernel.
        if k.shape[2] > 2048:
            return ref.chunked_attention(q, k, v, causal=causal,
                                         scale=scale, q_offset=q_offset)
        return ref.ref_attention(q, k, v, causal=causal, scale=scale,
                                 q_offset=q_offset)
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    if bq is None or bk is None:
        from repro.tuning import dispatch
        tuned_bq, tuned_bk = dispatch.attention_blocks(sq, sk, d, q.dtype)
        bq = bq if bq is not None else tuned_bq
        bk = bk if bk is not None else tuned_bk
    bq = min(bq, _round_up(sq, 8))
    bk = min(bk, _round_up(sk, 128))
    sqp, skp = _round_up(sq, bq), _round_up(sk, bk)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    out = _on_each_device(
        lambda q_, k_, v_, off: flash_attention(
            q_, k_, v_, bq=bq, bk=bk, scale=scale, causal=causal,
            q_offset=off, kv_len=sk, interpret=_interpret()))(
        qp, kp, vp, jnp.asarray(q_offset, jnp.int32))
    return out[:, :, :sq]


def decode(q: jax.Array, k: jax.Array, v: jax.Array, *,
           length: Optional[jax.Array] = None, bk: Optional[int] = None,
           scale: Optional[float] = None, mode: Mode = "auto") -> jax.Array:
    """Single-token decode attention.  q: (B,Hq,D); kv cache: (B,Hkv,Sk,D).

    ``length`` is a (B,) int32 vector of *per-slot* valid-prefix lengths
    (a ragged continuous batch: each slot attends only to its own
    prefix; positions >= length[b] are masked on every backend path).
    ``bk`` (the split-K block over the cache) defaults to the tuning
    cache's best for this (Sk, D) shape, falling back to the analytic
    default of 512.
    """
    _check_gqa(q.shape[1], k.shape[1])
    b, hq, d = q.shape
    _, hkv, sk, _ = k.shape
    if length is not None:
        length = jnp.asarray(length, jnp.int32)
        if length.shape != (b,):
            raise ValueError(
                f"decode length must be per-slot with shape ({b},), got "
                f"{length.shape} — a scalar would silently mask every "
                f"slot to one shared prefix")
        # An over-long slot (stale host bookkeeping) must not read the
        # pad region as valid history.
        length = jnp.minimum(length, sk)
    if not _use_kernel(mode):
        _obs_count("ops.decode.ref")
        return ref.ref_decode_attention(q, k, v, length=length, scale=scale)
    _obs_count("ops.decode.kernel")
    group = hq // hkv
    if bk is None:
        from repro.tuning import dispatch
        bk = dispatch.decode_block(sk, d, q.dtype)
    bk = min(bk, _round_up(sk, 128))
    skp = _round_up(sk, bk)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    if length is None:
        length = jnp.full((b,), sk, jnp.int32)
    # Sublane-pad the GQA group (padded q heads are sliced away below).
    gp = max(8, group)
    if gp != group:
        qg = q.reshape(b, hkv, group, d)
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
        qq = qg.reshape(b, hkv * gp, d)
    else:
        qq = q
    out = _on_each_device(
        lambda q_, k_, v_, ln: flash_decode(
            q_, k_, v_, length=ln, bk=bk, scale=scale,
            interpret=_interpret()))(qq, kp, vp, length)
    if gp != group:
        out = out.reshape(b, hkv, gp, d)[:, :, :group].reshape(b, hq, d)
    return out


def to_page_rows(x: jax.Array, width: int) -> jax.Array:
    """Zero-pad KV rows (..., D) to a page pool's row width
    (``decode_attention.page_row_dim``), as they are written into the
    pool."""
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array, *,
                 block_tables: jax.Array, length: jax.Array,
                 scale: Optional[float] = None,
                 k_scale: Optional[jax.Array] = None,
                 v_scale: Optional[jax.Array] = None,
                 buffers: int = 2,
                 mode: Mode = "auto") -> jax.Array:
    """Single-token decode attention over a **paged** KV cache
    (``repro.serving.kvpool``).  q: (B,Hq,D); k_pages/v_pages:
    (P,Hkv,page_size,R) pool arrays, the head dim in the first D lanes
    of each row (R = ``page_row_dim(D)`` for the serving pools);
    block_tables: (B,max_pages) int32 page ids; length: (B,) int32
    per-slot valid rows.

    The kernel path gathers each slot's pages via the block table
    inside the split-K loop (the last partial page masked by
    ``length``).  ``buffers=2`` (default) takes a block of pages of all
    KV heads per step, copies only the live pages with explicit DMAs
    into two ping-pong slots (block j+1's loads overlap block j's
    softmax/matmul), and sizes the block from the shapes
    (``paged_pages_per_block``, kept in the
    ``ops.decode_paged.pages_per_block`` gauge at trace time);
    ``buffers=1`` keeps the serial one-page-one-head BlockSpec gather.
    int8 pools pass per-row ``k_scale``/``v_scale`` rows
    ((P,Hkv,page_size) f32); dequant fuses into the split-K loop.  The
    ref path dequantizes + materializes the gather and runs the dense
    decode oracle — mathematically identical.
    """
    _check_gqa(q.shape[1], k_pages.shape[1])
    b, hq, d = q.shape
    _, hkv, page_size, _ = k_pages.shape
    if block_tables.shape[0] != b or block_tables.ndim != 2:
        raise ValueError(
            f"block_tables must be (B={b}, max_pages), got "
            f"{block_tables.shape}")
    length = jnp.asarray(length, jnp.int32)
    if length.shape != (b,):
        raise ValueError(
            f"paged decode length must be per-slot with shape ({b},), "
            f"got {length.shape}")
    quantized = k_pages.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError(
            "int8 k_pages/v_pages need per-row k_scale/v_scale rows "
            "(P, Hkv, page_size) — decoding raw int8 codes as values "
            "would be silently wrong")
    if not quantized and (k_scale is not None or v_scale is not None):
        raise ValueError("k_scale/v_scale are only valid for int8 pools")
    # Stale host bookkeeping must not read past the table's coverage.
    length = jnp.minimum(length, block_tables.shape[1] * page_size)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    if not _use_kernel(mode):
        _obs_count("ops.decode_paged.ref")
        return ref.ref_paged_decode_attention(
            q, k_pages, v_pages, block_tables, length=length, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
    _obs_count("ops.decode_paged.kernel")
    if buffers == 2:
        _get_obs().registry.gauge(
            "ops.decode_paged.pages_per_block",
            "table pages per grid step of the paged decode kernel").set(
            paged_pages_per_block(
                block_tables.shape[1], hkv, page_size, k_pages.shape[-1],
                k_pages.dtype.itemsize, quantized))
    group = hq // hkv
    gp = max(8, group)                  # sublane-pad the GQA group
    if gp != group:
        qg = q.reshape(b, hkv, group, d)
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
        qq = qg.reshape(b, hkv * gp, d)
    else:
        qq = q
    out = _on_each_device(
        lambda q_, kp_, vp_, bt, ln, ks, vs: flash_paged_decode(
            q_, kp_, vp_, bt, length=ln, scale=scale, k_scale=ks,
            v_scale=vs, buffers=buffers, interpret=_interpret()))(
        qq, k_pages, v_pages, block_tables, length, k_scale, v_scale)
    if gp != group:
        out = out.reshape(b, hkv, gp, d)[:, :, :group].reshape(b, hq, d)
    return out


def decode_latent_paged(q: jax.Array, pages: jax.Array, *,
                        block_tables: jax.Array, length: jax.Array,
                        value_dim: int, scale: float,
                        mode: Mode = "auto") -> jax.Array:
    """Single-token decode over a **latent** paged cache (multi-head
    latent attention, absorbed): q (B, H, D) absorbed queries; pages
    (P, 1, page_size, R) latent rows, the D lanes of a row in its first
    D of R = ``page_row_dim(D)``; values are a row's first
    ``value_dim`` lanes.  Returns (B, H, value_dim).  The kernel path
    is ``gama_latent_paged_decode``."""
    b = q.shape[0]
    if block_tables.shape[0] != b or block_tables.ndim != 2:
        raise ValueError(f"block_tables must be (B={b}, max_pages), got "
                         f"{block_tables.shape}")
    length = jnp.minimum(jnp.asarray(length, jnp.int32),
                         block_tables.shape[1] * pages.shape[2])
    block_tables = jnp.asarray(block_tables, jnp.int32)
    if not _use_kernel(mode):
        _obs_count("ops.decode_latent_paged.ref")
        return ref.ref_latent_paged_decode(
            q, pages, block_tables, length=length, value_dim=value_dim,
            scale=scale)
    _obs_count("ops.decode_latent_paged.kernel")
    qp = to_page_rows(q, pages.shape[-1])
    return _on_each_device(
        lambda q_, p_, bt, ln: latent_paged_decode(
            q_, p_, bt, length=ln, value_dim=value_dim, scale=scale,
            interpret=_interpret()))(qp, pages, block_tables, length)


def expert_gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
               mode: Mode = "auto") -> jax.Array:
    """Grouped matmul over held experts: x (M, K) rows sorted by expert,
    w (E, K, N), group_sizes (E,) rows per expert.  Returns (M, N); rows
    past the last group are zero on the ref path and unwritten on the
    kernel path (``gama_expert_gmm``), so callers mask them."""
    if not _use_kernel(mode):
        _obs_count("ops.expert_gmm.ref")
        return ref.ref_expert_gmm(x, w, group_sizes)
    _obs_count("ops.expert_gmm.kernel")
    m = x.shape[0]
    mp = _round_up(m, ROW_TILE)
    xp = jnp.pad(x, ((0, mp - m), (0, 0)))
    out = _on_each_device(functools.partial(
        _expert_gmm, interpret=_interpret()))(xp, w.astype(x.dtype),
                                              group_sizes)
    return out[:m]


def wkv(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
        u: jax.Array, *, chunk: Optional[int] = None, mode: Mode = "auto"
        ) -> jax.Array:
    """WKV6 recurrence.  r/k/v/w: (B, H, T, N); u: (H, N) -> (B, H, T, N).

    ``chunk`` (the time-axis grid step) defaults to the tuning cache's
    best for this (T, N) shape, falling back to the analytic 128.
    """
    if not _use_kernel(mode):
        return ref.ref_wkv(r, k, v, w, u)
    b, h, t, n = r.shape
    if chunk is None:
        from repro.tuning import dispatch
        chunk = dispatch.wkv_chunk(t, n, r.dtype)
    chunk = min(chunk, t)
    pad = (-t) % chunk
    if pad:
        zp = ((0, 0), (0, 0), (0, pad), (0, 0))
        # Pad decays with 1 (identity state update); r/k/v with 0 (no-op).
        r2, k2, v2 = (jnp.pad(x, zp) for x in (r, k, v))
        w2 = jnp.pad(w, zp, constant_values=1.0)
    else:
        r2, k2, v2, w2 = r, k, v, w
    out = _on_each_device(functools.partial(
        wkv6, chunk=chunk, interpret=_interpret()))(r2, k2, v2, w2, u)
    return out[:, :, :t]
