"""Flash decode — single-token KV-cache attention, split-K over the cache.

The decode shape (one query token, very long KV) is bandwidth-bound: the
kernel streams the KV cache once, keeping the online-softmax state in
VMEM.  GQA trick: the ``group = Hq/Hkv`` query heads sharing one KV head
are stacked into the sublane dimension so the (group, bk) logits block
feeds the MXU/VPU efficiently — this is the TPU analogue of the paper's
"pack" reading one stream and producing one combined result.

Grid: (B, Hkv, Sk/bk), KV innermost ("arbitrary"); length masking reads
the (B,) int32 length vector as a scalar-prefetch operand (SMEM): a
per-slot VMEM block of a (B, 1) array would be a (1, 1) block, which the
TPU compiler refuses for B > 1 (a block's last two dims must be
multiples of (8, 128) or span the array).

The length is **per slot**: in a ragged continuous batch every row of
the cache belongs to a different request at a different position, and
the kernel never attends past its own row's length — whole split-K
blocks beyond it are skipped (the ``k_block_start < length`` guard), a
zero-length row yields a zero output (the ``safe_l`` divisor), and
stale KV from a slot's previous occupant is unreachable by
construction.

:func:`flash_paged_decode` is the same online-softmax over a **paged**
KV cache (``repro.serving.kvpool``): K/V live in a global page pool of
``page_size``-token blocks, and the kernel's split-K step *is* one
page — the per-slot block table is scalar-prefetched, and each KV
block's index map dereferences it, so the pages of one sequence are
gathered inside the split-K loop without ever materializing a
contiguous cache.  The last (partial) page is masked by the same
per-slot length that masks the dense kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   k_steps: int, bk: int, gp: int, scale: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]
    k_block_start = ki * bk

    @pl.when(k_block_start < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (gp, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (gp, bk)
        k_pos = k_block_start + jax.lax.broadcasted_iota(
            jnp.int32, (gp, bk), 1)
        valid = k_pos < length
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[...][:, :1]
        l_prev = l_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == k_steps - 1)
    def _done():
        l = l_ref[...][:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    length: Optional[jax.Array] = None,
    bk: int = 512,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hq, D); k/v: (B, Hkv, Sk, D); returns (B, Hq, D).

    ``length``: (B,) int32 valid-prefix lengths (defaults to full Sk).
    The q-head group dimension must be sublane-padded by the caller
    (ops.py pads Hq/Hkv groups to >= 8 rows).
    """
    b, hq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    assert sk % bk == 0, (sk, bk)
    if scale is None:
        scale = d ** -0.5
    if length is None:
        length = jnp.full((b,), sk, jnp.int32)
    length = length.reshape(b).astype(jnp.int32)
    # Stack each KV head's q group into the sublane dim.
    qg = q.reshape(b, hkv, group, d)
    k_steps = sk // bk
    grid = (b, hkv, k_steps)

    kernel = functools.partial(_decode_kernel, k_steps=k_steps, bk=bk,
                               gp=group, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, group, d),
                             lambda bb, h, ki, ln: (bb, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda bb, h, ki, ln: (bb, h, ki, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda bb, h, ki, ln: (bb, h, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, group, d),
                                   lambda bb, h, ki, ln: (bb, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, _LANES), jnp.float32),
                pltpu.VMEM((group, _LANES), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gama_flash_decode",
    )(length, qg, k, v)
    return out.reshape(b, hq, d)


def _paged_page_step(q_ref, k, v, m_ref, l_ref, acc_ref, *,
                     k_block_start, length, gp: int, page_size: int,
                     scale: float, k_rows=None, v_rows=None):
    """The online-softmax update for one dereferenced page.  ``k``/``v``
    are the page's values cast to f32, so every buffering/precision
    variant of the paged kernel shares one arithmetic body and they
    stay bit-identical to each other (asserted by the fuzz suite).

    int8 pages pass their per-row scales as ``(1, page_size)`` rows:
    the K dequant is folded into the logits (``q.(s_t k_t) =
    s_t (q.k_t)``) and the V dequant into the probabilities
    (``sum_t p_t s_t v_t``), so a scale row broadcasts along lanes and
    is never relaid out into a (page_size, 1) column."""
    q = q_ref[0, 0].astype(jnp.float32)              # (gp, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (gp, page_size)
    if k_rows is not None:
        s = s * k_rows
    s = s * scale
    k_pos = k_block_start + jax.lax.broadcasted_iota(
        jnp.int32, (gp, page_size), 1)
    valid = k_pos < length                           # partial-page mask
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[...][:, :1]
    l_prev = l_ref[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    pv = p if v_rows is None else p * v_rows
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                         n_pages: int, page_size: int, gp: int,
                         scale: float, quantized: bool):
    """Single-buffer paged kernel: one grid step = one page of one
    slot's block table.  The K/V refs already hold the dereferenced page
    (the BlockSpec index map reads the scalar-prefetched table), so the
    body is the dense kernel's online-softmax with bk = page_size.
    Quantized pools carry two extra scale-row refs; dequant happens
    in-body, fused with the logits matmul."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = pl.program_id(1)
    length = len_ref[pl.program_id(0)]
    k_block_start = pi * page_size

    # Pages at or past the length are either the partial tail (handled
    # by the in-block mask) or unallocated table entries pointing at
    # the pool's null sink — the guard skips the sink pages entirely.
    @pl.when(k_block_start < length)
    def _body():
        k = k_ref[0, 0].astype(jnp.float32)          # (page_size, d)
        v = v_ref[0, 0].astype(jnp.float32)
        k_rows = v_rows = None
        if quantized:
            # The scale block holds every KV head's rows of this page;
            # pick this head's (1, page_size) row.
            k_rows = ks_ref[0, pl.ds(h, 1), :]
            v_rows = vs_ref[0, pl.ds(h, 1), :]
        _paged_page_step(q_ref, k, v, m_ref, l_ref, acc_ref,
                         k_block_start=k_block_start, length=length,
                         gp=gp, page_size=page_size, scale=scale,
                         k_rows=k_rows, v_rows=v_rows)

    @pl.when(pi == n_pages - 1)
    def _done():
        l = l_ref[...][:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def _paged_decode_dbuf_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, *rest,
                              n_pages: int, page_size: int, gp: int,
                              scale: float, quantized: bool):
    """Double-buffered paged kernel: the GAMA ping-pong (buff_0/buff_1)
    DMA pipeline.  K/V pools stay in HBM (memory_space=ANY); each grid
    step dereferences the block table itself and issues explicit async
    copies into two VMEM page slots, starting page ``pi+1``'s copy
    *before* waiting on page ``pi`` — the next page's KV loads overlap
    this page's softmax/matmul.  int8 scale rows are small and ride the
    BlockSpec pipeline, as in the single-buffer kernel.  The arithmetic
    body is shared with that kernel, so outputs are bit-identical."""
    if quantized:
        (ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref,
         k_buf, v_buf, sem) = rest
    else:
        o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf, sem = rest
    bb = pl.program_id(0)
    h = pl.program_id(1)
    pi = pl.program_id(2)

    def page_copies(slot, page_idx):
        """The (src, dst, sem) copy descriptors of one page gather.
        ``.start()`` on both issues the slot's DMAs; ``.wait()`` blocks
        until the slot holds the page."""
        page = bt_ref[bb, page_idx]
        return [
            pltpu.make_async_copy(k_hbm.at[page, h], k_buf.at[slot],
                                  sem.at[0, slot]),
            pltpu.make_async_copy(v_hbm.at[page, h], v_buf.at[slot],
                                  sem.at[1, slot]),
        ]

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # Warm-up: the first page has nothing to hide behind.
        for c in page_copies(0, 0):
            c.start()

    slot = jax.lax.rem(pi, 2)

    # Ping-pong: kick off page pi+1 into the *other* slot before
    # blocking on page pi — this is the load/compute overlap.
    @pl.when(pi + 1 < n_pages)
    def _prefetch():
        for c in page_copies(jax.lax.rem(pi + 1, 2), pi + 1):
            c.start()

    for c in page_copies(slot, pi):
        c.wait()

    length = len_ref[pl.program_id(0)]
    k_block_start = pi * page_size

    @pl.when(k_block_start < length)
    def _body():
        k = k_buf[slot].astype(jnp.float32)
        v = v_buf[slot].astype(jnp.float32)
        k_rows = v_rows = None
        if quantized:
            k_rows = ks_ref[0, pl.ds(h, 1), :]
            v_rows = vs_ref[0, pl.ds(h, 1), :]
        _paged_page_step(q_ref, k, v, m_ref, l_ref, acc_ref,
                         k_block_start=k_block_start, length=length,
                         gp=gp, page_size=page_size, scale=scale,
                         k_rows=k_rows, v_rows=v_rows)

    @pl.when(pi == n_pages - 1)
    def _done():
        l = l_ref[...][:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def page_row_dim(d_head: int) -> int:
    """Width of one stored KV page row: the head dim rounded up to a
    multiple of 128 lanes, the pad lanes holding zeros.  The explicit-
    DMA page gather (``buffers=2``) can only slice an HBM array whose
    minor dim is lane-aligned, and the TPU's tiled HBM layout pads a
    narrower row to 128 lanes anyway, so the padding costs no device
    memory.

    >>> page_row_dim(64), page_row_dim(128), page_row_dim(160)
    (128, 128, 256)
    """
    return -(-d_head // _LANES) * _LANES


def flash_paged_decode(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    *,
    length: jax.Array,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    buffers: int = 2,
    interpret: bool = False,
) -> jax.Array:
    """Paged flash decode.  q: (B, Hq, D); k_pages/v_pages:
    (P, Hkv, page_size, R) pool arrays (P includes the null sink page)
    whose rows hold the head dim in their first D lanes, R >= D (the
    serving pools use R = :func:`page_row_dim` (D), zero-padded);
    block_tables: (B, max_pages) int32 page ids; length: (B,) int32
    valid rows per slot.  Returns (B, Hq, D).

    The split-K grid walks the block table, not the pool: step ``i`` of
    slot ``b`` streams pool page ``block_tables[b, i]``, so KV is
    gathered page by page inside the loop.  ``buffers`` picks the
    gather pipeline: 1 = BlockSpec index maps (the scalar-prefetched
    table dereferenced per step), 2 = explicit two-slot DMA ping-pong
    (page ``i+1``'s copy issued before page ``i``'s compute), which the
    TPU compiler accepts only for lane-aligned rows (R % 128 == 0).
    Both are bit-identical — they share the arithmetic body.

    int8 pools pass per-row scale rows ``k_scale``/``v_scale``
    ((P, Hkv, page_size) f32); dequant is fused into the split-K loop,
    so quantized pages cost half the f32 HBM traffic and no extra pass.
    Table entries past a slot's allocation point at the null page and
    are skipped by the length guard.  The q-head group must be sublane-
    padded by the caller (ops.py pads to >= 8 rows, as for the dense
    kernel).
    """
    b, hq, d = q.shape
    _, hkv, page_size, rd = k_pages.shape
    _, n_pages = block_tables.shape
    assert hq % hkv == 0
    group = hq // hkv
    if buffers not in (1, 2):
        raise ValueError(f"buffers must be 1 or 2, got {buffers}")
    if rd < d:
        raise ValueError(f"page rows of {rd} lanes cannot hold head "
                         f"dim {d}")
    if buffers == 2 and not interpret and rd % _LANES:
        raise ValueError(
            f"buffers=2 gathers pages with explicit DMAs, and the TPU "
            f"compiler only slices an HBM array whose minor dim is a "
            f"multiple of 128 lanes (page rows of {rd}).  Store rows of "
            f"page_row_dim(d) lanes, as init_paged_kv_cache does, or "
            f"use buffers=1")
    quantized = k_pages.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 k_pages/v_pages need k_scale and v_scale "
                         "rows (P, Hkv, page_size)")
    if not quantized and (k_scale is not None or v_scale is not None):
        raise ValueError("k_scale/v_scale are only valid for int8 pools")
    if scale is None:
        scale = d ** -0.5
    if rd != d:
        # Zero query lanes meet the pool's zero pad lanes: q.k and the
        # output's first d lanes are unchanged.
        q = jnp.pad(q, ((0, 0), (0, 0), (0, rd - d)))
    qg = q.reshape(b, hkv, group, rd)
    grid = (b, hkv, n_pages)

    head_specs = [
        pl.BlockSpec((1, 1, group, rd),
                     lambda bb, h, pi, bt, ln: (bb, h, 0, 0)),
    ]
    state_scratch = [
        pltpu.VMEM((group, _LANES), jnp.float32),
        pltpu.VMEM((group, _LANES), jnp.float32),
        pltpu.VMEM((group, rd), jnp.float32),
    ]
    operands = [block_tables.astype(jnp.int32),
                length.reshape(b).astype(jnp.int32), qg, k_pages, v_pages]
    # The whole (Hkv, page_size) scale block of the page: a one-head
    # (1, page_size) block would break the TPU rule that a block's last
    # two dims are (8, 128)-aligned or span the array, and a DMA of a
    # scale row is not lane-aligned unless page_size % 128 == 0.
    srow_spec = pl.BlockSpec((1, hkv, page_size),
                             lambda bb, h, pi, bt, ln: (bt[bb, pi], 0, 0))
    scale_specs = [srow_spec, srow_spec] if quantized else []
    if quantized:
        operands += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

    if buffers == 1:
        kernel = functools.partial(
            _paged_decode_kernel, n_pages=n_pages, page_size=page_size,
            gp=group, scale=scale, quantized=quantized)
        page_spec = pl.BlockSpec(
            (1, 1, page_size, rd),
            lambda bb, h, pi, bt, ln: (bt[bb, pi], h, 0, 0))
        in_specs = head_specs + [page_spec, page_spec] + scale_specs
        scratch = list(state_scratch)
    else:
        kernel = functools.partial(
            _paged_decode_dbuf_kernel, n_pages=n_pages, page_size=page_size,
            gp=group, scale=scale, quantized=quantized)
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = head_specs + [any_spec, any_spec] + scale_specs
        scratch = list(state_scratch) + [
            pltpu.VMEM((2, page_size, rd), k_pages.dtype),
            pltpu.VMEM((2, page_size, rd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, group, rd),
                                   lambda bb, h, pi, bt, ln: (bb, h, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, rd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"gama_flash_paged_decode_b{buffers}",
    )(*operands)
    return out.reshape(b, hq, rd)[..., :d]
