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
``page_size``-token blocks, and the per-slot block table is
scalar-prefetched and dereferenced inside the split-K loop, so the
pages of one sequence are gathered without ever materializing a
contiguous cache.  Its split-K step is a block of consecutive table
pages of every KV head (``buffers=2``, copied by explicit DMAs, only
the live pages), or one page of one head (``buffers=1``, a BlockSpec
gather).  The last (partial) page is masked by the same per-slot
length that masks the dense kernel.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NEG_INF = -1e30
_PAGED_BLOCK_KEYS = 2048        # keys per grid step of the paged DMA kernel
_PAGED_BLOCK_VMEM = 12 << 20    # bytes of its two K and two V block slots


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


def _online_softmax_step(q, k, v, m_prev, l_prev, acc, *, k_block_start,
                         length, scale: float, k_rows=None, v_rows=None):
    """The online-softmax update of one KV head over one block of keys.
    ``q`` is (gp, R) and ``k``/``v`` (..., R) as loaded, the leading
    dims flattening to the block's bk keys; ``m_prev`` and ``l_prev``
    are (gp, 1) f32 columns, ``acc`` the (gp, R) f32 accumulator.
    Returns the new ``(m, l, acc)``.  Both paged kernels call it, so at
    one page per block they stay bit-identical to each other (asserted
    by the fuzz suite).

    Keys at or past ``length`` are masked before the max, so whatever
    the block holds there (a partial page's tail, a sink page, rows a
    skipped page left behind) never reaches the output.  int8 pages
    pass their per-row scales as ``(1, bk)`` rows: the K dequant is
    folded into the logits (``q.(s_t k_t) = s_t (q.k_t)``) and the V
    dequant into the probabilities (``sum_t p_t s_t v_t``), so a scale
    row broadcasts along lanes and is never relaid out into a column.

    bf16 queries over a bf16 pool feed the MXU as they are, with no f32
    copies of K and V: the logits are exact products summed in f32, and
    the f32 probabilities enter the PV product as two bf16 terms
    (``p = hi + lo``), which keeps about 16 of their 24 mantissa bits.
    Other dtypes are cast to f32."""
    native = q.dtype == k.dtype == v.dtype == jnp.bfloat16
    if not native:
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k = k.reshape(-1, k.shape[-1])
    v = v.reshape(-1, v.shape[-1])
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (gp, bk)
    if k_rows is not None:
        s = s * k_rows
    s = s * scale
    k_pos = k_block_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = k_pos < length
    s = jnp.where(valid, s, _NEG_INF)

    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    pv = p if v_rows is None else p * v_rows
    terms = [pv]
    if native:
        hi = pv.astype(jnp.bfloat16)
        terms = [hi, (pv - hi.astype(jnp.float32)).astype(jnp.bfloat16)]
    acc = acc * alpha
    for t in terms:
        acc = acc + jax.lax.dot_general(
            t, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                         n_pages: int, page_size: int, scale: float,
                         quantized: bool):
    """Single-buffer paged kernel: one grid step = one page of one KV
    head of one slot's block table.  The K/V refs already hold the
    dereferenced page (the BlockSpec index map reads the
    scalar-prefetched table), so the body is the dense kernel's
    online-softmax with bk = page_size.  Quantized pools carry two
    extra scale-row refs; dequant happens in-body, fused with the
    logits matmul."""
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
        k_rows = v_rows = None
        if quantized:
            # The scale block holds every KV head's rows of this page;
            # pick this head's (1, page_size) row.
            k_rows = ks_ref[0, pl.ds(h, 1), :]
            v_rows = vs_ref[0, pl.ds(h, 1), :]
        m, l, acc = _online_softmax_step(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0],
            m_ref[...][:, :1], l_ref[...][:, :1], acc_ref[...],
            k_block_start=k_block_start, length=length, scale=scale,
            k_rows=k_rows, v_rows=v_rows)
        acc_ref[...] = acc
        m_ref[...] = jnp.broadcast_to(m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l, l_ref.shape)

    @pl.when(pi == n_pages - 1)
    def _done():
        l = l_ref[...][:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def _paged_decode_dbuf_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, *rest,
                              n_blocks: int, ppb: int, page_size: int,
                              hkv: int, scale: float, quantized: bool):
    """Double-buffered paged kernel: one grid step = one block of
    ``ppb`` consecutive table pages of one slot, all KV heads at once.

    K/V pools stay in HBM (memory_space=ANY).  A pool page
    ``(Hkv, page_size, R)`` is contiguous, so each page of the block is
    one DMA carrying every head, into one of two VMEM block slots (the
    GAMA ping-pong buff_0/buff_1): block ``j+1``'s copies start before
    block ``j``'s are waited on, so the next block's loads overlap this
    block's softmax and matmuls.  Only the slot's live pages are copied
    — page ``i`` is live while ``i * page_size < length`` — and the
    start and the wait of a page share that one predicate, so a block
    wholly past the length issues no DMA and no compute.  int8 scale
    rows ride the BlockSpec pipeline, pre-gathered per block."""
    if quantized:
        (ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref,
         k_buf, v_buf, sem) = rest
    else:
        o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf, sem = rest
    bb = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[bb]
    bk = ppb * page_size
    slot = jax.lax.rem(j, 2)

    live_pages = (length + page_size - 1) // page_size

    def gather(blk, buf_slot, start: bool):
        """Start, or wait for, the copies of block ``blk``'s live pages
        into ``buf_slot``; a skipped page leaves its rows as they were,
        and the mask keeps them out of the softmax."""
        first = blk * ppb

        def page_copies(i, carry):
            page = bt_ref[bb, first + i]
            for kind, (src, dst) in enumerate(((k_hbm, k_buf),
                                               (v_hbm, v_buf))):
                copy = pltpu.make_async_copy(
                    src.at[page], dst.at[buf_slot, i],
                    sem.at[kind, buf_slot])
                if start:
                    copy.start()
                else:
                    copy.wait()
            return carry

        jax.lax.fori_loop(0, jnp.clip(live_pages - first, 0, ppb),
                          page_copies, 0)

    @pl.when((bb == 0) & (j == 0))
    def _clear():
        # Rows of a skipped page meet p = 0 in the PV matmul; zeroing
        # the never-written buffers keeps them finite (0 * NaN = NaN).
        # Later they only ever hold pool rows.
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # Warm-up: the first block has nothing to hide behind.
        gather(0, 0, start=True)

    @pl.when(j + 1 < n_blocks)
    def _prefetch():
        gather(j + 1, 1 - slot, start=True)

    gather(j, slot, start=False)

    @pl.when(j * bk < length)
    def _body():
        for h in range(hkv):
            k_rows = v_rows = None
            if quantized:
                k_rows = ks_ref[0, pl.ds(h, 1), :]       # (1, bk)
                v_rows = vs_ref[0, pl.ds(h, 1), :]
            m, l, acc = _online_softmax_step(
                q_ref[0, h], k_buf[slot, :, h], v_buf[slot, :, h],
                m_ref[h][:, :1], l_ref[h][:, :1], acc_ref[h],
                k_block_start=j * bk, length=length, scale=scale,
                k_rows=k_rows, v_rows=v_rows)
            acc_ref[h] = acc
            m_ref[h] = jnp.broadcast_to(m, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l, l_ref.shape[1:])

    @pl.when(j == n_blocks - 1)
    def _done():
        for h in range(hkv):
            l = l_ref[h][:, :1]
            safe_l = jnp.where(l > 0.0, l, 1.0)
            o_ref[0, h] = (acc_ref[h] / safe_l).astype(o_ref.dtype)


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


def paged_pages_per_block(max_pages: int, hkv: int, page_size: int,
                          row_dim: int, itemsize: int,
                          quantized: bool = False) -> int:
    """Table pages per grid step of ``flash_paged_decode(buffers=2)``,
    derived from the shapes it sees: 2048 keys, halved while both K and
    V block slots would take more than 12 MiB of VMEM, and never more
    than the table holds.  On a v5e, blocks of 16 to 128 pages of 16
    keys took about the same time at chat lengths, and the largest that
    fit took the least at long contexts.  An int8 pool's per-block scale
    rows must be whole 128-lane tiles unless one block spans the table,
    so its block rounds up to that.

    >>> paged_pages_per_block(128, 5, 16, 128, 2)      # smollm_360m
    128
    >>> paged_pages_per_block(128, 10, 16, 128, 2)     # phi3m
    64
    >>> paged_pages_per_block(128, 32, 16, 128, 4)     # VMEM-capped
    8
    >>> paged_pages_per_block(3, 2, 16, 128, 2)        # short table
    3
    """
    ppb = max(1, _PAGED_BLOCK_KEYS // page_size)
    while (ppb > 1 and 2 * 2 * ppb * hkv * page_size * row_dim * itemsize
           > _PAGED_BLOCK_VMEM):
        ppb //= 2
    if quantized:
        step = _LANES // math.gcd(page_size, _LANES)
        ppb = -(-ppb // step) * step
    return min(ppb, max_pages)


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
    pages_per_block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged flash decode.  q: (B, Hq, D); k_pages/v_pages:
    (P, Hkv, page_size, R) pool arrays (P includes the null sink page)
    whose rows hold the head dim in their first D lanes, R >= D (the
    serving pools use R = :func:`page_row_dim` (D), zero-padded);
    block_tables: (B, max_pages) int32 page ids; length: (B,) int32
    valid rows per slot.  Returns (B, Hq, D).

    The split-K loop walks the block table, not the pool: slot ``b``'s
    keys come from pool pages ``block_tables[b, :]`` in order, so KV is
    gathered inside the loop.  ``buffers`` picks the gather pipeline:

    * 1 — grid (B, Hkv, max_pages), one page of one head per step, the
      page fetched by a BlockSpec index map that reads the
      scalar-prefetched table;
    * 2 — grid (B, n_blocks), one block of ``pages_per_block`` pages of
      every head per step, fetched by explicit whole-page DMAs into two
      ping-pong VMEM slots (block ``j+1``'s copies issued before block
      ``j``'s compute).  Pages past a slot's length are not copied, so
      the cost follows the live lengths, not the table.  The TPU
      compiler accepts the copies only for lane-aligned rows
      (R % 128 == 0).  ``pages_per_block`` defaults to
      :func:`paged_pages_per_block`; at 1 the output is bit-identical
      to ``buffers=1``, as the two share one arithmetic body.

    int8 pools pass per-row scale rows ``k_scale``/``v_scale``
    ((P, Hkv, page_size) f32); dequant is fused into the split-K loop,
    so quantized pages cost half the f32 HBM traffic and no extra pass.
    Table entries past a slot's allocation point at the null page and
    are never read.  The q-head group must be sublane-padded by the
    caller (ops.py pads to >= 8 rows, as for the dense kernel).
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
    block_tables = block_tables.astype(jnp.int32)
    # Rows past the table are never keys, so no page past it is read.
    length = jnp.minimum(length.reshape(b).astype(jnp.int32),
                         n_pages * page_size)
    state_shapes = [(group, _LANES), (group, _LANES), (group, rd)]
    operands = [block_tables, length, qg, k_pages, v_pages]

    if buffers == 1:
        grid = (b, hkv, n_pages)
        kernel = functools.partial(
            _paged_decode_kernel, n_pages=n_pages, page_size=page_size,
            scale=scale, quantized=quantized)
        head_spec = pl.BlockSpec((1, 1, group, rd),
                                 lambda bb, h, pi, bt, ln: (bb, h, 0, 0))
        page_spec = pl.BlockSpec(
            (1, 1, page_size, rd),
            lambda bb, h, pi, bt, ln: (bt[bb, pi], h, 0, 0))
        in_specs = [head_spec, page_spec, page_spec]
        if quantized:
            # The whole (Hkv, page_size) scale block of the page: a
            # one-head (1, page_size) block would break the TPU rule
            # that a block's last two dims are (8, 128)-aligned or span
            # the array.
            in_specs += [pl.BlockSpec(
                (1, hkv, page_size),
                lambda bb, h, pi, bt, ln: (bt[bb, pi], 0, 0))] * 2
            operands += [k_scale.astype(jnp.float32),
                         v_scale.astype(jnp.float32)]
        scratch = [pltpu.VMEM(s, jnp.float32) for s in state_shapes]
        out_spec = head_spec
        semantics = ("parallel", "parallel", "arbitrary")
    else:
        ppb = pages_per_block or paged_pages_per_block(
            n_pages, hkv, page_size, rd, k_pages.dtype.itemsize, quantized)
        n_blocks = -(-n_pages // ppb)
        bk = ppb * page_size
        if n_blocks * ppb != n_pages:
            # Entries past the table lie past every length: never read.
            block_tables = jnp.pad(
                block_tables, ((0, 0), (0, n_blocks * ppb - n_pages)))
            operands[0] = block_tables
        grid = (b, n_blocks)
        kernel = functools.partial(
            _paged_decode_dbuf_kernel, n_blocks=n_blocks, ppb=ppb,
            page_size=page_size, hkv=hkv, scale=scale, quantized=quantized)
        heads_spec = pl.BlockSpec((1, hkv, group, rd),
                                  lambda bb, j, bt, ln: (bb, 0, 0, 0))
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [heads_spec, any_spec, any_spec]
        if quantized:
            # A scale row is not lane-aligned at small page sizes, so no
            # DMA can fetch it: gather each slot's rows in table order,
            # one (1, Hkv, bk) row block per grid step.
            def rows(sc):
                g = jnp.take(sc.astype(jnp.float32), block_tables, axis=0)
                return g.transpose(0, 2, 1, 3).reshape(
                    b, hkv, n_blocks * bk)
            in_specs += [pl.BlockSpec((1, hkv, bk),
                                      lambda bb, j, bt, ln: (bb, 0, j))] * 2
            operands += [rows(k_scale), rows(v_scale)]
        scratch = [pltpu.VMEM((hkv,) + s, jnp.float32)
                   for s in state_shapes] + [
            pltpu.VMEM((2, ppb, hkv, page_size, rd), k_pages.dtype),
            pltpu.VMEM((2, ppb, hkv, page_size, rd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ]
        out_spec = heads_spec
        # The first grid step clears the block slots for all others.
        semantics = ("arbitrary", "arbitrary")

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, rd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        interpret=interpret,
        name=f"gama_flash_paged_decode_b{buffers}",
    )(*operands)
    return out.reshape(b, hq, rd)[..., :d]


def latent_pages_per_block(max_pages: int, page_size: int, row_dim: int,
                           itemsize: int) -> int:
    """Table pages per grid step of :func:`latent_paged_decode`: 2048
    keys, halved while its two block slots would take more than 12 MiB
    of VMEM, and never more than the table holds.

    >>> latent_pages_per_block(128, 16, 576, 2)    # DeepSeek-V2-Lite
    128
    >>> latent_pages_per_block(3, 16, 576, 2)
    3
    """
    ppb = max(1, _PAGED_BLOCK_KEYS // page_size)
    while (ppb > 1
           and 2 * ppb * page_size * row_dim * itemsize > _PAGED_BLOCK_VMEM):
        ppb //= 2
    return min(ppb, max_pages)


def _latent_decode_kernel(bt_ref, len_ref, q_ref, pages_hbm, o_ref,
                          m_ref, l_ref, acc_ref, buf, sem, *,
                          batch: int, n_blocks: int, ppb: int,
                          page_size: int, value_dim: int, scale: float):
    """One grid step = one block of ``ppb`` table pages of one slot,
    every query head against each latent row.

    The pool stays in HBM; each live page is one DMA into one of two
    VMEM block slots.  The slots alternate over the whole grid, not
    per slot: the step before copies the next step's block (the next
    block of this slot, or the first block of the next slot), so a
    slot's one block at serving lengths still overlaps the slot before
    it.  A page is live while ``i * page_size < length``; the start and
    the wait of a copy share that predicate."""
    bb = pl.program_id(0)
    j = pl.program_id(1)
    step = bb * n_blocks + j
    slot = jax.lax.rem(step, 2)
    bk = ppb * page_size
    length = len_ref[bb]

    def gather(b_, blk, buf_slot, start: bool):
        live_pages = (len_ref[b_] + page_size - 1) // page_size
        first = blk * ppb

        def page_copy(i, carry):
            copy = pltpu.make_async_copy(
                pages_hbm.at[bt_ref[b_, first + i]], buf.at[buf_slot, i],
                sem.at[buf_slot])
            if start:
                copy.start()
            else:
                copy.wait()
            return carry

        jax.lax.fori_loop(0, jnp.clip(live_pages - first, 0, ppb),
                          page_copy, 0)

    @pl.when(step == 0)
    def _warm_up():
        # Rows of a skipped page meet p = 0 in the PV matmul; zeroing
        # the never-written slots keeps them finite (0 * NaN = NaN).
        buf[...] = jnp.zeros_like(buf)
        gather(0, 0, 0, start=True)

    last = j + 1 == n_blocks
    nb = jnp.where(last, bb + 1, bb)
    nj = jnp.where(last, 0, j + 1)

    @pl.when(nb < batch)
    def _prefetch():
        gather(nb, nj, 1 - slot, start=True)

    gather(bb, j, slot, start=False)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bk < length)
    def _body():
        m, l, acc = _online_softmax_step(
            q_ref[0], buf[slot], buf[slot, :, :, :, :value_dim],
            m_ref[...][:, :1], l_ref[...][:, :1], acc_ref[...],
            k_block_start=j * bk, length=length, scale=scale)
        acc_ref[...] = acc
        m_ref[...] = jnp.broadcast_to(m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l, l_ref.shape)

    @pl.when(j == n_blocks - 1)
    def _done():
        l = l_ref[...][:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def latent_paged_decode(
    q: jax.Array,
    pages: jax.Array,
    block_tables: jax.Array,
    *,
    length: jax.Array,
    value_dim: int,
    scale: float,
    pages_per_block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged decode over a latent KV cache (multi-head latent
    attention, absorbed form).  q: (B, H, W) absorbed queries; pages:
    (P, 1, page_size, W), one latent row per key (P includes the null
    sink page); block_tables: (B, max_pages) int32; length: (B,) int32.
    Returns (B, H, value_dim).

    Every query head attends over the same rows: the logits are q . row
    over all W lanes, the values are the rows' first ``value_dim``
    lanes.  So this is a multi-query attention whose keys and values
    share one pool and one DMA per page.  The grid is (B, n_blocks),
    one block of ``pages_per_block`` table pages per step (default
    :func:`latent_pages_per_block`), only the live pages copied, as in
    :func:`flash_paged_decode` with ``buffers=2``."""
    b, h, w = q.shape
    _, one, page_size, rw = pages.shape
    _, n_pages = block_tables.shape
    if one != 1 or rw != w:
        raise ValueError(f"latent pages must be (P, 1, page_size, {w}), "
                         f"got {pages.shape}")
    if not 0 < value_dim <= w:
        raise ValueError(f"value_dim {value_dim} outside the {w}-lane rows")
    ppb = pages_per_block or latent_pages_per_block(
        n_pages, page_size, w, pages.dtype.itemsize)
    n_blocks = -(-n_pages // ppb)
    block_tables = block_tables.astype(jnp.int32)
    if n_blocks * ppb != n_pages:
        # Entries past the table lie past every length: never read.
        block_tables = jnp.pad(block_tables,
                               ((0, 0), (0, n_blocks * ppb - n_pages)))
    length = jnp.minimum(length.reshape(b).astype(jnp.int32),
                         n_pages * page_size)
    kernel = functools.partial(
        _latent_decode_kernel, batch=b, n_blocks=n_blocks, ppb=ppb,
        page_size=page_size, value_dim=value_dim, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_blocks),
            in_specs=[pl.BlockSpec((1, h, w),
                                   lambda bb, j, bt, ln: (bb, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, value_dim),
                                   lambda bb, j, bt, ln: (bb, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, _LANES), jnp.float32),
                pltpu.VMEM((h, _LANES), jnp.float32),
                pltpu.VMEM((h, value_dim), jnp.float32),
                pltpu.VMEM((2, ppb, 1, page_size, w), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gama_latent_paged_decode",
    )(block_tables, length, q, pages)
    return out
