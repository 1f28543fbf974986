"""Pallas kernel validation vs the pure-jnp oracles (interpret mode).

Shape/dtype sweeps per the assignment + hypothesis property checks for the
int8 requantization epilogue.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback sampler, see _hypothesis_stub
    from _hypothesis_stub import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import (flash_paged_decode,
                                           paged_pages_per_block)

RNG = np.random.default_rng(42)


def randf(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


def randi8(shape):
    return jnp.asarray(RNG.integers(-128, 128, size=shape), jnp.int8)


# ---------------------------------------------------------------------------
# GEMM
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128), (256, 512, 128), (100, 300, 50), (8, 128, 128),
    (257, 129, 127),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_float(m, k, n, dtype):
    a, b = randf((m, k), dtype), randf((k, n), dtype)
    out = ops.matmul(a, b, mode="kernel")
    exp = ref.ref_gemm(a, b)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol * 8)


@pytest.mark.parametrize("out_dtype,scale", [
    (jnp.int32, 1.0), (jnp.int16, 0.05), (jnp.int8, 0.002),
])
@pytest.mark.parametrize("m,k,n", [(64, 256, 64), (33, 100, 65)])
def test_gemm_int8_epilogue_exact(m, k, n, out_dtype, scale):
    a, b = randi8((m, k)), randi8((k, n))
    out = ops.matmul(a, b, out_dtype=out_dtype, scale=scale, mode="kernel")
    exp = ref.ref_gemm(a, b, out_dtype=out_dtype, scale=scale)
    assert out.dtype == jnp.dtype(out_dtype)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@given(st.integers(1, 5), st.integers(1, 5), st.integers(1, 5),
       st.sampled_from(["int32", "int16", "int8"]))
@settings(max_examples=15, deadline=None)
def test_gemm_int8_property(mi, ki, ni, od):
    m, k, n = 8 * mi, 32 * ki, 16 * ni
    a = jnp.asarray(RNG.integers(-128, 128, size=(m, k)), jnp.int8)
    b = jnp.asarray(RNG.integers(-128, 128, size=(k, n)), jnp.int8)
    out = ops.matmul(a, b, out_dtype=jnp.dtype(od), scale=0.01,
                     mode="kernel")
    exp = ref.ref_gemm(a, b, out_dtype=jnp.dtype(od), scale=0.01)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1), (15, 5)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gqa(hq, hkv, causal):
    q = randf((2, hq, 96, 32))
    k = randf((2, hkv, 96, 32))
    v = randf((2, hkv, 96, 32))
    out = ops.attention(q, k, v, causal=causal, bq=32, bk=32, mode="kernel")
    exp = ref.ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq,sk,q_offset", [
    (64, 64, 0), (16, 80, 64), (100, 100, 0), (33, 77, 44),
])
def test_flash_attention_offsets(sq, sk, q_offset):
    """Chunked-prefill shapes: q at an absolute offset into the KV."""
    q = randf((1, 4, sq, 64))
    k = randf((1, 2, sk, 64))
    v = randf((1, 2, sk, 64))
    out = ops.attention(q, k, v, causal=True, q_offset=q_offset,
                        bq=32, bk=32, mode="kernel")
    exp = ref.ref_attention(q, k, v, causal=True, q_offset=q_offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    q = randf((2, 4, 128, 64), jnp.bfloat16)
    k = randf((2, 2, 128, 64), jnp.bfloat16)
    v = randf((2, 2, 128, 64), jnp.bfloat16)
    out = ops.attention(q, k, v, causal=True, mode="kernel")
    exp = ref.ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("mode", ["ref", "kernel"])
def test_attention_rejects_non_divisible_gqa(mode):
    """Regression: hq % hkv != 0 used to silently truncate the GQA
    group (wrong attention); now it raises on every backend path."""
    q = randf((1, 5, 32, 16))
    k = randf((1, 3, 32, 16))
    v = randf((1, 3, 32, 16))
    with pytest.raises(ValueError, match="divisible"):
        ops.attention(q, k, v, mode=mode)


# ---------------------------------------------------------------------------
# Decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["ref", "kernel"])
def test_decode_rejects_non_divisible_gqa(mode):
    q = randf((2, 6, 16))
    k = randf((2, 4, 64, 16))
    v = randf((2, 4, 64, 16))
    with pytest.raises(ValueError, match="divisible"):
        ops.decode(q, k, v, mode=mode)


@pytest.mark.parametrize("hq,hkv,sk", [(8, 2, 256), (4, 4, 300), (16, 2, 128)])
def test_flash_decode(hq, hkv, sk):
    q = randf((3, hq, 64))
    k = randf((3, hkv, sk, 64))
    v = randf((3, hkv, sk, 64))
    lengths = jnp.asarray([sk, sk // 2, 7], jnp.int32)
    out = ops.decode(q, k, v, length=lengths, bk=128, mode="kernel")
    exp = ref.ref_decode_attention(q, k, v, length=lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Paged decode attention (kvpool block tables)
# ---------------------------------------------------------------------------


def _rand_block_tables(b, max_pages, n_pool, lengths, page_size, seed=0):
    """Random *disjoint* per-slot page lists (null-sink tail)."""
    rng = np.random.default_rng(seed)
    perm = list(rng.permutation(n_pool))
    bt = np.full((b, max_pages), n_pool, np.int32)   # null = sink index
    for i, ln in enumerate(lengths):
        n = -(-int(ln) // page_size)
        pages, perm = perm[:n], perm[n:]
        bt[i, :n] = pages
    return jnp.asarray(bt)


@pytest.mark.parametrize("buffers", [1, 2])
@pytest.mark.parametrize("hq,hkv,ps", [(8, 2, 16), (4, 4, 32), (16, 2, 64)])
def test_flash_paged_decode_matches_ref(hq, hkv, ps, buffers):
    """The block-table kernel must equal the gather-then-dense oracle,
    including a partial last page and a one-token slot — on both the
    BlockSpec-gather path (buffers=1) and the explicit-DMA
    double-buffered pipeline (buffers=2)."""
    b, d, n_pool = 3, 64, 24
    lengths = np.asarray([3 * ps + 5, ps, 1])
    max_pages = 4
    q = randf((b, hq, d))
    k_pages = randf((n_pool + 1, hkv, ps, d))        # +1 = null sink
    v_pages = randf((n_pool + 1, hkv, ps, d))
    bt = _rand_block_tables(b, max_pages, n_pool, lengths, ps)
    ln = jnp.asarray(lengths, jnp.int32)
    out = ops.decode_paged(q, k_pages, v_pages, block_tables=bt,
                           length=ln, buffers=buffers, mode="kernel")
    exp = ref.ref_paged_decode_attention(q, k_pages, v_pages, bt,
                                         length=ln)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def _quantize_pool(pages):
    from repro.serving.quant import quantize_kv_pages
    return quantize_kv_pages(pages)


@pytest.mark.parametrize("buffers", [1, 2])
def test_flash_paged_decode_int8_matches_dequant_oracle(buffers):
    """int8 pages + per-row scales through the fused-dequant kernel must
    equal the dequantize-then-attend oracle to float tolerance (the
    kernel dequantizes inside its split-K page loop with the exact same
    q.astype(f32) * scale arithmetic)."""
    b, hq, hkv, d, ps, n_pool = 3, 8, 2, 64, 16, 24
    lengths = np.asarray([3 * ps + 5, ps, 1])
    q = randf((b, hq, d))
    kq, ksc = _quantize_pool(randf((n_pool + 1, hkv, ps, d)))
    vq, vsc = _quantize_pool(randf((n_pool + 1, hkv, ps, d)))
    bt = _rand_block_tables(b, 4, n_pool, lengths, ps)
    ln = jnp.asarray(lengths, jnp.int32)
    out = ops.decode_paged(q, kq, vq, block_tables=bt, length=ln,
                           k_scale=ksc, v_scale=vsc, buffers=buffers,
                           mode="kernel")
    exp = ref.ref_paged_decode_attention(q, kq, vq, bt, length=ln,
                                         k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_paged_decode_double_buffer_bit_identical(quantized):
    """buffers=2 (explicit-DMA pipelined block gather) at one page per
    block and buffers=1 (BlockSpec gather) share one arithmetic body —
    their outputs must be BIT-identical, not just close: any drift
    means the pipeline reordered or re-rounded the online softmax.  At
    its derived block size buffers=2 sums larger blocks, so it is held
    to the oracle's float tolerance instead."""
    b, hq, hkv, d, ps, n_pool = 4, 8, 2, 64, 16, 24
    lengths = np.asarray([3 * ps + 5, 2 * ps, ps - 1, 1])
    q = randf((b, hq, d))
    if quantized:
        kp, ks = _quantize_pool(randf((n_pool + 1, hkv, ps, d)))
        vp, vs = _quantize_pool(randf((n_pool + 1, hkv, ps, d)))
        scales = {"k_scale": ks, "v_scale": vs}
    else:
        kp = randf((n_pool + 1, hkv, ps, d))
        vp = randf((n_pool + 1, hkv, ps, d))
        scales = {}
    bt = _rand_block_tables(b, 4, n_pool, lengths, ps, seed=11)
    ln = jnp.asarray(lengths, jnp.int32)
    one = flash_paged_decode(q, kp, vp, bt, length=ln, buffers=1,
                             interpret=True, **scales)
    two = flash_paged_decode(q, kp, vp, bt, length=ln, buffers=2,
                             pages_per_block=1, interpret=True, **scales)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(two))
    derived = ops.decode_paged(q, kp, vp, block_tables=bt, length=ln,
                               buffers=2, mode="kernel", **scales)
    np.testing.assert_allclose(
        np.asarray(derived),
        np.asarray(ref.ref_paged_decode_attention(q, kp, vp, bt, length=ln,
                                                  **scales)),
        rtol=2e-5, atol=2e-5)


def test_paged_decode_bf16_pool_bit_identical_and_near_oracle():
    """bf16 queries over a bf16 pool, the serving dtypes, take the
    kernel's native-MXU body (no f32 copies of K and V, p split into two
    bf16 terms): still one body for both pipelines, so bit-identical at
    one page per block, and within two bf16 ulps of the f32 oracle at
    the derived block size (the output itself is bf16)."""
    rng = np.random.default_rng(21)
    b, hq, hkv, d, ps, n_pool = 4, 8, 2, 64, 16, 24
    lengths = np.asarray([3 * ps + 5, 2 * ps, ps - 1, 1])

    def bf16(shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    q = bf16((b, hq, d))
    kp, vp = bf16((n_pool + 1, hkv, ps, d)), bf16((n_pool + 1, hkv, ps, d))
    bt = _rand_block_tables(b, 4, n_pool, lengths, ps, seed=13)
    ln = jnp.asarray(lengths, jnp.int32)
    one = flash_paged_decode(q, kp, vp, bt, length=ln, buffers=1,
                             interpret=True)
    two = flash_paged_decode(q, kp, vp, bt, length=ln, buffers=2,
                             pages_per_block=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(one, np.float32),
                                  np.asarray(two, np.float32))
    derived = ops.decode_paged(q, kp, vp, block_tables=bt, length=ln,
                               mode="kernel")
    exp = ref.ref_paged_decode_attention(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), bt, length=ln)
    np.testing.assert_allclose(np.asarray(derived, np.float32),
                               np.asarray(exp), rtol=8e-3, atol=8e-3)


# Slot lengths against blocks of 8 pages of 256 tokens over a 20-page
# table (3 blocks, the last padded): ends mid-block, exactly at a block
# edge, one token (two blocks of sink entries only), the full table.
_BLOCK_LENGTHS = {"mid_block": 5 * 256 + 7, "block_edge": 8 * 256,
                  "one_token": 1, "full_table": 20 * 256}


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("pattern", sorted(_BLOCK_LENGTHS))
def test_paged_decode_blocks_match_ref(pattern, quantized):
    """The block kernel at its derived block size (8 pages at page 256)
    against the oracle, with the slot under test beside a full-table
    slot: the block that holds the length's end, the blocks wholly past
    it (no DMA, no compute) and the padded last block must all leave
    the output the oracle's."""
    rng = np.random.default_rng(17)
    b, hq, hkv, d, ps, max_pages = 2, 8, 2, 64, 256, 20
    lengths = np.asarray([_BLOCK_LENGTHS[pattern], max_pages * ps])
    n_pool = int(sum(-(-n // ps) for n in lengths))

    def pool():
        x = rng.normal(size=(n_pool + 1, hkv, ps, d)).astype(np.float32)
        x[n_pool] = 1e4                   # sink garbage, never a key
        return jnp.asarray(x)

    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    kp, vp = pool(), pool()
    scales = {}
    if quantized:
        (kp, ks), (vp, vs) = _quantize_pool(kp), _quantize_pool(vp)
        scales = {"k_scale": ks, "v_scale": vs}
    assert paged_pages_per_block(max_pages, hkv, ps, d, kp.dtype.itemsize,
                                 quantized) == 8
    bt = _rand_block_tables(b, max_pages, n_pool, lengths, ps, seed=2)
    ln = jnp.asarray(lengths, jnp.int32)
    out = ops.decode_paged(q, kp, vp, block_tables=bt, length=ln,
                           buffers=2, mode="kernel", **scales)
    exp = ref.ref_paged_decode_attention(q, kp, vp, bt, length=ln, **scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_pages_per_block_gauge():
    """The block size the kernel derived is kept in the registry at
    trace time: 32 pages of 64 tokens (2048 keys) here."""
    from repro import obs
    obs.reset()
    try:
        rng = np.random.default_rng(3)
        b, hkv, d, ps = 2, 2, 64, 64
        bt = _rand_block_tables(b, 40, 20, [70, 300], ps)
        pool = jnp.asarray(rng.normal(size=(21, hkv, ps, d)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(b, 8, d)), jnp.float32)
        ops.decode_paged(q, pool, pool, block_tables=bt,
                         length=jnp.asarray([70, 300], jnp.int32),
                         mode="kernel")
        gauge = obs.get_obs().registry.gauges[
            "ops.decode_paged.pages_per_block"]
        assert gauge.value == 32
    finally:
        obs.reset()


@pytest.mark.parametrize("buffers", [1, 2])
@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_paged_decode_lane_padded_rows(quantized, buffers):
    """Serving pools store head-dim-64 rows zero-padded to 128 lanes
    (``page_row_dim``) so the explicit-DMA gather compiles for the chip.
    A padded pool must decode to the unpadded pool's result — exactly
    on the oracle, to float tolerance on the kernel."""
    from repro.kernels.decode_attention import page_row_dim
    b, hq, hkv, d, ps, n_pool = 3, 8, 2, 64, 16, 24
    lengths = np.asarray([3 * ps + 5, ps, 1])
    q = randf((b, hq, d))
    kp, vp = randf((n_pool + 1, hkv, ps, d)), randf((n_pool + 1, hkv, ps, d))
    scales = {}
    if quantized:
        (kp, ks), (vp, vs) = _quantize_pool(kp), _quantize_pool(vp)
        scales = {"k_scale": ks, "v_scale": vs}
    rd = page_row_dim(d)
    assert rd == 128
    kw, vw = ops.to_page_rows(kp, rd), ops.to_page_rows(vp, rd)
    bt = _rand_block_tables(b, 4, n_pool, lengths, ps, seed=5)
    ln = jnp.asarray(lengths, jnp.int32)
    narrow = ops.decode_paged(q, kp, vp, block_tables=bt, length=ln,
                              buffers=buffers, mode="kernel", **scales)
    wide = ops.decode_paged(q, kw, vw, block_tables=bt, length=ln,
                            buffers=buffers, mode="kernel", **scales)
    assert wide.shape == (b, hq, d)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(narrow),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(ref.ref_paged_decode_attention(q, kw, vw, bt, length=ln,
                                                  **scales)),
        np.asarray(ref.ref_paged_decode_attention(q, kp, vp, bt, length=ln,
                                                  **scales)))


def test_paged_decode_scale_validation():
    """int8 pools without scale rows would be silently wrong (raw
    quantized integers attended as values); float pools with scale rows
    are a caller bug.  Both must raise."""
    b, hkv, ps, d, n_pool = 2, 2, 16, 64, 8
    q = randf((b, 8, d))
    bt = _rand_block_tables(b, 2, n_pool, [ps, 4], ps)
    ln = jnp.asarray([ps, 4], jnp.int32)
    fpool = randf((n_pool + 1, hkv, ps, d))
    qpool, scale = _quantize_pool(fpool)
    with pytest.raises(ValueError, match="k_scale"):
        ops.decode_paged(q, qpool, qpool, block_tables=bt, length=ln)
    with pytest.raises(ValueError, match="int8"):
        ops.decode_paged(q, fpool, fpool, block_tables=bt, length=ln,
                         k_scale=scale, v_scale=scale)
    with pytest.raises(ValueError, match="buffers"):
        ops.decode_paged(q, fpool, fpool, block_tables=bt, length=ln,
                         buffers=3, mode="kernel")


def test_paged_decode_equals_dense_on_gathered_cache():
    """Paged and dense decode are the same attention: gathering the
    pages into a contiguous cache and running the dense kernel must
    give the paged kernel's answer exactly (same masking semantics)."""
    b, hq, hkv, d, ps, n_pool = 2, 8, 2, 64, 16, 12
    lengths = np.asarray([2 * ps + 7, 5])
    q = randf((b, hq, d))
    k_pages = randf((n_pool + 1, hkv, ps, d))
    v_pages = randf((n_pool + 1, hkv, ps, d))
    bt = _rand_block_tables(b, 3, n_pool, lengths, ps, seed=3)
    ln = jnp.asarray(lengths, jnp.int32)
    paged = ops.decode_paged(q, k_pages, v_pages, block_tables=bt,
                             length=ln, mode="kernel")
    dense = ops.decode(q, ref.gather_pages(k_pages, bt),
                       ref.gather_pages(v_pages, bt), length=ln,
                       bk=ps, mode="kernel")
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_validation():
    q = randf((2, 8, 64))
    pool = randf((5, 2, 16, 64))
    bt = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="per-slot"):
        ops.decode_paged(q, pool, pool, block_tables=bt,
                         length=jnp.zeros((3,), jnp.int32))
    with pytest.raises(ValueError, match="block_tables"):
        ops.decode_paged(q, pool, pool,
                         block_tables=jnp.zeros((3, 2), jnp.int32),
                         length=jnp.zeros((2,), jnp.int32))


# ---------------------------------------------------------------------------
# Chunked (dry-run) attention vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal,q_offset", [(True, 0), (True, 13),
                                             (False, 0)])
def test_chunked_attention_matches_oracle(causal, q_offset):
    q = randf((2, 6, 200, 32))
    k = randf((2, 2, 200, 32))
    v = randf((2, 2, 200, 32))
    out = ref.chunked_attention(q, k, v, causal=causal, q_offset=q_offset,
                                q_chunk=64, kv_chunk=48)
    exp = ref.ref_attention(q, k, v, causal=causal, q_offset=q_offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_chunked_attention_grad_finite():
    q = randf((1, 2, 64, 16))
    k = randf((1, 2, 64, 16))
    v = randf((1, 2, 64, 16))

    def f(q):
        return ref.chunked_attention(q, k, v, q_chunk=32,
                                     kv_chunk=32).sum()
    g = jax.grad(f)(q)
    assert bool(jnp.all(jnp.isfinite(g)))


# ---------------------------------------------------------------------------
# WKV6 (RWKV recurrence)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bh,t,n,chunk", [
    (2, 64, 16, 32), (3, 100, 16, 32), (1, 17, 32, 8), (4, 128, 8, 128),
])
def test_wkv_kernel_vs_oracle(bh, t, n, chunk):
    b, h = bh, 2
    r = randf((b, h, t, n))
    k = randf((b, h, t, n)) * 0.3
    v = randf((b, h, t, n))
    w = jnp.asarray(RNG.uniform(0.6, 0.99, size=(b, h, t, n)), jnp.float32)
    u = randf((h, n)) * 0.2
    out = ops.wkv(r, k, v, w, u, chunk=chunk, mode="kernel")
    exp = ref.ref_wkv(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_wkv_matches_rwkv_module_scan():
    """The kernel path (cache=None) must equal the cached-scan path."""
    from repro.models.rwkv import RwkvConfig, init_rwkv_cache, init_time_mix, time_mix
    rng = jax.random.PRNGKey(7)
    cfg = RwkvConfig(head_size=16, lora_mix=8, lora_decay=8)
    p = init_time_mix(rng, 64, cfg)
    x = jax.random.normal(rng, (2, 24, 64), jnp.float32)
    out_kernel, _ = time_mix(p, x, cfg, cache=None)
    cache = init_rwkv_cache(2, 64, cfg, jnp.float32)
    out_scan, _ = time_mix(p, x, cfg, cache=cache)
    np.testing.assert_allclose(np.asarray(out_kernel),
                               np.asarray(out_scan), rtol=2e-4, atol=2e-4)
