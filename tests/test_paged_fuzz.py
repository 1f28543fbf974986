"""Differential fuzz of ``flash_paged_decode`` vs its jnp oracle.

Hypothesis-driven (or stub-sampled — see ``_hypothesis_stub``) sweeps
over the paged-decode kernel's geometry: random page sizes, per-slot
length patterns that force the known edge shapes (a single page, an
exact page boundary, a tail page holding one token, a one-token slot),
GQA ratios on both sides of the sublane-padding threshold, and f32 vs
int8 (per-row-scale) pools.  Every drawn case checks BOTH properties
the tentpole relies on:

* **oracle agreement** — the kernel (single-buffer BlockSpec gather)
  matches ``ref_paged_decode_attention`` to float tolerance;
* **buffer bit-identity** — the explicit-DMA double-buffered pipeline
  (``buffers=2``) at one page per block is BIT-identical to the
  single-buffer path.  The two kernels share one arithmetic body; any
  drift means the pipeline reordered or re-rounded the online softmax.
  At its derived block size, and at a drawn one, ``buffers=2`` sums
  larger blocks and is held to the oracle's tolerance.

The pool's null sink page is always filled with large garbage, so every
example also proves sink rows are unreachable (table entries past a
slot's allocation are skipped by the length guard; tail-page rows past
the length are masked before the online-softmax max).

Marked ``kernelfuzz`` — excluded from tier-1.  Example count is bounded
by ``REPRO_KERNELFUZZ_EXAMPLES`` (CI: small on PRs, an extended sweep
on the schedule).
"""

import os

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
from repro.serving.quant import quantize_kv_pages

pytestmark = pytest.mark.kernelfuzz

N_EXAMPLES = int(os.environ.get("REPRO_KERNELFUZZ_EXAMPLES", "25"))

# Named per-slot length patterns: the geometry edges a uniform draw
# would rarely hit get their own generators.
_PATTERNS = ("rand", "one_token", "single_page", "exact_boundary",
             "tail_of_one", "full_table")


def _pattern_length(pattern, rng, ps, max_pages):
    cap = max_pages * ps
    if pattern == "one_token":
        return 1
    if pattern == "single_page":
        return int(rng.integers(1, ps + 1))
    if pattern == "exact_boundary":
        return ps * int(rng.integers(1, max_pages + 1))
    if pattern == "tail_of_one":                  # k full pages + 1 token
        return ps * int(rng.integers(0, max_pages)) + 1
    if pattern == "full_table":
        return cap
    return int(rng.integers(1, cap + 1))


@st.composite
def paged_cases(draw):
    """One fuzz case: geometry + per-slot length patterns + pool dtype."""
    ps = draw(st.sampled_from([8, 16, 32]))
    hkv = draw(st.sampled_from([1, 2]))
    group = draw(st.sampled_from([1, 2, 4, 8]))   # both sides of gp=8 pad
    d = draw(st.sampled_from([16, 32]))
    max_pages = draw(st.integers(min_value=1, max_value=4))
    b = draw(st.integers(min_value=1, max_value=4))
    patterns = [draw(st.sampled_from(_PATTERNS)) for _ in range(b)]
    quantized = draw(st.booleans())
    seed = draw(st.integers(min_value=0, max_value=10 ** 6))
    ppb = draw(st.integers(min_value=1, max_value=max_pages))
    return (ps, hkv, group, d, max_pages, b, tuple(patterns), quantized,
            seed, ppb)


def _build_case(ps, hkv, group, d, max_pages, b, patterns, quantized, seed):
    rng = np.random.default_rng(seed)
    lengths = np.asarray([_pattern_length(p, rng, ps, max_pages)
                          for p in patterns])
    n_pool = int(sum(-(-int(ln) // ps) for ln in lengths)) + 1
    q = jnp.asarray(rng.normal(size=(b, hkv * group, d)), jnp.float32)
    kf = rng.normal(size=(n_pool + 1, hkv, ps, d)).astype(np.float32)
    vf = rng.normal(size=(n_pool + 1, hkv, ps, d)).astype(np.float32)
    # Null sink page = large garbage: reachable only through a masking
    # bug, in which case the diff vs the oracle explodes loudly.
    kf[n_pool] = 1e4
    vf[n_pool] = -1e4
    # Disjoint random page lists per slot, null-sink tail.
    perm = list(rng.permutation(n_pool))
    bt = np.full((b, max_pages), n_pool, np.int32)
    for i, ln in enumerate(lengths):
        n = -(-int(ln) // ps)
        bt[i, :n], perm = perm[:n], perm[n:]
    scales = {}
    if quantized:
        k_pages, ks = quantize_kv_pages(jnp.asarray(kf))
        v_pages, vs = quantize_kv_pages(jnp.asarray(vf))
        scales = {"k_scale": ks, "v_scale": vs}
    else:
        k_pages, v_pages = jnp.asarray(kf), jnp.asarray(vf)
    return (q, k_pages, v_pages, jnp.asarray(bt),
            jnp.asarray(lengths, jnp.int32), scales)


@given(paged_cases())
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_fuzz_paged_decode_oracle_and_buffer_identity(case):
    ps, hkv, group, d, max_pages, b, patterns, quantized, seed, ppb = case
    q, kp, vp, bt, ln, scales = _build_case(
        ps, hkv, group, d, max_pages, b, patterns, quantized, seed)
    what = (f"ps={ps} hkv={hkv} group={group} d={d} patterns={patterns} "
            f"quantized={quantized} seed={seed}")
    exp = np.asarray(ref.ref_paged_decode_attention(q, kp, vp, bt,
                                                    length=ln, **scales))

    def check(out, label):
        np.testing.assert_allclose(
            np.asarray(out), exp, rtol=2e-5, atol=2e-5,
            err_msg=f"{label} vs oracle diverged: {what}")

    one = flash_paged_decode(q, kp, vp, bt, length=ln, buffers=1,
                             interpret=True, **scales)
    check(one, "buffers=1")
    two = flash_paged_decode(q, kp, vp, bt, length=ln, buffers=2,
                             pages_per_block=1, interpret=True, **scales)
    np.testing.assert_array_equal(
        np.asarray(one), np.asarray(two),
        err_msg=f"double-buffer drift: {what}")
    check(ops.decode_paged(q, kp, vp, block_tables=bt, length=ln,
                           buffers=2, mode="kernel", **scales),
          "buffers=2 at its derived block size")
    derived = paged_pages_per_block(max_pages, hkv, ps, d,
                                    kp.dtype.itemsize, quantized)
    if ppb not in (1, derived):
        check(flash_paged_decode(q, kp, vp, bt, length=ln, buffers=2,
                                 pages_per_block=ppb, interpret=True,
                                 **scales),
              f"{ppb} pages per block")


@pytest.mark.parametrize("buffers", [1, 2])
def test_null_sink_garbage_is_unreachable(buffers):
    """Swapping the sink page between zeros and huge garbage must not
    change a single output bit: unallocated table entries are skipped
    by the page guard (buffers=2: never copied, at its derived block of
    all 3 table pages), and tail rows past the length are masked before
    the softmax max."""
    rng = np.random.default_rng(7)
    b, hkv, group, d, ps, n_pool = 3, 2, 4, 32, 16, 9
    lengths = jnp.asarray([2 * ps + 3, ps, 1], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, hkv * group, d)), jnp.float32)
    kf = rng.normal(size=(n_pool + 1, hkv, ps, d)).astype(np.float32)
    vf = rng.normal(size=(n_pool + 1, hkv, ps, d)).astype(np.float32)
    perm = list(rng.permutation(n_pool))
    bt = np.full((b, 3), n_pool, np.int32)
    for i, ln in enumerate([2 * ps + 3, ps, 1]):
        n = -(-ln // ps)
        bt[i, :n], perm = perm[:n], perm[n:]
    bt = jnp.asarray(bt)
    outs = []
    for sink in (0.0, 1e4):
        kf[n_pool] = sink
        vf[n_pool] = -sink
        outs.append(ops.decode_paged(
            jnp.asarray(q), jnp.asarray(kf), jnp.asarray(vf),
            block_tables=bt, length=lengths, buffers=buffers,
            mode="kernel"))
    np.testing.assert_array_equal(np.asarray(outs[0]),
                                  np.asarray(outs[1]))


def test_null_sink_garbage_is_unreachable_across_blocks():
    """The same bit-exactness over a table of several blocks (8 pages of
    256 tokens each, 10 pages): a slot that ends mid-block, one whose
    later block holds only sink entries, and a full table."""
    rng = np.random.default_rng(8)
    b, hkv, group, d, ps, max_pages = 3, 2, 4, 32, 256, 10
    lengths = [5 * ps + 9, 1, max_pages * ps]
    n_pool = sum(-(-n // ps) for n in lengths)
    q = jnp.asarray(rng.normal(size=(b, hkv * group, d)), jnp.float32)
    kf = rng.normal(size=(n_pool + 1, hkv, ps, d)).astype(np.float32)
    vf = rng.normal(size=(n_pool + 1, hkv, ps, d)).astype(np.float32)
    perm = list(rng.permutation(n_pool))
    bt = np.full((b, max_pages), n_pool, np.int32)
    for i, ln in enumerate(lengths):
        n = -(-ln // ps)
        bt[i, :n], perm = perm[:n], perm[n:]
    outs = []
    for sink in (0.0, 1e4):
        kf[n_pool] = sink
        vf[n_pool] = -sink
        outs.append(ops.decode_paged(
            q, jnp.asarray(kf), jnp.asarray(vf), block_tables=jnp.asarray(bt),
            length=jnp.asarray(lengths, jnp.int32), mode="kernel"))
    np.testing.assert_array_equal(np.asarray(outs[0]),
                                  np.asarray(outs[1]))
