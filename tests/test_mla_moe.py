"""Latent attention (MLA) with YaRN rope, its paged latent decode, and
the dropless expert layer that holds one chip's share of the experts —
small sizes, float32, on the CPU."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import latent_paged_decode
from repro.kernels.expert_gmm import expert_gmm
from repro.models import (decode_step, forward, init_cache,
                          init_paged_cache, init_params, prefill)
from repro.models import layers as L
from repro.models.config import BlockSpec, ModelConfig
from repro.models.mla import MLAConfig, softmax_scale
from repro.models.moe import MoEConfig, init_moe, moe_ffn_held, route

RNG = np.random.default_rng(16)

PUBLISHED_YARN = L.YarnScaling(factor=40, original_max_position=4096,
                               beta_fast=32, beta_slow=1, mscale=0.707,
                               mscale_all_dim=0.707)


def tiny_cfg(**kw):
    """One dense MLA layer, then two MLA layers with 4 of 16 experts
    held: DeepSeek-V2's block at CPU size."""
    base = dict(
        name="tiny_mla", n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
        d_head=16, d_ff=96, vocab_size=256,
        pattern=(BlockSpec("mla", "moe"),),
        mla=MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                      v_head_dim=16),
        moe=MoEConfig(num_experts=16, top_k=3, d_ff=32, n_shared=2,
                      shared_d_ff=64, held=(4, 4), norm_topk_prob=False),
        first_dense_layers=1, rope_scaling=PUBLISHED_YARN,
        param_dtype="float32", compute_dtype="float32",
        cache_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


# -- YaRN ------------------------------------------------------------------


def test_yarn_published_settings():
    assert L.yarn_correction_range(PUBLISHED_YARN, 64, 10000.0) == (10, 23)
    cfg = tiny_cfg(mla=MLAConfig(), rope_scaling=PUBLISHED_YARN)
    assert softmax_scale(cfg) == pytest.approx(0.114721, abs=5e-7)
    assert L.yarn_cos_scale(PUBLISHED_YARN) == 1.0
    inv = np.asarray(L.yarn_inv_freq(64, 10000.0, PUBLISHED_YARN))
    orig = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # Below the correction range the original frequencies, above it the
    # interpolated ones (divided by the factor), a ramp between.
    np.testing.assert_allclose(inv[:11], orig[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], orig[23:] / 40, rtol=1e-6)
    assert np.all((inv[11:23] < orig[11:23]) & (inv[11:23] > orig[11:23] / 40))


def test_yarn_mscale():
    assert L.yarn_mscale(40, 0.707) == pytest.approx(
        0.1 * 0.707 * math.log(40) + 1)
    assert L.yarn_mscale(1.0, 0.707) == 1.0


# -- MLA: expanded and absorbed --------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_cfg()
    return cfg, init_params(jax.random.key(3), cfg)


def test_absorbed_decode_equals_expanded(tiny):
    """Prefill (expanded, writing latent rows) then absorbed decode
    through the dense latent cache gives the full expanded forward's
    logits."""
    cfg, params = tiny
    toks = jnp.asarray(RNG.integers(1, 256, (2, 12)), jnp.int32)
    full, _, _ = forward(params, {"tokens": toks}, cfg)
    caches = init_cache(cfg, 2, 16)
    lg, caches = prefill(params, {"tokens": toks[:, :8]}, cfg, caches)
    np.testing.assert_allclose(lg, full[:, 7], atol=2e-4, rtol=2e-4)
    for t in range(8, 12):
        lg, caches = decode_step(params, toks[:, t], jnp.full((2,), t),
                                 cfg, caches)
        np.testing.assert_allclose(lg, full[:, t], atol=2e-4, rtol=2e-4)


def test_absorbed_paged_decode_equals_expanded(tiny):
    """The paged latent pool: rows appended by decode, read back by the
    absorbed paged decode, give the expanded forward's logits."""
    cfg, params = tiny
    ps, b, n = 4, 2, 11
    toks = jnp.asarray(RNG.integers(1, 256, (b, n)), jnp.int32)
    full, _, _ = forward(params, {"tokens": toks}, cfg)
    pool = init_paged_cache(cfg, 8, ps)
    assert pool[1]["mla"]["lat_pages"].shape == (2, 9, 1, ps, 128)
    tables = jnp.asarray([[5, 0, 7], [2, 6, 1]], jnp.int32)
    for t in range(n):
        lg, pool = decode_step(params, toks[:, t], jnp.full((b,), t), cfg,
                               pool, block_tables=tables)
        np.testing.assert_allclose(lg, full[:, t], atol=2e-4, rtol=2e-4)


def test_rope_pairs_adjacent_lanes(tiny):
    """Rope rotates the lane pairs (2i, 2i+1), as the published code's
    de-interleave before rotate-half: lanes 2i and 2i+1 of one position
    keep their norm together."""
    from repro.models.mla import _rope
    cfg, _ = tiny
    x = jnp.asarray(RNG.normal(size=(1, 3, 2, 8)), jnp.float32)
    out = _rope(x, jnp.arange(3)[None] + 5, cfg.mla, 10000.0,
                PUBLISHED_YARN)
    pair = (x[..., 0::2] ** 2 + x[..., 1::2] ** 2)
    got = out[..., :4] ** 2 + out[..., 4:] ** 2
    np.testing.assert_allclose(got, pair, rtol=1e-5)


# -- gama_latent_paged_decode -----------------------------------------------


@pytest.mark.parametrize("ppb", [1, 2, None])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_latent_paged_decode_kernel_matches_ref(ppb, dtype):
    b, h, w, ps, vd = 4, 16, 128, 8, 96
    n_pages, max_pages = 20, 5
    q = jnp.asarray(RNG.normal(size=(b, h, w)), dtype)
    pages = jnp.asarray(RNG.normal(size=(n_pages + 1, 1, ps, w)), dtype)
    tables = jnp.asarray(RNG.permutation(n_pages)[:b * max_pages]
                         .reshape(b, max_pages), jnp.int32)
    length = jnp.asarray([1, 8, 9, 37], jnp.int32)    # ragged page counts
    got = latent_paged_decode(q, pages, tables, length=length,
                              value_dim=vd, scale=0.2, pages_per_block=ppb,
                              interpret=True)
    want = ref.ref_latent_paged_decode(q, pages, tables, length=length,
                                       value_dim=vd, scale=0.2)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_latent_decode_op_pads_query_lanes():
    """ops.decode_latent_paged takes the latent width and pads it to
    the pool's row, whose pad lanes are zero."""
    b, h, d, ps = 2, 4, 40, 4
    pages = jnp.zeros((7, 1, ps, 128), jnp.float32).at[..., :d].set(
        jnp.asarray(RNG.normal(size=(7, 1, ps, d)), jnp.float32))
    q = jnp.asarray(RNG.normal(size=(b, h, d)), jnp.float32)
    tables = jnp.asarray([[3, 1], [0, 5]], jnp.int32)
    length = jnp.asarray([5, 3], jnp.int32)
    kw = dict(block_tables=tables, length=length, value_dim=32, scale=0.3)
    np.testing.assert_allclose(
        ops.decode_latent_paged(q, pages, mode="kernel", **kw),
        ops.decode_latent_paged(q, pages, mode="ref", **kw), atol=1e-5)


# -- gama_expert_gmm ---------------------------------------------------------


@pytest.mark.parametrize("sizes", [
    [5, 0, 130, 3], [0, 0, 0, 0], [256, 0, 0, 0], [1, 1, 1, 1],
    [0, 0, 0, 200], [100, 100, 100, 84],
])
def test_expert_gmm_kernel_matches_ref(sizes):
    m, k, n = 384, 64, 256
    x = jnp.asarray(RNG.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(4, k, n)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = expert_gmm(x, w, gs, interpret=True)
    want = ref.ref_expert_gmm(x, w, gs)
    rows = sum(sizes)
    np.testing.assert_allclose(got[:rows], want[:rows], rtol=1e-5, atol=1e-4)
    assert not np.any(np.asarray(want[rows:]))


# -- the dropless held-expert layer -----------------------------------------


def plain_layer(p, x, cfg):
    """Every expert's SwiGLU on every row, weighted by the routed gates
    of the held ones, plus the shared experts: the layer written out."""
    first, n = cfg.held
    probs = jax.nn.softmax(x @ p["router"]["w"], -1)
    top, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None],
                                     idx].set(top)[:, first:first + n]
    y = jnp.einsum("tef,efd->ted",
                   jax.nn.silu(jnp.einsum("td,edf->tef", x, p["gate"]))
                   * jnp.einsum("td,edf->tef", x, p["up"]), p["down"])
    return jnp.einsum("te,ted->td", gates, y) + L.mlp(p["shared"], x)


def held_cfg(**kw):
    base = dict(num_experts=16, top_k=4, d_ff=24, n_shared=2,
                shared_d_ff=48, held=(0, 16), norm_topk_prob=False)
    base.update(kw)
    return MoEConfig(**base)


def test_every_routed_pair_served_under_skewed_routing():
    """Every token routes to the same 4 experts, 3 of them held: a
    capacity layer would drop most pairs, this one serves them all."""
    cfg = held_cfg(held=(5, 4))
    p = init_moe(jax.random.key(0), 32, cfg)
    bias = jnp.zeros((32, 16)).at[:, jnp.asarray([4, 5, 6, 7])].set(5.0)
    p["router"]["w"] = p["router"]["w"] * 0.01 + bias
    x = jnp.abs(jnp.asarray(RNG.normal(size=(2, 40, 32)), jnp.float32)) + 1
    out, held = moe_ffn_held(p, x, cfg)
    # Every pair on experts 5-7 is served; expert 8 gets none.
    assert held.tolist() == [80 * 3, 3]
    np.testing.assert_allclose(out.reshape(80, 32),
                               plain_layer(p, x.reshape(80, 32), cfg),
                               rtol=1e-4, atol=1e-4)


def test_raw_softmax_gates_without_norm_topk():
    cfg = held_cfg()
    p = init_moe(jax.random.key(1), 32, cfg)
    x = jnp.asarray(RNG.normal(size=(10, 32)), jnp.float32)
    gates, idx = route(p, x, cfg)
    probs = jax.nn.softmax(x @ p["router"]["w"], -1)
    np.testing.assert_allclose(
        gates, jnp.take_along_axis(probs, idx, 1), rtol=1e-6)
    assert np.all(np.asarray(gates.sum(-1)) < 1.0)
    normed, _ = route(p, x, dataclasses.replace(cfg, norm_topk_prob=True,
                                                routed_scaling_factor=2.0))
    np.testing.assert_allclose(normed.sum(-1), 2.0, rtol=1e-5)


def test_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of 2 experts each, the shared experts counted
    once, give the uncut layer over all 16."""
    full = held_cfg()
    p = init_moe(jax.random.key(2), 32, full)
    x = jnp.asarray(RNG.normal(size=(1, 30, 32)), jnp.float32)
    shared = L.mlp(p["shared"], x.reshape(30, 32))
    total = -7 * shared
    for chip in range(8):
        share = {**p, **{n: p[n][2 * chip:2 * chip + 2]
                         for n in ("gate", "up", "down")}}
        out, _ = moe_ffn_held(share, x,
                              dataclasses.replace(full, held=(2 * chip, 2)))
        total = total + out.reshape(30, 32)
    np.testing.assert_allclose(total, plain_layer(p, x.reshape(30, 32), full),
                               rtol=1e-4, atol=1e-4)
    whole, _ = moe_ffn_held(p, x, full)
    np.testing.assert_allclose(total, whole.reshape(30, 32), rtol=1e-4,
                               atol=1e-4)


def test_held_layer_kernel_path_matches_ref():
    cfg = held_cfg(held=(3, 6))
    p = init_moe(jax.random.key(4), 32, dataclasses.replace(cfg))
    x = jnp.asarray(RNG.normal(size=(3, 7, 32)), jnp.float32)
    want, held_ref = moe_ffn_held(p, x, cfg)
    with ops.force_mode("kernel"):
        got, held = jax.jit(lambda p_, x_: moe_ffn_held(p_, x_, cfg))(p, x)
    assert held.tolist() == held_ref.tolist()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_leading_dense_layer_stack(tiny):
    cfg, params = tiny
    assert cfg.n_groups == 2 and len(params["lead"]) == 1
    assert params["lead"][0]["mlp"]["gate"]["w"].shape == (1, 64, 96)
    assert params["blocks"][0]["moe"]["gate"].shape == (2, 4, 64, 32)
    assert len(init_cache(cfg, 1, 8)) == 2
    _, _, _, held = forward(params, {"tokens": jnp.ones((1, 5), jnp.int32)},
                            cfg, held_counts=True)
    # 5 rows x top-3 x 2 expert layers routed; the held share of them,
    # on at most the 4 held experts of each of the 2 layers.
    pairs, reached = held.tolist()
    assert 0 <= pairs <= 30 and reached <= min(pairs, 8)
