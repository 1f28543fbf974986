"""Plain reference of DeepSeek-V2 (DeepseekV2ForCausalLM) at one chip's
share of its routed experts.

Per layer: RMSNorm, then multi-head latent attention in its expanded
form, as the published modelling code computes it: ``q = h W_q`` split
per head into ``q_nope`` and ``q_pe``; ``[c, k_pe] = h W_kva``;
``[k_nope, v] = RMSNorm(c) W_kvb`` per head; rope on ``q_pe`` and on
``k_pe`` (one for all heads) after de-interleaving their lanes, with
YaRN's frequencies and cos/sin scale; causal softmax of
``q . k`` (``k = [k_nope, k_pe]``) times ``qk_dim^-0.5`` and YaRN's
``mscale(factor, mscale_all_dim)^2``; the heads' outputs through
``W_o``.  Then RMSNorm and the FFN: a SwiGLU of ``intermediate_size``
in the first ``first_k_dense_replace`` layers; in the others, the
softmax over every router expert, its greedy top-k (renormalised only
where ``norm_topk_prob``, times ``routed_scaling_factor``), and the
gate-weighted SwiGLU outputs of the experts this chip holds, plus the
shared experts' SwiGLU.  A final RMSNorm and the lm head.

Where it departs from the published forward: the routed sum holds only
the held experts' terms, as the program's does (the others live on the
deployment's other chips); and every held expert is computed on every
row, its gate zero where it was not chosen.

float32 throughout under ``highest`` matmul precision, one layer at a
time, so that only that layer's weights are ever held in float32.  With
``int8`` every matrix is first rounded to int8 with one scale per output
channel (per expert too), a precision below the bfloat16 the checkpoint
is published in: the output check's control.  Nothing of the program
under test is imported or taken.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = "highest"

ATTENTION_KEYS = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo", "ln1", "ln2")
DENSE_KEYS = ATTENTION_KEYS + ("gate", "up", "down")
EXPERT_KEYS = ATTENTION_KEYS + ("router", "gate", "up", "down",
                                "shared_gate", "shared_up", "shared_down")


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(s):
    """(inv_freq (rope/2,), cos/sin scale, softmax scale) of YaRN, as
    DeepseekV2YarnRotaryEmbedding and DeepseekV2Attention set them."""
    factor, orig, fast, slow, ms, ms_all = s.yarn
    dim, base = s.qk_rope, s.rope_theta

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    if low == high:
        high += 0.001
    pos = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / pos, 1.0 / (factor * pos)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    inv_freq = inter * (1.0 - mask) + extra * mask
    cos_scale = _mscale(factor, ms) / _mscale(factor, ms_all)
    softmax = s.qk_dim ** -0.5
    if ms_all:
        softmax *= _mscale(factor, ms_all) ** 2
    return inv_freq.astype(np.float32), cos_scale, softmax


def _rope(x, pos, s):
    """x: (T, H, rope): de-interleave the lanes, then rotate-half."""
    inv_freq, cos_scale, _ = yarn(s)
    t, h, d = x.shape
    x = x.reshape(t, h, d // 2, 2).swapaxes(-1, -2).reshape(t, h, d)
    freqs = pos[:, None].astype(F32) * jnp.asarray(inv_freq)
    emb = jnp.concatenate([freqs, freqs], -1)
    cos = (jnp.cos(emb) * cos_scale)[:, None]
    sin = (jnp.sin(emb) * cos_scale)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _upcast(a, int8: bool):
    """A weight in float32; a matrix (in, out) rounded to int8 first,
    one scale per output channel, where ``int8``."""
    a = a.astype(F32)
    if not int8 or a.ndim < 2:
        return a
    scale = jnp.max(jnp.abs(a), axis=-2, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def _attention(x, w, s):
    t = x.shape[0]
    pos = jnp.arange(t)
    h, nope, r = s.heads, s.qk_nope, s.kv_lora_rank
    hx = _rmsnorm(x, w["ln1"], s.eps)
    q = _mm(hx, w["wq"]).reshape(t, h, s.qk_dim)
    ckv = _mm(hx, w["wkv_a"])
    kv = _mm(_rmsnorm(ckv[:, :r], w["kv_norm"], s.eps),
             w["wkv_b"]).reshape(t, h, nope + s.v_head)
    q_pe = _rope(q[..., nope:], pos, s)
    k_pe = _rope(ckv[:, None, r:], pos, s)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (t, h, s.qk_rope))], -1)
    att = jnp.einsum("thd,shd->hts", q, k, precision=HI) * yarn(s)[2]
    att = jnp.where((pos[:, None] >= pos[None, :])[None], att, -jnp.inf)
    att = jax.nn.softmax(att, axis=-1)
    o = jnp.einsum("hts,shd->thd", att, kv[..., nope:], precision=HI)
    return _mm(o.reshape(t, h * s.v_head), w["wo"])


def _swiglu(h, gate, up, down):
    return _mm(jax.nn.silu(_mm(h, gate)) * _mm(h, up), down)


def _experts(h, w, s):
    """The held experts' part of the routed sum, plus the shared
    experts."""
    probs = jax.nn.softmax(_mm(h, w["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, s.top_k)
    if s.norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * s.routed_scaling
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)
    held = gates[:, s.first_held:s.first_held + s.held_experts]   # (T, E)
    g = jnp.einsum("td,edf->tef", h, w["gate"], precision=HI)
    u = jnp.einsum("td,edf->tef", h, w["up"], precision=HI)
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, w["down"],
                   precision=HI)
    routed = jnp.einsum("te,ted->td", held, y, precision=HI)
    return routed + _swiglu(h, w["shared_gate"], w["shared_up"],
                            w["shared_down"])


@functools.partial(jax.jit, static_argnames=("s", "int8"))
def _dense_layer(x, w, s, int8=False):
    w = jax.tree.map(lambda a: _upcast(a, int8), w)
    x = x + _attention(x, w, s)
    return x + _swiglu(_rmsnorm(x, w["ln2"], s.eps), w["gate"], w["up"],
                       w["down"])


@functools.partial(jax.jit, static_argnames=("s", "int8"))
def _expert_layer(x, w, s, int8=False):
    w = jax.tree.map(lambda a: _upcast(a, int8), w)
    x = x + _attention(x, w, s)
    return x + _experts(_rmsnorm(x, w["ln2"], s.eps), w, s)


@functools.partial(jax.jit, static_argnames=("s", "int8"))
def _head(x, final_norm, head, s, int8=False):
    x = _rmsnorm(x, final_norm.astype(F32), s.eps)
    return _mm(x, _upcast(head, int8))


@functools.partial(jax.jit, static_argnames=("int8",))
def _embed(table, tokens, int8=False):
    return _upcast(table, int8)[tokens]


def logits(weights: dict, tokens: jax.Array, shape,
           int8: bool = False) -> jax.Array:
    """tokens: (T,) int32 -> (T, vocab) float32 logits, causal; one
    layer at a time."""
    with jax.default_matmul_precision(HI):
        x = _embed(weights["embed"], tokens, int8)
        for i in range(shape.dense_layers):
            x = _dense_layer(
                x, {k: weights["lead_" + k][i] for k in DENSE_KEYS},
                shape, int8)
        for i in range(shape.layers - shape.dense_layers):
            x = _expert_layer(x, {k: weights[k][i] for k in EXPERT_KEYS},
                              shape, int8)
        head = weights["embed"].T if "head" not in weights \
            else weights["head"]
        return _head(x, weights["final_norm"], head, shape, int8)
