"""Plain reference of a dense decoder with grouped-query attention, as
LlamaForCausalLM and Phi3ForCausalLM define it: RMSNorm before
attention and before the SwiGLU MLP, rotary embeddings on the rotate-half
convention, causal softmax attention with each KV head shared by
heads / kv_heads query heads, a final RMSNorm and the lm head (the
embedding's transpose where the embeddings are tied).

float32 throughout with matmuls at ``highest`` precision; the weights
are upcast one layer at a time.  With ``int8`` every matrix is first
rounded to int8 with one scale per output channel, a precision below the
bfloat16 the checkpoints are published in: the output check's control.
Nothing of the program under test is imported or taken.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, pos, theta):
    """x: (T, H, D); rotate-half convention."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[:, None].astype(F32) * inv                 # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


LAYER_KEYS = ("wq", "wk", "wv", "wo", "gate", "up", "down", "ln1", "ln2")


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
    """The attention branch of a layer: RMSNorm, then causal GQA over
    the rows of ``x`` with the float32 weights ``w``."""
    t = x.shape[0]
    pos = jnp.arange(t)
    mask = pos[:, None] >= pos[None, :]
    group = s.heads // s.kv_heads
    mm = functools.partial(jnp.matmul, precision="highest")
    h = _rmsnorm(x, w["ln1"], s.eps)
    q = mm(h, w["wq"]).reshape(t, s.heads, s.d_head)
    k = mm(h, w["wk"]).reshape(t, s.kv_heads, s.d_head)
    v = mm(h, w["wv"]).reshape(t, s.kv_heads, s.d_head)
    q, k = _rope(q, pos, s.rope_theta), _rope(k, pos, s.rope_theta)
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    att = jnp.einsum("thd,shd->hts", q, k, precision="highest")
    att = jnp.where(mask[None], att * s.d_head ** -0.5, -jnp.inf)
    att = jax.nn.softmax(att, axis=-1)
    o = jnp.einsum("hts,shd->thd", att, v, precision="highest")
    return mm(o.reshape(t, s.heads * s.d_head), w["wo"])


@functools.partial(jax.jit, static_argnames=("s", "int8"))
def _layer(x, w, s, int8=False):
    mm = functools.partial(jnp.matmul, precision="highest")
    w = jax.tree.map(lambda a: _upcast(a, int8), w)
    x = x + _attention(x, w, s)
    h = _rmsnorm(x, w["ln2"], s.eps)
    return x + mm(jax.nn.silu(mm(h, w["gate"])) * mm(h, w["up"]), w["down"])


@functools.partial(jax.jit, static_argnames=("s", "int8"))
def _head(x, final_norm, head, s, int8=False):
    x = _rmsnorm(x, final_norm, s.eps)
    return jnp.matmul(x, _upcast(head, int8), precision="highest")


@functools.partial(jax.jit, static_argnames=("int8",))
def _embed(table, tokens, int8=False):
    """Rows of the embedding table (vocab, d_model), rounded as the
    matrix it is where ``int8`` (one scale per model channel)."""
    return _upcast(table, int8)[tokens]


def logits(weights: dict, tokens: jax.Array, shape,
           int8: bool = False) -> jax.Array:
    """tokens: (T,) int32 -> (T, vocab) float32 logits, causal, so
    positions past a sequence's end never change the ones before it.
    One layer at a time, so that only that layer's weights are ever
    held in float32."""
    x = _embed(weights["embed"], tokens, int8)
    for i in range(shape.layers):
        x = _layer(x, {k: weights[k][i] for k in LAYER_KEYS}, shape, int8)
    head = weights["embed"].T if "head" not in weights else weights["head"]
    return _head(x, weights["final_norm"], head, shape, int8)
