"""Plain reference of the test architecture ``moe_gqa``: each layer is
``bench/reference/dense_gqa.py``'s attention branch, then RMSNorm and a
routed SwiGLU expert FFN as MixtralForCausalLM defines it: router
logits over every expert, their softmax, the ``top_k`` largest, those
gates renormalised to sum to 1 as the program's ``moe_ffn`` does, and
the gate-weighted sum of those experts' SwiGLU outputs.

Where it departs from ``moe_ffn``:

- Every routed row is computed: there is no capacity, so none is dropped.
- Routing is over all rows at once: there are no dispatch groups.
- The router runs in float32 at ``highest`` precision, as every matmul.
- Every expert runs on every row, and a gate matrix that is zero off the
  ``top_k`` sums their outputs; ``moe_ffn`` scatters each row into its
  experts' buffers and gathers it back.
- There is no load-balancing loss, which serving never reads.

float32 throughout, and ``int8`` rounds every matrix as ``dense_gqa``
does, the experts' per expert and output channel.  Nothing of the
program under test is imported or taken.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import dense_gqa as dense

LAYER_KEYS = ("wq", "wk", "wv", "wo", "router", "gate", "up", "down",
              "ln1", "ln2")


def _experts(h, w, s):
    """h: (T, d) -> (T, d), the routed experts' weighted sum."""
    hi = "highest"
    probs = jax.nn.softmax(jnp.matmul(h, w["router"], precision=hi), -1)
    top, idx = jax.lax.top_k(probs, s.top_k)
    top = top / jnp.maximum(jnp.sum(top, axis=-1, keepdims=True), 1e-9)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)           # (T, E)
    g = jnp.einsum("td,edf->tef", h, w["gate"], precision=hi)
    u = jnp.einsum("td,edf->tef", h, w["up"], precision=hi)
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, w["down"],
                   precision=hi)
    return jnp.einsum("te,ted->td", gates, y, precision=hi)


@functools.partial(jax.jit, static_argnames=("s", "int8"))
def _layer(x, w, s, int8=False):
    w = jax.tree.map(lambda a: dense._upcast(a, int8), w)
    x = x + dense._attention(x, w, s)
    return x + _experts(dense._rmsnorm(x, w["ln2"], s.eps), w, s)


def logits(weights: dict, tokens: jax.Array, shape,
           int8: bool = False) -> jax.Array:
    """tokens: (T,) int32 -> (T, vocab) float32 logits, causal; one
    layer at a time, as ``dense_gqa.logits``."""
    x = dense._embed(weights["embed"], tokens, int8)
    for i in range(shape.layers):
        x = _layer(x, {k: weights[k][i] for k in LAYER_KEYS}, shape, int8)
    head = weights["embed"].T if "head" not in weights else weights["head"]
    return dense._head(x, weights["final_norm"], head, shape, int8)
