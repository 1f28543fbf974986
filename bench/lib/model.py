"""A configuration file's sizes, and its weights drawn from a seed.

The weights are canonical: one dict of stacked arrays that the plain
reference reads under its own names and that :mod:`bench.lib.program`
hands to the program in its layout.  They are drawn on the device in
one jitted call, in the dtype the checkpoint is published in.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Shape:
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    tied: bool
    rope_theta: float
    eps: float
    init_std: float
    dtype: str
    reference: str

    @property
    def matmul_params(self) -> int:
        """Parameters of every matrix a token's forward pass multiplies
        by, the lm head included."""
        attn = self.d_model * (self.heads + 2 * self.kv_heads) * self.d_head \
            + self.heads * self.d_head * self.d_model
        return self.layers * (attn + 3 * self.d_model * self.d_ff) \
            + self.d_model * self.vocab


def load_shape(path: Path, name: str) -> Shape:
    c = json.loads(Path(path).read_text())
    heads = c["num_attention_heads"]
    return Shape(
        name=name, layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        heads=heads, kv_heads=c["num_key_value_heads"],
        d_head=c.get("head_dim") or c["hidden_size"] // heads,
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        tied=bool(c["tie_word_embeddings"]), rope_theta=c["rope_theta"],
        eps=c["rms_norm_eps"], init_std=c["initializer_range"],
        dtype=c["torch_dtype"], reference=c["reference"])


def _key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative whole-number seed."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def weight_shapes(s: Shape) -> dict:
    L, d, f = s.layers, s.d_model, s.d_ff
    shapes = {
        "embed": (s.vocab, d),
        "wq": (L, d, s.heads * s.d_head),
        "wk": (L, d, s.kv_heads * s.d_head),
        "wv": (L, d, s.kv_heads * s.d_head),
        "wo": (L, s.heads * s.d_head, d),
        "gate": (L, d, f), "up": (L, d, f), "down": (L, f, d),
        "ln1": (L, d), "ln2": (L, d), "final_norm": (d,),
    }
    if not s.tied:
        shapes["head"] = (d, s.vocab)
    return shapes


def make_weights(s: Shape, seed: int) -> dict:
    """Every weight, drawn from ``seed`` on the default device in one
    jitted call: matrices N(0, init_std), norm scales 1 + 0.1 N(0, 1)."""
    shapes = weight_shapes(s)
    dtype = jnp.dtype(s.dtype)

    def draw(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            scale = z * 0.1 + 1.0 if len(shape) == 1 or name.startswith(
                ("ln", "final")) else z * s.init_std
            out[name] = scale.astype(dtype)
        return out

    return jax.jit(draw)(_key(seed))
