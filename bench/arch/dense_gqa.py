"""Dense decoder with grouped-query attention, as LlamaForCausalLM and
Phi3ForCausalLM define it: per layer, RMSNorm, attention with each KV
head shared by heads / kv_heads query heads, RMSNorm and a SwiGLU MLP.

The architecture module of ``bench/reference/dense_gqa.py``; the
interface it fills is set out in :mod:`bench.lib.model`.  Its cost
terms count dense work: every token row goes through every matrix of
every layer, and a decode step reads each live key's K and V at
``d_head`` lanes for each KV head.  An architecture with other work
brings its own terms in its own module: a mixture of experts counts the
rows its held experts serve in ``forward_gemms``, and latent attention
counts the latent bytes a key keeps in ``paged_decode_cost``.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

from bench.lib.costs import BF16


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


def load_shape(c: dict, name: str) -> Shape:
    heads = c["num_attention_heads"]
    return Shape(
        name=name, layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        heads=heads, kv_heads=c["num_key_value_heads"],
        d_head=c.get("head_dim") or c["hidden_size"] // heads,
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        tied=bool(c["tie_word_embeddings"]), rope_theta=c["rope_theta"],
        eps=c["rms_norm_eps"], init_std=c["initializer_range"],
        dtype=c["torch_dtype"], reference=c["reference"])


def weight_shapes(s: Shape) -> Tuple[dict, frozenset]:
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
    return shapes, frozenset({"ln1", "ln2", "final_norm"})


# -- the program's form of the model -----------------------------------


def program_config(s: Shape):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=s.name, n_layers=s.layers, d_model=s.d_model, n_heads=s.heads,
        n_kv_heads=s.kv_heads, d_head=s.d_head, d_ff=s.d_ff,
        vocab_size=s.vocab, tie_embeddings=s.tied, rope_theta=s.rope_theta,
        norm_eps=s.eps, param_dtype=s.dtype, compute_dtype=s.dtype,
        cache_dtype=s.dtype)


def attention_params(w: dict) -> dict:
    return {n: {"w": w[n]} for n in ("wq", "wk", "wv", "wo")}


def program_params(w: dict) -> dict:
    """The canonical weights in the program's tree (one dense block
    pattern, layers stacked on the leading axis)."""
    block = {
        "ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
        "attn": attention_params(w),
        "mlp": {n: {"w": w[n]} for n in ("gate", "up", "down")},
    }
    return outer_params(w, block)


def outer_params(w: dict, block: dict) -> dict:
    """The embedding, final norm and head around one block pattern."""
    p = {"embed": {"table": w["embed"]},
         "final_norm": {"scale": w["final_norm"]}, "blocks": [block]}
    if "head" in w:
        p["head"] = {"w": w["head"]}
    return p


# -- cost terms ----------------------------------------------------------


def attention_matmul_params(s: Shape) -> int:
    return s.d_model * (s.heads + 2 * s.kv_heads) * s.d_head \
        + s.heads * s.d_head * s.d_model


def matmul_params(s: Shape) -> int:
    """Parameters of every matrix a token's forward pass multiplies by,
    the lm head included."""
    return s.layers * (attention_matmul_params(s) + 3 * s.d_model * s.d_ff) \
        + s.d_model * s.vocab


def attention_gemms(s: Shape, rows: int) -> List[Tuple[int, int, int]]:
    """(M, K, N) of one layer's Q, K, V and output projections."""
    d, hd, kvd = s.d_model, s.heads * s.d_head, s.kv_heads * s.d_head
    return [(rows, d, hd), (rows, d, kvd), (rows, d, kvd), (rows, hd, d)]


def forward_gemms(s: Shape, rows: int, head_rows: int
                  ) -> List[Tuple[int, int, int]]:
    """(M, K, N) of every GEMM of one forward over ``rows`` token rows,
    ``head_rows`` of which need logits."""
    d = s.d_model
    layer = attention_gemms(s, rows) + [
        (rows, d, s.d_ff), (rows, d, s.d_ff), (rows, s.d_ff, d)]
    out = layer * s.layers
    if head_rows:
        out.append((head_rows, d, s.vocab))
    return out


def attention_flops(s: Shape, keys: int) -> float:
    """QKᵀ and PV of queries attending over ``keys`` keys in total
    (a query at position ``p`` attends over ``p + 1``): 4·heads·d_head
    operations per key."""
    return 4.0 * s.layers * s.heads * s.d_head * keys


def paged_decode_cost(s: Shape, contexts: List[int],
                      nbytes: int = BF16) -> Tuple[float, float]:
    """(operations, bytes) of one decode step's paged attention over
    every layer: each live slot reads its context's K and V rows at
    ``d_head`` lanes, and its query and output."""
    keys = sum(contexts)
    flops = attention_flops(s, keys)
    kv = s.layers * 2 * s.kv_heads * s.d_head * keys * nbytes
    qo = s.layers * 2 * len(contexts) * s.heads * s.d_head * nbytes
    return flops, float(kv + qo)
