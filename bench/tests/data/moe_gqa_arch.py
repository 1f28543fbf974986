"""A test architecture: dense GQA attention and a top-k routed SwiGLU
expert FFN, as MixtralForCausalLM defines its block, with every expert
held on the chip.  Its plain reference is ``moe_gqa_reference.py``.

It is built from ``bench/arch/dense_gqa.py``'s attention terms and adds
only what the experts change: the router and the experts' weights, the
program's ``BlockSpec("attn", "moe")`` block with a ``MoEConfig``, and
the expert rows in the cost terms.  The program's expert layer routes
with a capacity per expert; ``moe_min_capacity`` in the configuration
sets it at least as large as the rows of any call, so that it drops no
token and serves the same function as the reference.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

from bench.arch import dense_gqa as dense


@dataclasses.dataclass(frozen=True)
class Shape(dense.Shape):
    experts: int
    top_k: int
    min_capacity: int


def load_shape(c: dict, name: str) -> Shape:
    return Shape(**dataclasses.asdict(dense.load_shape(c, name)),
                 experts=c["num_local_experts"],
                 top_k=c["num_experts_per_tok"],
                 min_capacity=c["moe_min_capacity"])


def weight_shapes(s: Shape) -> Tuple[dict, frozenset]:
    shapes, norms = dense.weight_shapes(s)
    L, d, e, f = s.layers, s.d_model, s.experts, s.d_ff
    shapes.update(router=(L, d, e), gate=(L, e, d, f), up=(L, e, d, f),
                  down=(L, e, f, d))
    return shapes, norms


def program_config(s: Shape):
    from repro.models.config import BlockSpec
    from repro.models.moe import MoEConfig
    return dataclasses.replace(
        dense.program_config(s), pattern=(BlockSpec("attn", "moe"),),
        moe=MoEConfig(num_experts=s.experts, top_k=s.top_k, d_ff=s.d_ff,
                      min_capacity=s.min_capacity))


def program_params(w: dict) -> dict:
    block = {
        "ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
        "attn": dense.attention_params(w),
        "moe": {"router": {"w": w["router"]}, "gate": w["gate"],
                "up": w["up"], "down": w["down"]},
    }
    return dense.outer_params(w, block)


def matmul_params(s: Shape) -> int:
    """Parameters of every matrix a token's forward pass multiplies by:
    attention, the router, the ``top_k`` experts it is routed to, and
    the lm head."""
    d = s.d_model
    return s.layers * (dense.attention_matmul_params(s) + d * s.experts
                       + s.top_k * 3 * d * s.d_ff) + d * s.vocab


def forward_gemms(s: Shape, rows: int, head_rows: int
                  ) -> List[Tuple[int, int, int]]:
    """(M, K, N) of every GEMM of one forward over ``rows`` token rows,
    ``head_rows`` of which need logits.  Each row goes through the
    router and ``top_k`` experts; the experts are counted as ``top_k``
    SwiGLU GEMMs over all rows.  The operations are exact, and the bytes
    count ``top_k`` experts' weights, the fewest any routing reads, so
    a roofline share over them cannot pass 100%."""
    d, f = s.d_model, s.d_ff
    layer = dense.attention_gemms(s, rows) + [(rows, d, s.experts)] \
        + [(rows, d, f), (rows, d, f), (rows, f, d)] * s.top_k
    out = layer * s.layers
    if head_rows:
        out.append((head_rows, d, s.vocab))
    return out


attention_flops = dense.attention_flops
paged_decode_cost = dense.paged_decode_cost
