"""DeepSeek-V2, as DeepseekV2ForCausalLM defines it, at one chip's share
of its experts: per layer, RMSNorm, multi-head latent attention (MLA)
with YaRN rope, RMSNorm and an FFN.  The first ``first_k_dense_replace``
layers have a dense SwiGLU; every later one routes each token over all
``router_experts`` experts (softmax, greedy top-k, no renormalisation
when ``norm_topk_prob`` is false) and adds its shared experts.  This
chip holds ``n_routed_experts`` of them (from ``first_held_expert``):
the routed sum is the part those experts give.

The architecture module of ``bench/reference/deepseek_v2.py``; the
interface it fills is set out in :mod:`bench.lib.model`.  The canonical
weights keep the leading dense layers under ``lead_`` names, stacked
apart from the expert layers', so that the program's two stacks are the
same arrays.

Cost terms count the work the served tokens need on this chip: each
held expert serves ``rows * top_k / router_experts`` rows of a call
(its share of an even routing), attention counts the expanded form
(q . k over ``nope + rope`` lanes, p . v over ``v_head_dim``), and a
paged decode step reads one latent row of ``kv_lora_rank + rope`` lanes
a key a layer and runs the absorbed products over them.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

from bench.lib.costs import BF16


@dataclasses.dataclass(frozen=True)
class Shape:
    name: str
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    dense_d_ff: int
    expert_d_ff: int
    router_experts: int
    held_experts: int
    first_held: int
    top_k: int
    shared_experts: int
    norm_topk: bool
    routed_scaling: float
    vocab: int
    tied: bool
    rope_theta: float
    yarn: tuple      # (factor, original_max, beta_fast, beta_slow,
                     #  mscale, mscale_all_dim)
    eps: float
    init_std: float
    dtype: str
    reference: str

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def latent(self) -> int:
        return self.kv_lora_rank + self.qk_rope

    @property
    def qk_dim(self) -> int:
        return self.qk_nope + self.qk_rope


def load_shape(c: dict, name: str) -> Shape:
    y = c["rope_scaling"]
    if y.get("type") != "yarn" or c.get("q_lora_rank") is not None:
        raise ValueError(f"{name}: only YaRN rope and no query "
                         f"compression are supported")
    return Shape(
        name=name, layers=c["num_hidden_layers"],
        dense_layers=c["first_k_dense_replace"], d_model=c["hidden_size"],
        heads=c["num_attention_heads"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope=c["qk_nope_head_dim"], qk_rope=c["qk_rope_head_dim"],
        v_head=c["v_head_dim"], dense_d_ff=c["intermediate_size"],
        expert_d_ff=c["moe_intermediate_size"],
        router_experts=c["router_experts"],
        held_experts=c["n_routed_experts"],
        first_held=c.get("first_held_expert", 0),
        top_k=c["num_experts_per_tok"],
        shared_experts=c["n_shared_experts"],
        norm_topk=bool(c["norm_topk_prob"]),
        routed_scaling=float(c["routed_scaling_factor"]),
        vocab=c["vocab_size"], tied=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]),
        yarn=(float(y["factor"]), int(y["original_max_position_embeddings"]),
              float(y["beta_fast"]), float(y["beta_slow"]),
              float(y["mscale"]), float(y["mscale_all_dim"])),
        eps=c["rms_norm_eps"], init_std=c["initializer_range"],
        dtype=c["torch_dtype"], reference=c["reference"])


def weight_shapes(s: Shape) -> Tuple[dict, frozenset]:
    d, h = s.d_model, s.heads
    e, f = s.held_experts, s.expert_d_ff
    sf = s.shared_experts * f

    def attention(n):
        return {"wq": (n, d, h * s.qk_dim), "wkv_a": (n, d, s.latent),
                "kv_norm": (n, s.kv_lora_rank),
                "wkv_b": (n, s.kv_lora_rank, h * (s.qk_nope + s.v_head)),
                "wo": (n, h * s.v_head, d), "ln1": (n, d), "ln2": (n, d)}

    lead, n = s.dense_layers, s.expert_layers
    shapes = {f"lead_{k}": v for k, v in attention(lead).items()}
    shapes.update({"lead_gate": (lead, d, s.dense_d_ff),
                   "lead_up": (lead, d, s.dense_d_ff),
                   "lead_down": (lead, s.dense_d_ff, d)})
    shapes.update(attention(n))
    shapes.update(router=(n, d, s.router_experts), gate=(n, e, d, f),
                  up=(n, e, d, f), down=(n, e, f, d),
                  shared_gate=(n, d, sf), shared_up=(n, d, sf),
                  shared_down=(n, sf, d),
                  embed=(s.vocab, d), final_norm=(d,))
    if not s.tied:
        shapes["head"] = (d, s.vocab)
    norms = {"kv_norm", "ln1", "ln2", "lead_kv_norm", "lead_ln1",
             "lead_ln2", "final_norm"}
    return shapes, frozenset(norms)


# -- the program's form of the model -----------------------------------


def program_config(s: Shape):
    from repro.models.config import BlockSpec, ModelConfig
    from repro.models.layers import YarnScaling
    from repro.models.mla import MLAConfig
    from repro.models.moe import MoEConfig
    return ModelConfig(
        name=s.name, n_layers=s.layers, d_model=s.d_model, n_heads=s.heads,
        n_kv_heads=s.heads, d_head=s.v_head, d_ff=s.dense_d_ff,
        vocab_size=s.vocab, pattern=(BlockSpec("mla", "moe"),),
        mla=MLAConfig(kv_lora_rank=s.kv_lora_rank, qk_nope_dim=s.qk_nope,
                      qk_rope_dim=s.qk_rope, v_head_dim=s.v_head),
        moe=MoEConfig(num_experts=s.router_experts, top_k=s.top_k,
                      d_ff=s.expert_d_ff, n_shared=s.shared_experts,
                      shared_d_ff=s.shared_experts * s.expert_d_ff,
                      held=(s.first_held, s.held_experts),
                      norm_topk_prob=s.norm_topk,
                      routed_scaling_factor=s.routed_scaling),
        first_dense_layers=s.dense_layers,
        rope_theta=s.rope_theta, rope_scaling=YarnScaling(*s.yarn),
        tie_embeddings=s.tied, norm_eps=s.eps, param_dtype=s.dtype,
        compute_dtype=s.dtype, cache_dtype=s.dtype)


def _block(w: dict, pre: str) -> dict:
    mla = {n: {"w": w[pre + n]} for n in ("wq", "wkv_a", "wkv_b", "wo")}
    mla["kv_norm"] = {"scale": w[pre + "kv_norm"]}
    return {"ln1": {"scale": w[pre + "ln1"]},
            "ln2": {"scale": w[pre + "ln2"]}, "mla": mla}


def program_params(w: dict) -> dict:
    """The canonical weights in the program's tree: the leading dense
    layers' stack and the expert layers' stack, the same arrays."""
    lead = _block(w, "lead_")
    lead["mlp"] = {n: {"w": w["lead_" + n]} for n in ("gate", "up", "down")}
    block = _block(w, "")
    block["moe"] = {
        "router": {"w": w["router"]},
        "gate": w["gate"], "up": w["up"], "down": w["down"],
        "shared": {n: {"w": w["shared_" + n]}
                   for n in ("gate", "up", "down")}}
    p = {"embed": {"table": w["embed"]},
         "final_norm": {"scale": w["final_norm"]},
         "blocks": [block], "lead": [lead]}
    if "head" in w:
        p["head"] = {"w": w["head"]}
    return p


# -- cost terms ----------------------------------------------------------


def attention_matmul_params(s: Shape) -> int:
    d, h = s.d_model, s.heads
    return d * (h * s.qk_dim + s.latent) \
        + s.kv_lora_rank * h * (s.qk_nope + s.v_head) + h * s.v_head * d


def held_share(s: Shape) -> float:
    """The expert rows of a token this chip serves: top_k times the
    held share of the router's experts."""
    return s.top_k * s.held_experts / s.router_experts


def matmul_params(s: Shape) -> float:
    """Parameters of every matrix a token's forward pass multiplies by
    on this chip: attention, the dense layers, the router, its share of
    the routed experts (``held_share`` experts), the shared experts and
    the lm head."""
    d, f = s.d_model, s.expert_d_ff
    expert_layer = d * s.router_experts + held_share(s) * 3 * d * f \
        + 3 * d * s.shared_experts * f
    return s.layers * attention_matmul_params(s) \
        + s.dense_layers * 3 * d * s.dense_d_ff \
        + s.expert_layers * expert_layer + d * s.vocab


def attention_gemms(s: Shape, rows: int) -> List[Tuple[int, int, int]]:
    """(M, K, N) of one layer's Q, latent, expansion and output
    projections.  The expansion counts each token's own latent row once:
    the absorbed decode does the same operations as two per-head
    products."""
    d, h = s.d_model, s.heads
    return [(rows, d, h * s.qk_dim), (rows, d, s.latent),
            (rows, s.kv_lora_rank, h * (s.qk_nope + s.v_head)),
            (rows, h * s.v_head, d)]


def expert_gemms(s: Shape, rows: int) -> List[Tuple[float, int, int]]:
    """(M, K, N) of the held experts' GEMMs over one call of ``rows``
    token rows, every expert layer: each held expert at
    ``rows * top_k / router_experts`` rows (an even routing's share),
    its gate, up and down projections (their operations; the bytes of
    ``gama_expert_gmm`` are :func:`expert_gmm_cost`'s)."""
    d, f = s.d_model, s.expert_d_ff
    m = rows * s.top_k / s.router_experts
    return [(m, d, f), (m, d, f), (m, f, d)] * s.held_experts \
        * s.expert_layers


def expert_gmm_cost(s: Shape, rows: int, reached: float,
                    nbytes: int = BF16) -> Tuple[float, float]:
    """(operations, bytes) of one call's grouped matmuls over ``rows``
    token rows, every expert layer: the held pairs (an even routing's
    share, ``rows * top_k * held / router``) through gate, up and down,
    and the weights of the ``reached`` held experts (those with any
    pair; a layer call's mean) read once, each pair's row read in and
    written out once."""
    d, f = s.d_model, s.expert_d_ff
    pairs = rows * held_share(s)
    flops = 2.0 * 3 * pairs * d * f * s.expert_layers
    weights = reached * 3 * d * f
    return flops, float((weights + 2 * pairs * d) * nbytes
                        * s.expert_layers)


def forward_gemms(s: Shape, rows: int, head_rows: int
                  ) -> List[Tuple[float, int, int]]:
    """(M, K, N) of every GEMM of one forward over ``rows`` token rows,
    ``head_rows`` of which need logits."""
    d, f = s.d_model, s.expert_d_ff
    sf = s.shared_experts * f
    dense = [(rows, d, s.dense_d_ff), (rows, d, s.dense_d_ff),
             (rows, s.dense_d_ff, d)]
    expert = [(rows, d, s.router_experts), (rows, d, sf), (rows, d, sf),
              (rows, sf, d)]
    out = (attention_gemms(s, rows) + dense) * s.dense_layers \
        + (attention_gemms(s, rows) + expert) * s.expert_layers \
        + expert_gemms(s, rows)
    if head_rows:
        out.append((head_rows, d, s.vocab))
    return out


def attention_flops(s: Shape, keys: int) -> float:
    """QKᵀ over ``nope + rope`` lanes and PV over ``v_head`` lanes, per
    head, of queries attending over ``keys`` keys in total."""
    return 2.0 * s.layers * s.heads * (s.qk_dim + s.v_head) * keys


def paged_decode_cost(s: Shape, contexts: List[int],
                      nbytes: int = BF16) -> Tuple[float, float]:
    """(operations, bytes) of one decode step's absorbed attention over
    the latent pool, every layer: each live slot reads its context's
    latent rows (``latent`` lanes), every head takes q . row over those
    lanes and p . row over the first ``kv_lora_rank``, and each slot
    reads its queries and writes its outputs."""
    keys = sum(contexts)
    flops = 2.0 * s.layers * s.heads * (s.latent + s.kv_lora_rank) * keys
    rows = s.layers * s.latent * keys * nbytes
    qo = s.layers * len(contexts) * s.heads \
        * (s.latent + s.kv_lora_rank) * nbytes
    return flops, float(rows + qo)
