"""Model configuration — one dataclass covers all 10 assigned architectures.

A model is a stack of `n_layers` blocks cycling through `pattern` (a tuple
of BlockSpec): dense transformers use a single ("attn","dense") entry;
MoE models ("attn","moe"); RWKV ("rwkv","rwkv_cm"); Jamba an 8-entry
hybrid pattern.  `first_dense_layers` leading blocks (the pattern's
mixer with a dense FFN of `d_ff`) may come before the cycled ones, as
DeepSeek's first_k_dense_replace.  Latent attention
(DeepSeek-V2's MLA) is the mixer "mla" with an `MLAConfig`.
Encoder-decoder models add an encoder stack and give decoder blocks
cross-attention.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.models.layers import YarnScaling
from repro.models.mamba import MambaConfig
from repro.models.mla import MLAConfig
from repro.models.moe import MoEConfig
from repro.models.rwkv import RwkvConfig


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    mixer: str = "attn"      # "attn" | "mla" | "mamba" | "rwkv"
    ffn: str = "dense"       # "dense" | "moe" | "rwkv_cm" | "none"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    pattern: Tuple[BlockSpec, ...] = (BlockSpec(),)

    # Sub-configs.
    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None
    rwkv: Optional[RwkvConfig] = None
    mla: Optional[MLAConfig] = None

    # Leading blocks before the cycled pattern: the pattern's first
    # mixer with a dense FFN of d_ff.
    first_dense_layers: int = 0

    # Attention details.
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnScaling] = None     # read by the mla mixer
    mrope_sections: Optional[Tuple[int, int, int]] = None
    causal: bool = True

    # Encoder-decoder (seamless-m4t).
    encoder_decoder: bool = False
    n_encoder_layers: int = 0

    # Modality frontend stub: "none" (tokens) | "audio" | "vision" —
    # the stubs take precomputed (B, S, d_model) embeddings from
    # input_specs(), per the assignment.
    frontend: str = "none"

    norm: str = "rmsnorm"            # "rmsnorm" | "layernorm" (rwkv)
    ffn_kind: str = "swiglu"         # dense-FFN activation
    tie_embeddings: bool = False
    norm_eps: float = 1e-6

    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    cache_dtype: str = "bfloat16"

    # Long-context capability marker: True only for architectures whose
    # decode state is O(1)/sub-quadratic (ssm/hybrid) — gates long_500k.
    sub_quadratic: bool = False

    @property
    def n_groups(self) -> int:
        cycled = self.n_layers - self.first_dense_layers
        assert cycled % len(self.pattern) == 0, (
            f"{self.name}: {cycled} cycled layers not divisible by "
            f"pattern period {len(self.pattern)}")
        return cycled // len(self.pattern)

    @property
    def lead_spec(self) -> BlockSpec:
        """The block of the leading dense layers."""
        return BlockSpec(self.pattern[0].mixer, "dense")

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def n_params(self) -> int:
        """Total parameter count (analytic, for 6ND roofline maths)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        total = v * d  # embedding
        if not self.tie_embeddings:
            total += v * d
        specs = [(spec, self.n_groups) for spec in self.pattern]
        if self.first_dense_layers:
            specs.append((self.lead_spec, self.first_dense_layers))
        for spec, count in specs:
            n = 0
            if spec.mixer == "mla":
                m = self.mla
                n += d * (self.n_heads * m.qk_dim + m.latent_dim)
                n += m.kv_lora_rank * self.n_heads * (m.qk_nope_dim
                                                      + m.v_head_dim)
                n += self.n_heads * m.v_head_dim * d
            elif spec.mixer == "attn":
                n += d * (self.n_heads + 2 * self.n_kv_heads) * self.d_head
                n += self.n_heads * self.d_head * d
            elif spec.mixer == "mamba":
                mc = self.mamba or MambaConfig()
                di = mc.expand * d
                n += d * 2 * di + di * d            # in/out proj
                n += mc.d_conv * di
                n += di * (mc.resolve_dt_rank(d) + 2 * mc.d_state)
                n += mc.resolve_dt_rank(d) * di + di * mc.d_state
            elif spec.mixer == "rwkv":
                n += 5 * d * d                       # r,k,v,g,o
                n += d * 5 * 32 + 5 * 32 * d         # ddlerp loras
                n += d * 64 + 64 * d                 # decay lora
            if spec.ffn == "dense":
                mult = 3 if self.ffn_kind == "swiglu" else 2
                n += mult * d * f
            elif spec.ffn == "moe":
                assert self.moe is not None
                n += d * self.moe.num_experts
                n += 3 * d * self.moe.d_ff * self.moe.n_held
                if self.moe.n_shared:
                    sf = self.moe.shared_d_ff or self.moe.d_ff * self.moe.n_shared
                    n += 3 * d * sf
            elif spec.ffn == "rwkv_cm":
                n += 2 * d * f + d * d
            total += n * count
        if self.encoder_decoder:
            # Encoder layers (attn+dense ffn) + decoder cross-attn.
            enc = self.n_encoder_layers * (
                d * (self.n_heads + 2 * self.n_kv_heads) * self.d_head
                + self.n_heads * self.d_head * d + 3 * d * f)
            cross = self.n_layers * (
                d * (self.n_heads + 2 * self.n_kv_heads) * self.d_head
                + self.n_heads * self.d_head * d)
            total += enc + cross
        return total

    def n_active_params(self) -> int:
        """Active params per token (MoE: top_k + shared experts only)."""
        if self.moe is None:
            return self.n_params()
        full = self.n_params()
        moe_layers = sum(1 for s in self.pattern if s.ffn == "moe") \
            * self.n_groups
        all_experts = 3 * self.d_model * self.moe.d_ff \
            * self.moe.n_held * moe_layers
        active_experts = 3 * self.d_model * self.moe.d_ff \
            * self.moe.top_k * moe_layers
        return full - all_experts + active_experts
