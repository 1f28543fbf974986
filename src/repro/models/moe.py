"""Mixture-of-Experts FFN: top-k routing, capacity, scatter dispatch.

Capacity-based top-k routing (GShard/Switch style) implemented with
scatter/gather so it shards cleanly under GSPMD: tokens are sharded over
`data`, the expert dimension over `model` (expert parallelism); the
scatter into the (E, C, d) expert buffers lowers to the dispatch
all-to-all on a real mesh.

Supports top-1 (llama4-maverick / Switch) through top-8 (kimi-k2), a
shared-expert branch (DeepSeek/Kimi style), and a load-balancing auxiliary
loss.  Dropped tokens (over capacity) fall through via the residual
connection, as in GShard.

:func:`moe_ffn_held` is the serving layer of one chip's share of the
experts (``MoEConfig.held``): it routes every token over all the
experts, drops nothing, and computes the part of the routed sum that the
held experts give, with one grouped matmul per projection over them
(``gama_expert_gmm``).  What the experts on other chips would add is
left out; on one chip there is no exchange.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import layers as L
from repro.serving.quant import maybe_dequant

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden size
    capacity_factor: float = 1.25
    min_capacity: int = 4
    n_shared: int = 0              # shared (always-on) experts
    shared_d_ff: int = 0           # hidden size of the shared branch
    aux_loss_weight: float = 0.01
    # Combine formulation: "gather" indexes the (E, C, d) expert outputs
    # by token (GSPMD all-gathers the expert-sharded operand — ~7 TB/dev
    # per kimi-k2 train step); "scatter" writes each expert's slots back
    # into the token buffer (updates stay expert-sharded; GSPMD emits
    # local scatters + one (T, d) partial-sum combine).  §Perf lever.
    combine: str = "scatter"
    # GShard-style dispatch groups: routing/capacity computed per group of
    # T/G tokens (aligned with the data shards) instead of globally.  Cuts
    # the O(T*k*E) position-cumsum to per-group parallel scans and keeps
    # the dispatch scatter group-local — the §Perf hillclimb lever for the
    # MoE architectures.  1 = the paper-faithful global dispatch.
    dispatch_groups: int = 1
    # The experts this chip holds, (first, count) of num_experts: the
    # dropless share layer (moe_ffn_held).  None: every expert, through
    # the capacity layer (moe_ffn).
    held: Optional[Tuple[int, int]] = None
    # Renormalise the top-k gates to sum to 1 (Mixtral, the capacity
    # layer's historical behaviour); DeepSeek-V2 keeps the raw softmax.
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0

    @property
    def n_held(self) -> int:
        """Experts whose weights this layer holds."""
        return self.num_experts if self.held is None else self.held[1]


def init_moe(rng, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> Params:
    rr, rg, ru, rd, rs = jax.random.split(rng, 5)
    e, f = cfg.n_held, cfg.d_ff
    scale = (1.0 / d_model) ** 0.5
    p = {
        "router": L.dense_init(rr, d_model, cfg.num_experts, jnp.float32),
        "gate": jax.random.normal(rg, (e, d_model, f), dtype) * scale,
        "up": jax.random.normal(ru, (e, d_model, f), dtype) * scale,
        "down": jax.random.normal(rd, (e, f, d_model), dtype)
        * (1.0 / f) ** 0.5,
    }
    if cfg.n_shared:
        shared_ff = cfg.shared_d_ff or cfg.d_ff * cfg.n_shared
        p["shared"] = L.mlp_init(rs, d_model, shared_ff, "swiglu", dtype)
    return p


def capacity(tokens: int, cfg: MoEConfig) -> int:
    c = int(cfg.capacity_factor * tokens * cfg.top_k / cfg.num_experts)
    return max(cfg.min_capacity, c)


def moe_ffn(p: Params, x: jax.Array, cfg: MoEConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.top_k
    groups = cfg.dispatch_groups if t % max(cfg.dispatch_groups, 1) == 0 \
        else 1
    tg = t // groups
    cap = capacity(tg, cfg)

    xt = L.shard_hint(x.reshape(t, d), "tokens2d")
    xg = xt.reshape(groups, tg, d)

    router_logits = L.dense(p["router"],
                            xg.astype(jnp.float32))          # (G, Tg, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                 # (G, Tg, k)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        gate_vals = gate_vals * cfg.routed_scaling_factor

    # Position of each (token, slot) within its expert's (per-group)
    # capacity buffer: cumsum over the group's token axis.
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)         # (G,Tg,k,E)
    flat = onehot.reshape(groups, tg * k, e)
    pos = jnp.cumsum(flat, axis=1) - 1                       # (G,Tg*k,E)
    pos_in_e = jnp.sum(pos * flat, axis=-1).reshape(groups, tg, k)
    keep = pos_in_e < cap

    # Scatter tokens into (G, E, C, d) expert buffers (the EP dispatch;
    # lowers to the all-to-all on a real mesh).
    safe_pos = jnp.where(keep, pos_in_e, cap - 1)
    contrib = jnp.where(keep[..., None], 1.0, 0.0).astype(x.dtype)
    gidx = jnp.broadcast_to(
        jnp.arange(groups, dtype=jnp.int32)[:, None, None], idx.shape)
    expert_in = jnp.zeros((groups, e, cap, d), x.dtype)
    expert_in = expert_in.at[gidx, idx, safe_pos].add(
        contrib * xg[:, :, None, :], mode="drop")
    expert_in = L.shard_hint(expert_in, "experts")

    # Per-expert SwiGLU (batched einsum; E shards over the model axis,
    # G over the data axes).
    g = jnp.einsum("gecd,edf->gecf", expert_in, maybe_dequant(p["gate"], x.dtype))
    u = jnp.einsum("gecd,edf->gecf", expert_in, maybe_dequant(p["up"], x.dtype))
    h = jax.nn.silu(g) * u
    h = L.shard_hint(h, "experts")
    expert_out = L.shard_hint(
        jnp.einsum("gecf,efd->gecd", h, maybe_dequant(p["down"], x.dtype)),
        "experts")

    weights = (gate_vals * keep).astype(x.dtype)             # (G,Tg,k)
    if cfg.combine == "gather":
        # Index expert outputs by token (simple, but the expert-sharded
        # operand gets all-gathered to every device).
        out_slots = expert_out[gidx, idx, safe_pos]          # (G,Tg,k,d)
        out = jnp.einsum("gtkd,gtk->gtd", out_slots, weights).reshape(t, d)
    else:
        # Scatter-combine: record which token (and gate weight) owns each
        # capacity slot during dispatch, then push every expert's slots
        # back into the token buffer.  Updates are expert-sharded; unfilled
        # slots carry weight 0 and token id 0 (contribute nothing).
        tok_ids = jnp.broadcast_to(
            jnp.arange(tg, dtype=jnp.int32)[None, :, None], idx.shape)
        slot_token = jnp.zeros((groups, e, cap), jnp.int32)
        slot_token = slot_token.at[gidx, idx, safe_pos].max(
            jnp.where(keep, tok_ids, 0), mode="drop")
        slot_w = jnp.zeros((groups, e, cap), x.dtype)
        slot_w = slot_w.at[gidx, idx, safe_pos].add(
            jnp.where(keep, weights, 0.0), mode="drop")
        gix = jnp.broadcast_to(
            jnp.arange(groups, dtype=jnp.int32)[:, None, None],
            slot_token.shape)
        outg = jnp.zeros((groups, tg, d), x.dtype)
        outg = outg.at[gix, slot_token].add(
            expert_out * slot_w[..., None], mode="drop")
        out = outg.reshape(t, d)
    out = L.shard_hint(out, "tokens2d")

    if cfg.n_shared:
        out = out + L.mlp(p["shared"], xt, "swiglu")

    # Load-balance auxiliary loss (Switch): E * sum(frac_tokens * frac_prob).
    me = jnp.mean(jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32),
                  axis=(0, 1))
    pe = jnp.mean(probs, axis=(0, 1))
    aux = cfg.aux_loss_weight * e * jnp.sum(me * pe)

    return out.reshape(b, s, d), aux


def route(p: Params, xt: jax.Array, cfg: MoEConfig
          ) -> Tuple[jax.Array, jax.Array]:
    """Gates and experts of each token row of ``xt`` (T, d): the f32
    softmax over every expert, its ``top_k`` largest (greedy), those
    renormalised where ``norm_topk_prob``, times the routed scaling
    factor.  Returns ((T, k) f32 gates, (T, k) int32 expert ids)."""
    probs = jax.nn.softmax(L.dense(p["router"], xt.astype(jnp.float32)),
                           axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates * cfg.routed_scaling_factor, idx.astype(jnp.int32)


def moe_ffn_held(p: Params, x: jax.Array, cfg: MoEConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the routed sum, plus the shared
    experts.  x: (B, S, d) -> (out, counts): ``counts`` is an int32
    pair, the (token, expert) pairs served here and the held experts
    that got any (the grouped matmul reads those experts' weights).

    Every (token, expert) pair whose expert is held is served: the
    pairs are sorted by expert (the others after them), their rows
    gathered, and each held expert's SwiGLU runs over its own rows as
    one grouped matmul per projection; the gate-weighted rows are added
    back to their tokens.  There is no capacity, so none is dropped."""
    b, s, d = x.shape
    t, k = b * s, cfg.top_k
    first, n_held = cfg.held
    xt = x.reshape(t, d)
    with jax.named_scope("moe.route"):
        gates, idx = route(p, xt, cfg)
    with jax.named_scope("moe.dispatch"):
        local = idx.reshape(t * k) - first
        held = (local >= 0) & (local < n_held)
        key = jnp.where(held, local, n_held)
        order = jnp.argsort(key, stable=True)
        token = (jnp.arange(t * k, dtype=jnp.int32) // k)[order]
        sizes = jnp.zeros((n_held,), jnp.int32).at[key].add(1, mode="drop")
        rows = xt[token]
    dt = x.dtype
    g = kops.expert_gmm(rows, maybe_dequant(p["gate"], dt), sizes)
    u = kops.expert_gmm(rows, maybe_dequant(p["up"], dt), sizes)
    y = kops.expert_gmm(jax.nn.silu(g) * u, maybe_dequant(p["down"], dt),
                        sizes)
    with jax.named_scope("moe.combine"):
        # Rows past the held pairs hold nothing a kernel wrote: select,
        # never multiply, them away.
        part = jnp.where(held[order][:, None],
                         y.astype(jnp.float32)
                         * gates.reshape(t * k)[order][:, None], 0.0)
        out = jnp.zeros((t, d), jnp.float32).at[token].add(part).astype(dt)
    if cfg.n_shared:
        out = out + L.mlp(p["shared"], xt, "swiglu")
    return out.reshape(b, s, d), jnp.stack([jnp.sum(sizes),
                                            jnp.sum(sizes > 0)])
