"""Top-level model API: init / train loss / prefill / decode.

Batch dict conventions (all arrays):
  tokens    (B, S) int32          decoder token ids (absent for pure-embed)
  embeds    (B, S, d_model)       frontend-stub inputs (vlm/audio) instead
  labels    (B, S) int32          next-token targets (train)
  positions (B, S) or (B, S, 3)   optional; defaults to arange / (t,t,t)
  enc_embeds (B, S_enc, d_model)  encoder inputs (enc-dec archs)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import transformer as T
from repro.models.config import BlockSpec, ModelConfig

Params = Dict[str, Any]
Batch = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(rng, cfg: ModelConfig) -> Params:
    r_embed, r_blocks, r_head, r_enc = jax.random.split(rng, 4)
    p: Params = {
        "embed": L.embedding_init(r_embed, cfg.vocab_size, cfg.d_model,
                                  cfg.pdtype),
        "final_norm": (L.layernorm_init(cfg.d_model, cfg.pdtype)
                       if cfg.norm == "layernorm"
                       else L.norm_init(cfg.d_model, cfg.pdtype)),
        "blocks": T.init_stack(r_blocks, cfg,
                               cross_attn=cfg.encoder_decoder),
    }
    if cfg.first_dense_layers:
        p["lead"] = T.init_lead(
            jax.random.fold_in(r_blocks, len(cfg.pattern)), cfg)
    if cfg.norm == "layernorm":
        p["ln0"] = L.layernorm_init(cfg.d_model, cfg.pdtype)  # rwkv style
    if not cfg.tie_embeddings:
        p["head"] = L.dense_init(r_head, cfg.d_model, cfg.vocab_size,
                                 cfg.pdtype)
    if cfg.encoder_decoder:
        enc_cfg = _encoder_cfg(cfg)
        p["encoder"] = {
            "blocks": T.init_stack(r_enc, enc_cfg, cross_attn=False),
            "final_norm": (L.layernorm_init(cfg.d_model, cfg.pdtype)
                           if cfg.norm == "layernorm"
                           else L.norm_init(cfg.d_model, cfg.pdtype)),
        }
    return p


def _encoder_cfg(cfg: ModelConfig) -> ModelConfig:
    import dataclasses
    return dataclasses.replace(
        cfg, n_layers=cfg.n_encoder_layers, causal=False,
        pattern=(BlockSpec("attn", "dense"),), moe=None,
        encoder_decoder=False)


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _positions(batch: Batch, cfg: ModelConfig, s: int,
               offset: int = 0) -> jax.Array:
    if "positions" in batch:
        return batch["positions"]
    b = (batch.get("tokens", batch.get("embeds"))).shape[0]
    off = jnp.asarray(offset, jnp.int32)
    if off.ndim == 1:
        # Per-slot decode offsets (continuous batching): each sequence
        # in the batch sits at its own position in its KV cache.
        off = off[:, None]
    pos = jnp.arange(s, dtype=jnp.int32)[None, :] + off
    pos = jnp.broadcast_to(pos, (b, s))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[..., None], (b, s, 3))
    return pos


def _input_embed(params: Params, batch: Batch, cfg: ModelConfig
                 ) -> jax.Array:
    if "embeds" in batch:
        x = batch["embeds"].astype(cfg.cdtype)
    else:
        x = L.embed(params["embed"], batch["tokens"], cfg.cdtype)
    if "ln0" in params:
        x = L.layernorm(params["ln0"], x, cfg.norm_eps)
    return L.shard_hint(x, "residual")


def _encode(params: Params, batch: Batch, cfg: ModelConfig,
            remat: bool = False) -> Optional[jax.Array]:
    if not cfg.encoder_decoder:
        return None
    enc_cfg = _encoder_cfg(cfg)
    x = batch["enc_embeds"].astype(cfg.cdtype)
    pos = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
    x, _, _, _ = T.apply_stack(params["encoder"]["blocks"], x, enc_cfg,
                            positions=pos, remat=remat)
    if cfg.norm == "layernorm":
        return L.layernorm(params["encoder"]["final_norm"], x, cfg.norm_eps)
    return L.rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps)


def forward(params: Params, batch: Batch, cfg: ModelConfig, *,
            caches: Optional[List] = None,
            cache_pos: Optional[jax.Array] = None,
            block_tables: Optional[jax.Array] = None,
            decode: bool = False,
            remat: bool = False,
            remat_policy: str = "full",
            held_counts: bool = False,
            ) -> Tuple:
    """Returns (logits (B,S,V) f32, new_caches, aux_loss), and with
    ``held_counts`` a fourth element: summed over the held-expert
    layers, the (token, expert) pairs they served and the held experts
    that got any (int32 (2,); needs ``cfg.moe.held``)."""
    x = _input_embed(params, batch, cfg)
    s = x.shape[1]
    offset = 0 if cache_pos is None else cache_pos
    pos = _positions(batch, cfg, s, offset)
    # Decode reuses the prefill-time cross-attention cache; no re-encode.
    enc_out = None if decode else _encode(params, batch, cfg, remat)
    x, new_caches, aux, held = T.apply_stack(
        params["blocks"], x, cfg, positions=pos, caches=caches,
        cache_pos=cache_pos, block_tables=block_tables, enc_out=enc_out,
        decode=decode, remat=remat, remat_policy=remat_policy,
        lead=params.get("lead"))
    if cfg.norm == "layernorm":
        x = L.layernorm(params["final_norm"], x, cfg.norm_eps)
    else:
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    lg = L.logits(params["embed"], x, params.get("head"))
    if held_counts:
        return lg, new_caches, aux, held
    return lg, new_caches, aux


def loss_fn(params: Params, batch: Batch, cfg: ModelConfig,
            remat: bool = True,
            remat_policy: str = "full"
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    lg, _, aux = forward(params, batch, cfg, remat=remat,
                         remat_policy=remat_policy)
    mask = batch.get("mask")
    ce = L.cross_entropy(lg, batch["labels"], mask)
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: int = 0) -> List:
    return T.init_stack_cache(cfg, batch, max_len,
                              cross_len=enc_len if cfg.encoder_decoder else 0)


def paged_eligible(cfg: ModelConfig) -> bool:
    """True when the arch can decode through the paged KV pool: every
    mixer is attention, GQA or latent (recurrent mamba/rwkv state is
    fixed-size per slot — nothing to page) and there is no enc-dec
    cross cache."""
    return (not cfg.encoder_decoder
            and all(spec.mixer in ("attn", "mla") for spec in cfg.pattern))


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     kv_dtype: Optional[str] = None) -> List:
    """Paged-KV cache stack (``repro.serving.kvpool``): per attention
    layer, a (num_pages + 1, Hkv, page_size, D) page pool — the extra
    row is the null sink unallocated block-table entries point at.  A
    latent-attention layer holds one (num_pages + 1, 1, page_size, R)
    pool of latent rows instead (``models/mla.py``).
    ``kv_dtype`` overrides the page dtype (``"int8"`` adds per-row
    scale-row arrays; see ``attention.init_paged_kv_cache``)."""
    if not paged_eligible(cfg):
        raise ValueError(
            f"arch {cfg.name!r} has non-attention state (or an enc-dec "
            f"cross cache) — the paged KV pool covers attention KV only")
    return T.init_stack_cache(cfg, 0, 0,
                              paged=(num_pages + 1, page_size, kv_dtype))


def prefill(params: Params, batch: Batch, cfg: ModelConfig,
            caches: List) -> Tuple[jax.Array, List]:
    """Run the prompt, fill caches; returns (last-token logits, caches)."""
    lg, new_caches, _ = forward(params, batch, cfg, caches=caches,
                                cache_pos=jnp.zeros((), jnp.int32))
    return lg[:, -1], new_caches


def decode_step(params: Params, token: jax.Array, pos: jax.Array,
                cfg: ModelConfig, caches: List,
                embeds: Optional[jax.Array] = None,
                block_tables: Optional[jax.Array] = None,
                held_counts: bool = False) -> Tuple:
    """One token (B,) at position `pos`; returns (logits, caches).

    ``pos`` is either a scalar (uniform batch — every sequence sits at
    the same position, the one-shot ``generate`` shape) or a (B,) int32
    vector of per-slot positions (ragged continuous batching: each slot
    writes its KV at its own offset and attends only to its own valid
    prefix).  With a paged cache (``init_paged_cache``),
    ``block_tables`` (B, max_pages) maps each slot's positions onto
    pool pages; ``pos`` must then be the per-slot vector form.  With
    ``held_counts`` a third element is returned, as :func:`forward`'s.
    """
    batch: Batch = {}
    if embeds is not None:
        batch["embeds"] = embeds[:, None]
    else:
        batch["tokens"] = token[:, None]
    out = forward(params, batch, cfg, caches=caches, cache_pos=pos,
                  block_tables=block_tables, decode=True,
                  held_counts=held_counts)
    return (out[0][:, 0], out[1]) + tuple(out[3:])
