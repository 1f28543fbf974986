"""Block stacks: init/apply for the pattern-cycled layer architecture.

Layers are stacked per pattern position and iterated with ``lax.scan``
(one compiled block group regardless of depth — essential for compiling
80-layer configs in the dry-run).  Caches are stacked the same way and
threaded through the scan.  ``cfg.first_dense_layers`` leading blocks
are a stack of their own (``params["lead"]``, the first cache entry),
scanned before the pattern.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.models import attention as A
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rwkv as R
from repro.models.config import BlockSpec, ModelConfig

Params = Dict[str, Any]
Cache = Dict[str, Any]


def _norm_init(cfg: ModelConfig):
    return (L.layernorm_init(cfg.d_model, cfg.pdtype)
            if cfg.norm == "layernorm" else
            L.norm_init(cfg.d_model, cfg.pdtype))


def _norm(cfg: ModelConfig, p, x):
    if cfg.norm == "layernorm":
        return L.layernorm(p, x, cfg.norm_eps)
    return L.rmsnorm(p, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------


def init_block(rng, cfg: ModelConfig, spec: BlockSpec,
               cross_attn: bool = False) -> Params:
    keys = jax.random.split(rng, 6)
    p: Params = {"ln1": _norm_init(cfg)}
    if spec.mixer == "mla":
        p["mla"] = MLA.init_mla(keys[0], cfg.d_model, cfg.n_heads, cfg.mla,
                                cfg.pdtype)
    elif spec.mixer == "attn":
        p["attn"] = A.init_attention(
            keys[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head,
            cfg.qk_norm, cfg.pdtype)
    elif spec.mixer == "mamba":
        p["mamba"] = M.init_mamba(keys[0], cfg.d_model,
                                  cfg.mamba or M.MambaConfig(), cfg.pdtype)
    elif spec.mixer == "rwkv":
        p["rwkv_tm"] = R.init_time_mix(keys[0], cfg.d_model,
                                       cfg.rwkv or R.RwkvConfig(),
                                       cfg.pdtype)
    else:
        raise ValueError(spec.mixer)
    if cross_attn:
        p["ln_cross"] = _norm_init(cfg)
        p["cross_attn"] = A.init_attention(
            keys[1], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head,
            cfg.qk_norm, cfg.pdtype)
    if spec.ffn != "none":
        p["ln2"] = _norm_init(cfg)
    if spec.ffn == "dense":
        p["mlp"] = L.mlp_init(keys[2], cfg.d_model, cfg.d_ff,
                              cfg.ffn_kind, cfg.pdtype)
    elif spec.ffn == "moe":
        assert cfg.moe is not None
        p["moe"] = MOE.init_moe(keys[2], cfg.d_model, cfg.moe, cfg.pdtype)
    elif spec.ffn == "rwkv_cm":
        p["rwkv_cm"] = R.init_channel_mix(keys[2], cfg.d_model, cfg.d_ff,
                                          cfg.pdtype)
    return p


def init_block_cache(cfg: ModelConfig, spec: BlockSpec, batch: int,
                     max_len: int, cross_len: int = 0,
                     paged: Optional[Tuple] = None) -> Cache:
    """``paged=(num_pages, page_size[, kv_dtype])`` swaps the attention
    KV layout for the kvpool page-pool arrays (decode addresses them
    through a block table; ``batch``/``max_len`` are then ignored for
    attention).  The optional ``kv_dtype`` element overrides the page
    dtype — ``"int8"`` adds per-row scale rows (see
    ``attention.init_paged_kv_cache``).  Recurrent state (mamba/rwkv)
    is fixed-size per slot and has no paged form; enc-dec cross caches
    are likewise dense-only."""
    c: Cache = {}
    if spec.mixer == "mla":
        if cross_len:
            raise NotImplementedError("latent attention has no cross cache")
        dt = jnp.dtype(cfg.cache_dtype)
        if paged is not None:
            c["mla"] = MLA.init_paged_mla_cache(
                paged[0], paged[1], cfg.mla, dt,
                kv_dtype=paged[2] if len(paged) > 2 else None)
        else:
            c["mla"] = MLA.init_mla_cache(batch, max_len, cfg.mla, dt)
    elif spec.mixer == "attn":
        if paged is not None:
            if cross_len:
                raise NotImplementedError(
                    "paged KV does not cover enc-dec cross caches")
            c["attn"] = A.init_paged_kv_cache(
                paged[0], cfg.n_kv_heads, paged[1], cfg.d_head,
                jnp.dtype(cfg.cache_dtype),
                kv_dtype=paged[2] if len(paged) > 2 else None)
        else:
            c["attn"] = A.init_kv_cache(batch, cfg.n_kv_heads, max_len,
                                        cfg.d_head,
                                        jnp.dtype(cfg.cache_dtype))
    elif spec.mixer == "mamba":
        c["mamba"] = M.init_mamba_cache(batch, cfg.d_model,
                                        cfg.mamba or M.MambaConfig(),
                                        jnp.dtype(cfg.cache_dtype))
    elif spec.mixer == "rwkv":
        c["rwkv"] = R.init_rwkv_cache(batch, cfg.d_model,
                                      cfg.rwkv or R.RwkvConfig(),
                                      jnp.dtype(cfg.cache_dtype))
    if cross_len:
        c["cross"] = A.init_kv_cache(batch, cfg.n_kv_heads, cross_len,
                                     cfg.d_head, jnp.dtype(cfg.cache_dtype))
    return c


def apply_block(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    spec: BlockSpec,
    *,
    positions: Optional[jax.Array],
    cache: Optional[Cache] = None,
    cache_pos: Optional[jax.Array] = None,
    block_tables: Optional[jax.Array] = None,
    enc_out: Optional[jax.Array] = None,
    decode: bool = False,
) -> Tuple[jax.Array, Optional[Cache], jax.Array, Optional[jax.Array]]:
    """Returns (x, new_cache, aux_loss, held counts): the last is a
    held-expert layer's (pairs served, experts reached), as
    :func:`~repro.models.moe.moe_ffn_held` gives them (None for any
    other block)."""
    aux = jnp.zeros((), jnp.float32)
    held = None
    new_cache: Cache = {} if cache is not None else None

    h = _norm(cfg, p["ln1"], x)
    if spec.mixer == "mla":
        out, nc = MLA.mla_attention(
            p["mla"], h, cfg, positions=positions,
            cache=None if cache is None else cache.get("mla"),
            cache_pos=cache_pos, block_tables=block_tables)
        if new_cache is not None and nc is not None:
            new_cache["mla"] = nc
    elif spec.mixer == "attn":
        out, nc = A.attention(
            p["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            d_head=cfg.d_head, positions=positions,
            rope_theta=cfg.rope_theta, mrope_sections=cfg.mrope_sections,
            qk_norm=cfg.qk_norm, causal=cfg.causal,
            cache=None if cache is None else cache.get("attn"),
            cache_pos=cache_pos, block_tables=block_tables)
        if new_cache is not None and nc is not None:
            new_cache["attn"] = nc
    elif spec.mixer == "mamba":
        out, nc = M.mamba_forward(p["mamba"], h, cfg.mamba or M.MambaConfig(),
                                  None if cache is None
                                  else cache.get("mamba"))
        if new_cache is not None and nc is not None:
            new_cache["mamba"] = nc
    else:  # rwkv
        out, nc = R.time_mix(p["rwkv_tm"], h, cfg.rwkv or R.RwkvConfig(),
                             None if cache is None else cache.get("rwkv"))
        if new_cache is not None and nc is not None:
            new_cache["rwkv"] = nc
    # Post-collective output: the row-parallel combine's result.  Named so
    # the "tp_outs" remat policy can save exactly these (backward then
    # never re-runs the forward all-reduces).
    x = x + checkpoint_name(out, "tp_out")

    if "cross_attn" in p:
        h = _norm(cfg, p["ln_cross"], x)
        cross_cache = None if cache is None else cache.get("cross")
        out, nc = A.attention(
            p["cross_attn"], h, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, d_head=cfg.d_head, positions=None,
            qk_norm=cfg.qk_norm, causal=False,
            cache=cross_cache, cache_pos=None if decode else 0,
            kv_from=None if decode else enc_out,
            use_cached_kv=decode)
        if new_cache is not None and nc is not None:
            new_cache["cross"] = nc
        x = x + out

    if spec.ffn != "none":
        h = _norm(cfg, p["ln2"], x)
        if spec.ffn == "dense":
            x = x + checkpoint_name(L.mlp(p["mlp"], h, cfg.ffn_kind),
                                    "tp_out")
        elif spec.ffn == "moe" and cfg.moe.held is not None:
            out, held = MOE.moe_ffn_held(p["moe"], h, cfg.moe)
            x = x + checkpoint_name(out, "tp_out")
        elif spec.ffn == "moe":
            out, aux = MOE.moe_ffn(p["moe"], h, cfg.moe)
            x = x + checkpoint_name(out, "tp_out")
        elif spec.ffn == "rwkv_cm":
            out, nc = R.channel_mix(
                p["rwkv_cm"], h,
                None if cache is None else cache.get("rwkv"))
            if new_cache is not None and nc is not None:
                # Merge channel-mix shift state into the rwkv cache entry.
                merged = dict(new_cache.get("rwkv", cache.get("rwkv")))
                merged["shift_cm"] = nc["shift_cm"]
                new_cache["rwkv"] = merged
            x = x + out
    x = L.shard_hint(x, "residual")
    return x, new_cache, aux, held


# ---------------------------------------------------------------------------
# Stacks (scan over groups)
# ---------------------------------------------------------------------------


def init_stack(rng, cfg: ModelConfig, cross_attn: bool = False
               ) -> List[Params]:
    """Per pattern position: params stacked over n_groups (leading axis)."""
    stacks = []
    for i, spec in enumerate(cfg.pattern):
        rngs = jax.random.split(jax.random.fold_in(rng, i), cfg.n_groups)
        stacks.append(jax.vmap(
            lambda r, s=spec: init_block(r, cfg, s, cross_attn))(rngs))
    return stacks


def init_lead(rng, cfg: ModelConfig) -> List[Params]:
    """The leading dense blocks, stacked over ``first_dense_layers``."""
    rngs = jax.random.split(rng, cfg.first_dense_layers)
    return [jax.vmap(lambda r: init_block(r, cfg, cfg.lead_spec))(rngs)]


def init_stack_cache(cfg: ModelConfig, batch: int, max_len: int,
                     cross_len: int = 0,
                     paged: Optional[Tuple] = None) -> List[Cache]:
    """Per pattern position, stacked as the params; the leading dense
    blocks' stack, where there are any, comes first."""
    caches = []
    lead = [(cfg.lead_spec, cfg.first_dense_layers)] \
        if cfg.first_dense_layers else []
    for spec, n in lead + [(spec, cfg.n_groups) for spec in cfg.pattern]:
        one = init_block_cache(cfg, spec, batch, max_len, cross_len,
                               paged=paged)
        caches.append(jax.tree.map(
            lambda x, n=n: jnp.broadcast_to(x, (n,) + x.shape), one))
    return caches


def apply_stack(
    stacks: List[Params],
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array],
    caches: Optional[List[Cache]] = None,
    cache_pos: Optional[jax.Array] = None,
    block_tables: Optional[jax.Array] = None,
    enc_out: Optional[jax.Array] = None,
    decode: bool = False,
    remat: bool = False,
    remat_policy: str = "full",
    lead: Optional[List[Params]] = None,
) -> Tuple[jax.Array, Optional[List[Cache]], jax.Array,
           Optional[jax.Array]]:
    """Scan the group over n_groups, after the ``lead`` stack of leading
    dense blocks where there is one (its cache is ``caches[0]``).
    Returns (x, new_caches, aux_sum, held): ``held`` sums the
    held-expert layers' (pairs served, experts reached) (None without
    them).

    remat_policy: "full" saves nothing (max recompute — the backward
    re-executes the forward *including its partial-sum all-reduces*);
    "dots" saves matmul outputs (jax.checkpoint_policies.checkpoint_dots)
    so the collective results survive to the backward — the §Perf lever
    that removes the remat-duplicated collectives.
    """
    track = cfg.moe is not None and cfg.moe.held is not None

    def scan(stacks_, specs, caches_, carry):
        def group_fn(carry, xs):
            x, aux = carry[:2]
            held = carry[2:]
            params_g = xs["params"]
            caches_g = xs.get("cache")
            new_caches_g = [] if caches_g is not None else None
            for i, spec in enumerate(specs):
                c = None if caches_g is None else caches_g[i]
                x, nc, a, h = apply_block(
                    params_g[i], x, cfg, spec, positions=positions,
                    cache=c, cache_pos=cache_pos,
                    block_tables=block_tables, enc_out=enc_out,
                    decode=decode)
                aux = aux + a
                if h is not None:
                    held = (held[0] + h,)
                if new_caches_g is not None:
                    new_caches_g.append(nc if nc else c)
            out = {"cache": new_caches_g} if new_caches_g is not None \
                else {}
            return (x, aux) + held, out

        if remat:
            if remat_policy == "dots":
                policy = jax.checkpoint_policies.checkpoint_dots
            elif remat_policy == "tp_outs":
                # Save only the named post-collective block outputs: the
                # backward re-runs elementwise/attention work but never
                # the partial-sum combines — minimal memory for maximal
                # collective savings.
                policy = jax.checkpoint_policies.save_only_these_names(
                    "tp_out")
            else:
                policy = None
            fn = jax.checkpoint(group_fn, policy=policy)
        else:
            fn = group_fn
        xs = {"params": stacks_}
        if caches_ is not None:
            xs["cache"] = caches_
        carry, ys = jax.lax.scan(fn, carry, xs)
        return carry, (ys.get("cache") if caches_ is not None else None)

    carry = (x, jnp.zeros((), jnp.float32))
    if track:
        carry += (jnp.zeros((2,), jnp.int32),)
    new_caches = None
    if lead is not None:
        carry, new_caches = scan(lead, [cfg.lead_spec],
                                 None if caches is None else caches[:1],
                                 carry)
        caches = None if caches is None else caches[1:]
    carry, ys = scan(stacks, cfg.pattern, caches, carry)
    if ys is not None:
        new_caches = ys if new_caches is None else new_caches + ys
    x, aux = carry[:2]
    return x, new_caches, aux, (carry[2] if track else None)
