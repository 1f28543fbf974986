"""Dry-run cell construction: step functions, input specs, shardings,
lower+compile, and roofline extraction.  Importable without touching jax
device state — the 512-device placeholder env var is set only by
launch/dryrun.py (the CLI entry point).
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs as C
from repro.analysis import hlo as hlo_mod
from repro.analysis.roofline import compute_roofline
from repro.core import hw
from repro.distributed.sharding import ShardingPolicy
from repro.models import (decode_step, init_cache, init_params, prefill)
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.training.trainer import make_train_step

# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------


def input_specs(arch_id: str, shape_id: str,
                cfg: Optional[ModelConfig] = None) -> Dict[str, Any]:
    """Batch ShapeDtypeStructs for one (arch x shape) cell.

    train:   {tokens/embeds, labels [, positions, enc_embeds]}
    prefill: {tokens/embeds [, positions, enc_embeds]}
    decode:  {token (B,), pos ()}  (cache specs come from init_cache)
    """
    cfg = cfg or C.get(arch_id)
    spec = C.SHAPES[shape_id]
    b, s = spec["global_batch"], spec["seq_len"]
    kind = spec["kind"]
    i32 = jnp.int32
    cd = cfg.cdtype

    def tok(shape):
        return jax.ShapeDtypeStruct(shape, i32)

    batch: Dict[str, Any] = {}
    if kind in ("train", "prefill"):
        if cfg.frontend == "vision":
            batch["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), cd)
            batch["positions"] = tok((b, s, 3))
        else:
            batch["tokens"] = tok((b, s))
        if cfg.encoder_decoder:
            # Frame embeddings from the (stubbed) speech frontend.
            batch["enc_embeds"] = jax.ShapeDtypeStruct(
                (b, s, cfg.d_model), cd)
        if kind == "train":
            batch["labels"] = tok((b, s))
        return batch
    # decode
    return {"token": tok((b,)), "pos": jax.ShapeDtypeStruct((), i32)}


# ---------------------------------------------------------------------------
# Analytic FLOPs (loop fraction + MODEL_FLOPS for the roofline)
# ---------------------------------------------------------------------------


def analytic_flops(cfg: ModelConfig, batch: int, seq: int,
                   kind: str) -> Dict[str, float]:
    """Forward FLOPs split into per-group (in-scan) and out-of-scan parts.

    Training multiplies by 3 (fwd + 2x bwd); remat adds one more forward
    for in-scan work (jax.checkpoint on the group).
    """
    t = batch * (seq if kind in ("train", "prefill") else 1)
    kv_ctx = seq  # decode attends to the full cached context
    d, dh = cfg.d_model, cfg.d_head

    def attn_flops():
        proj = 2 * t * d * (cfg.n_heads + 2 * cfg.n_kv_heads) * dh \
            + 2 * t * cfg.n_heads * dh * d
        if kind == "decode":
            av = 4 * t * kv_ctx * cfg.n_heads * dh
        else:
            av = 4 * t * seq * cfg.n_heads * dh / 2  # causal half
        return proj + av

    def ffn_flops(kind_):
        if kind_ == "dense":
            mult = 3 if cfg.ffn_kind == "swiglu" else 2
            return 2 * t * mult * d * cfg.d_ff
        if kind_ == "moe":
            m = cfg.moe
            active = m.top_k + (m.n_shared or 0)
            return 2 * t * (d * m.num_experts
                            + active * 3 * d * m.d_ff)
        if kind_ == "rwkv_cm":
            return 2 * t * (2 * d * cfg.d_ff + d * d)
        return 0.0

    def mixer_flops(kind_):
        if kind_ == "attn":
            return attn_flops()
        if kind_ == "mamba":
            mc = cfg.mamba
            di = mc.expand * d
            proj = 2 * t * (d * 2 * di + di * (mc.resolve_dt_rank(d)
                                               + 2 * mc.d_state)
                            + mc.resolve_dt_rank(d) * di + di * d)
            scan = 6 * t * di * mc.d_state
            return proj + scan
        if kind_ == "rwkv":
            rc = cfg.rwkv
            n = rc.head_size
            proj = 2 * t * 5 * d * d
            wkv = 4 * t * (d // n) * n * n
            return proj + wkv
        return 0.0

    group = sum(mixer_flops(s.mixer) + ffn_flops(s.ffn)
                for s in cfg.pattern)
    nonloop = 2 * t * d * cfg.vocab_size          # logits
    if cfg.encoder_decoder and kind != "decode":
        enc_t = batch * seq
        enc_layer = (2 * enc_t * d * (cfg.n_heads + 2 * cfg.n_kv_heads) * dh
                     + 2 * enc_t * cfg.n_heads * dh * d
                     + 4 * enc_t * seq * cfg.n_heads * dh / 2
                     + 2 * enc_t * 3 * d * cfg.d_ff)
        nonloop += enc_layer * cfg.n_encoder_layers
        cross = (2 * t * d * (cfg.n_heads + 2 * cfg.n_kv_heads) * dh
                 + 2 * t * cfg.n_heads * dh * d
                 + 4 * t * seq * cfg.n_heads * dh)
        group += cross * len(cfg.pattern)

    mult = 3.0 if kind == "train" else 1.0
    return {
        "group_fwd": group,
        "nonloop_fwd": nonloop,
        "total": mult * (group * cfg.n_groups + nonloop),
        "loop_fraction_counted_once":
            group / max(group + nonloop, 1.0),
        "tokens": float(t),
    }


# ---------------------------------------------------------------------------
# Cell assembly
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    cfg: ModelConfig
    fn: Any
    args: Tuple
    in_shardings: Tuple
    kind: str
    trips: int
    meta: Dict[str, Any]


def build_cell(arch_id: str, shape_id: str, mesh, *,
               schedule: str = "rs_ag", fsdp: bool = True,
               remat: bool = True, rope_dtype: str = "float32",
               moe_groups: int = 1, remat_policy: str = "full",
               serve_dtype: Optional[str] = None,
               train_dtype: Optional[str] = None) -> Cell:
    import dataclasses as _dc
    cfg = C.get(arch_id)
    if moe_groups > 1 and cfg.moe is not None:
        cfg = _dc.replace(cfg, moe=_dc.replace(
            cfg.moe, dispatch_groups=moe_groups))
    kind0 = C.SHAPES[shape_id]["kind"]
    if serve_dtype and kind0 in ("prefill", "decode"):
        # Serving runs quantized/bf16 weights (no optimizer states).
        cfg = _dc.replace(cfg, param_dtype=serve_dtype)
    master_weights = False
    if train_dtype and kind0 == "train":
        # bf16 live params + f32 master in the optimizer shard.
        cfg = _dc.replace(cfg, param_dtype=train_dtype)
        master_weights = train_dtype != "float32"
    from repro.models import layers as _L
    _L.set_rope_dtype(rope_dtype)
    spec = C.SHAPES[shape_id]
    b, s = spec["global_batch"], spec["seq_len"]
    kind = spec["kind"]
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    policy = ShardingPolicy(mesh=mesh, data_axes=data_axes, fsdp=fsdp,
                            schedule=schedule)
    # Install the activation-sharding hook (models call shard_hint).
    from repro.models import layers as L
    L.set_shard_hook(policy.act)

    rng = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda r: init_params(r, cfg), rng)
    params_sh = policy.param_sharding(params_shape)
    batch_specs = input_specs(arch_id, shape_id, cfg)

    if kind == "train":
        opt_cfg = adamw.AdamWConfig(master_weights=master_weights)
        opt_shape = jax.eval_shape(
            lambda ps: adamw.init(ps, master_weights), params_shape)
        opt_sh = policy.param_sharding_opt(opt_shape) \
            if hasattr(policy, "param_sharding_opt") \
            else policy.param_sharding(opt_shape)
        step = make_train_step(cfg, opt_cfg, remat=remat,
                               remat_policy=remat_policy)
        args = (params_shape, opt_shape, batch_specs)
        in_sh = (params_sh, opt_sh, policy.batch_sharding(batch_specs))
        fn = step
    elif kind == "prefill":
        caches_shape = jax.eval_shape(
            lambda: init_cache(cfg, b, s, enc_len=s if cfg.encoder_decoder
                               else 0))
        cache_sh = policy.cache_sharding(caches_shape, b)
        fn = lambda p, bt, c: prefill(p, bt, cfg, c)  # noqa: E731
        args = (params_shape, batch_specs, caches_shape)
        in_sh = (params_sh, policy.batch_sharding(batch_specs), cache_sh)
    else:  # decode
        caches_shape = jax.eval_shape(
            lambda: init_cache(cfg, b, s,
                               enc_len=4096 if cfg.encoder_decoder else 0))
        cache_sh = policy.cache_sharding(caches_shape, b)
        fn = lambda p, t, pos, c: decode_step(p, t, pos, cfg, c)  # noqa
        args = (params_shape, batch_specs["token"], batch_specs["pos"],
                caches_shape)
        tok_sh = policy.batch_sharding({"token": batch_specs["token"]})
        in_sh = (params_sh, tok_sh["token"],
                 NamedSharding(mesh, P()), cache_sh)

    af = analytic_flops(cfg, b, s, kind)
    return Cell(arch=arch_id, shape=shape_id, cfg=cfg, fn=fn, args=args,
                in_shardings=in_sh, kind=kind, trips=cfg.n_groups,
                meta={"analytic": af, "batch": b, "seq": s,
                      "schedule": schedule, "fsdp": fsdp})


def run_cell(arch_id: str, shape_id: str, *, multi_pod: bool = False,
             schedule: str = "rs_ag", fsdp: bool = True,
             remat: bool = True, rope_dtype: str = "float32",
             moe_groups: int = 1, remat_policy: str = "full",
             serve_dtype: Optional[str] = None,
             train_dtype: Optional[str] = None,
             keep_hlo: bool = False) -> Dict[str, Any]:
    """Lower + compile one cell on the production mesh; return the record."""
    from repro.launch.mesh import make_production_mesh
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cell = build_cell(arch_id, shape_id, mesh, schedule=schedule,
                      fsdp=fsdp, remat=remat, rope_dtype=rope_dtype,
                      moe_groups=moe_groups, remat_policy=remat_policy,
                      serve_dtype=serve_dtype, train_dtype=train_dtype)

    t0 = time.monotonic()
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings)
        lowered = jitted.lower(*cell.args)
        t_lower = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo_text = compiled.as_text()
    coll = hlo_mod.parse_collectives(hlo_text, loop_trip_count=cell.trips)

    af = cell.meta["analytic"]
    terms = compute_roofline(
        arch=arch_id, shape=shape_id,
        mesh_name="2x16x16" if multi_pod else "16x16", chips=chips,
        cost=cost, collectives=coll, loop_trip_count=cell.trips,
        loop_flop_fraction=af["loop_fraction_counted_once"],
        tokens=af["tokens"],
        n_active_params=cell.cfg.n_active_params(),
        training=cell.kind == "train",
        peak_bytes_per_chip=float(mem.argument_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  + mem.output_size_in_bytes))

    record = {
        "arch": arch_id, "shape": shape_id,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": cell.kind, "chips": chips,
        "schedule": schedule, "fsdp": fsdp, "remat": remat,
        "rope_dtype": rope_dtype, "moe_groups": moe_groups,
        "remat_policy": remat_policy, "serve_dtype": serve_dtype,
        "train_dtype": train_dtype,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost_analysis": {k: v for k, v in cost.items()
                          if k in ("flops", "bytes accessed",
                                   "transcendentals")},
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_per_device_gib": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes) / 2**30, 3),
        },
        "collectives": {
            "bytes_by_op": coll.bytes_by_op,
            "count_by_op": coll.count_by_op,
            "total_bytes_per_device": coll.total_bytes,
            "bf16_equivalent_bytes_per_device": coll.bf16_equivalent_bytes,
        },
        "analytic": af,
        "roofline": terms.as_dict(),
    }
    if keep_hlo:
        record["hlo_size_bytes"] = len(hlo_text)
    return record
