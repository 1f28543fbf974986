"""Training launcher.

Host-scale entry point: builds the mesh over the available devices,
derives GamaPlan shardings from the policy, and runs the fault-tolerant
trainer on the synthetic pipeline.  On a real TPU pod slice the same code
path runs under `jax.distributed.initialize()`; on this host it trains
the smoke configs (or full configs with --dry_steps 0 for shape checks).

    PYTHONPATH=src python -m repro.launch.train --arch smollm_360m \
        --smoke --steps 50 --model_parallel 1

Options mirror the dry-run knobs: --schedule, --remat_policy,
--grad_compression int8 (manual-DP path), --pod_strategy {data,pipeline}.
"""

from __future__ import annotations

import argparse
import tempfile

import jax
import numpy as np

from repro import configs as C
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed.sharding import ShardingPolicy
from repro.launch.mesh import make_host_mesh
from repro.models import init_params, layers as L, param_count
from repro.optim import adamw
from repro.training.trainer import TrainConfig, Trainer, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="smollm_360m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global_batch", type=int, default=8)
    ap.add_argument("--seq_len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--model_parallel", type=int, default=1)
    ap.add_argument("--schedule", type=str, default="rs_ag",
                    choices=["rs_ag", "allreduce"])
    ap.add_argument("--remat_policy", type=str, default="tp_outs",
                    choices=["full", "dots", "tp_outs"])
    ap.add_argument("--no_remat", action="store_true")
    ap.add_argument("--ckpt_dir", type=str, default=None)
    ap.add_argument("--ckpt_every", type=int, default=25)
    args = ap.parse_args()

    cfg = C.get_smoke(args.arch) if args.smoke else C.get(args.arch)
    print(f"[train] arch={cfg.name} devices={len(jax.devices())}")

    params = init_params(jax.random.PRNGKey(0), cfg)
    print(f"[train] params: {param_count(params)/1e6:.2f}M")
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=5,
                                total_steps=args.steps)
    opt_state = adamw.init(params)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq_len,
                                  global_batch=args.global_batch))
    step = make_train_step(cfg, opt_cfg, remat=not args.no_remat,
                           remat_policy=args.remat_policy)

    shardings = None
    if len(jax.devices()) > 1 and args.model_parallel >= 1:
        mesh = make_host_mesh(model=args.model_parallel)
        policy = ShardingPolicy(mesh=mesh, data_axes=("data",),
                                schedule=args.schedule)
        L.set_shard_hook(policy.act)
        p_sh = policy.param_sharding(params)
        o_sh = policy.param_sharding(opt_state)
        # Commit the state to its shardings (jit requires matching layouts).
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)
        with jax.set_mesh(mesh):
            step_fn = jax.jit(step, in_shardings=(p_sh, o_sh, None),
                              out_shardings=(p_sh, o_sh, None))
        shardings = {"params": p_sh, "opt": o_sh}
        print(f"[train] mesh {dict(mesh.shape)} schedule={args.schedule}")
    else:
        step_fn = jax.jit(step)

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_train_")
    trainer = Trainer(
        cfg, TrainConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=ckpt_dir, log_every=5),
        opt_cfg, params, opt_state,
        lambda s: data.iterate(s), step_fn,
        shardings={"params": shardings["params"],
                   "opt": shardings["opt"]} if shardings else None)
    result = trainer.run()
    for m in result["metrics"]:
        print(f"  step {m['step']:4d} loss {m['loss']:.4f} "
              f"gnorm {m['grad_norm']:.2f} ({m['dt']*1e3:.0f} ms)")
    print(f"[train] done: steps={result['final_step']} "
          f"restarts={result['restarts']} "
          f"stragglers={len(result['straggler_events'])} ckpt={ckpt_dir}")


if __name__ == "__main__":
    main()
