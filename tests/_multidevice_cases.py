"""Multi-device correctness cases, run in a subprocess with 8 host
devices (tests/test_distributed.py drives this; the flag must be set
before jax initializes, which pytest's process cannot do globally)."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.distributed.cascade import (cascade_ffn,  # noqa: E402
                                       cascade_ffn_reference, cascade_matmul)
from repro.distributed.compression import compressed_mean_flat  # noqa: E402
from repro.distributed.pipeline import pipeline_apply  # noqa: E402
from repro.distributed.sharding import ShardingPolicy  # noqa: E402


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def check_cascade():
    mesh = _mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
    for g in (1, 2, 4):
        out = cascade_matmul(x, w, mesh, g=g)
        assert float(jnp.max(jnp.abs(out - x @ w))) < 1e-4, f"matmul g={g}"
    xf = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(32, 48)), jnp.float32)
    wu = jnp.asarray(rng.normal(size=(32, 48)), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(48, 32)), jnp.float32)
    ref = cascade_ffn_reference(xf, wg, wu, wd)
    for g in (1, 2, 4):
        out = cascade_ffn(xf, wg, wu, wd, mesh, g=g)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-3, f"ffn g={g}"
    print("cascade OK")


def check_pipeline():
    mesh = _mesh((4, 2), ("pod", "data"))
    rng = np.random.default_rng(1)
    ws = jnp.asarray(rng.normal(size=(4, 8, 8)) * 0.5, jnp.float32)
    x = jnp.asarray(rng.normal(size=(6, 3, 8)), jnp.float32)
    out = pipeline_apply(lambda p, z: jnp.tanh(z @ p["w"]), {"w": ws}, x,
                         mesh, axis="pod")
    ref = x
    for s in range(4):
        ref = jnp.tanh(ref @ ws[s])
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0
    print("pipeline OK")


def check_compression():
    mesh = _mesh((8,), ("data",))
    rng = np.random.default_rng(2)
    gs = jnp.asarray(rng.normal(size=(8, 1024)), jnp.float32)

    def local(g_l):
        g = g_l[0]
        mean, err = compressed_mean_flat(g, jnp.zeros_like(g), "data", 8)
        return mean[None], err[None]

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P("data", None),),
                       out_specs=(P("data", None), P("data", None)),
                       check_vma=False)
    mean, err = fn(gs)
    true = jnp.mean(gs, axis=0)
    rel = float(jnp.max(jnp.abs(mean[0] - true)) / jnp.max(jnp.abs(true)))
    assert rel < 0.03, rel                        # int8 wire error bound
    assert float(jnp.max(jnp.abs(mean[0] - mean[5]))) == 0.0  # consistent
    # Error feedback: err equals what dequantization lost.
    assert float(jnp.max(jnp.abs(err))) < 0.05
    print("compression OK")


def check_sharded_train_step():
    """End-to-end pjit train step on a 2x4 mesh with the full policy:
    loss matches the single-device step bit-for-bit-ish."""
    from repro.models import ModelConfig, init_params, loss_fn
    from repro.models import layers as L
    from repro.optim import adamw
    from repro.training.trainer import make_train_step

    cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_head=16, d_ff=128, vocab_size=128,
                      compute_dtype="float32", cache_dtype="float32")
    mesh = _mesh((2, 4), ("data", "model"))
    policy = ShardingPolicy(mesh=mesh, data_axes=("data",), fsdp=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = adamw.AdamWConfig(lr=1e-3)
    opt = adamw.init(params)
    rng = np.random.default_rng(3)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, 128, size=(4, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 128, size=(4, 16)), jnp.int32),
    }
    step = make_train_step(cfg, opt_cfg, remat=False)
    # Reference: single-device.
    _, _, m_ref = jax.jit(step)(params, opt, batch)

    L.set_shard_hook(policy.act)
    try:
        with jax.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=(
                policy.param_sharding(params), policy.param_sharding(opt),
                policy.batch_sharding(batch)))
            _, _, m_sh = jitted(params, opt, batch)
    finally:
        L.set_shard_hook(None)
    assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-4, (
        float(m_ref["loss"]), float(m_sh["loss"]))
    print("sharded train step OK")


if __name__ == "__main__":
    check_cascade()
    check_pipeline()
    check_compression()
    check_sharded_train_step()
    print("ALL MULTIDEVICE OK")
