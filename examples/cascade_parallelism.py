"""Cascade parallelism demo — the paper's pack as TPU collectives.

Runs on 8 host-CPU devices (the device-count flag is set in this
process before jax initializes; no child process is started): a
K-sharded GEMM whose partial sums combine via subgroup reduce-scatter
(the cascade), swept over pack sizes G like the paper's Fig. 6, plus the
planner's cost-model view of the same sweep for the production mesh.

    PYTHONPATH=src JAX_PLATFORMS=cpu python examples/cascade_parallelism.py
"""

import os

# Must precede jax initialization; an explicit device-count flag from
# the caller wins.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.core import planner  # noqa: E402
from repro.distributed.cascade import (cascade_ffn,  # noqa: E402
                                       cascade_ffn_reference)


def main() -> None:
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    rng = np.random.default_rng(0)
    t, d, f = 32, 64, 256
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(d, f)), jnp.float32)
    wu = jnp.asarray(rng.normal(size=(d, f)), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(f, d)), jnp.float32)
    ref = cascade_ffn_reference(x, wg, wu, wd)
    print("cascade FFN on a 2x4 mesh (model axis W=4):")
    for g in (1, 2, 4):
        out = cascade_ffn(x, wg, wu, wd, mesh, g=g)
        err = float(jnp.max(jnp.abs(out - ref)))
        print(f"  G={g} (X={4//g}): maxerr vs reference = {err:.2e}")

    print("\nplanner's Fig.6-style sweep for the production 16x16 mesh "
          "(kimi-k2 expert FFN):")
    site = planner.GemmSite("expert_ffn", m=1_048_576, k=7168, n=2048 * 8)
    for c in planner.plan_cascade(site, data_axis=16, model_axis=16):
        print(f"  G={c.g:2d} X={c.x:2d}: compute {c.compute_s*1e3:7.2f} ms, "
              f"hbm {c.hbm_s*1e3:6.2f} ms, cascade-ICI {c.ici_s*1e3:7.2f} ms"
              f" -> step {c.step_s*1e3:7.2f} ms  gamma={c.gamma:.2f}")
    best = planner.best_cascade(site, 16, 16)
    print(f"  planner picks G={best.g} "
          f"(compute-bound: keep the combine local)")


if __name__ == "__main__":
    main()
