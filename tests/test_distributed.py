"""Distributed correctness: multi-device cases run in a subprocess (the
8-device host-platform flag must precede jax init), plus single-process
policy unit tests."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, PartitionSpec as P


def test_multidevice_suite():
    """cascade matmul/FFN vs reference (G in {1,2,4}), pipeline
    parallelism vs sequential, int8-compressed allreduce, and a fully
    sharded train step matching the single-device loss."""
    script = os.path.join(os.path.dirname(__file__),
                          "_multidevice_cases.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    res = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "ALL MULTIDEVICE OK" in res.stdout


class TestPolicyUnits:
    def _policy(self):
        from repro.distributed.sharding import ShardingPolicy
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        return ShardingPolicy(mesh=mesh, data_axes=("data",))

    def test_param_spec_rules(self):
        pol = self._policy()
        w = jnp.zeros((64, 128))
        assert pol.param_spec(("blocks", "0", "attn", "wq", "w"),
                              jnp.zeros((2, 64, 128)))[2] == "model"
        assert pol.param_spec(("blocks", "0", "attn", "wo", "w"),
                              jnp.zeros((2, 128, 64)))[1] == "model"
        assert pol.param_spec(("embed", "table"), w)[0] == "model"
        down = pol.param_spec(("blocks", "0", "mlp", "down", "w"),
                              jnp.zeros((2, 128, 64)))
        assert down[1] == "model"      # row-parallel = cascade
        moe = pol.param_spec(("blocks", "0", "moe", "gate"),
                             jnp.zeros((2, 8, 64, 32)))
        assert moe[1] == "model"       # expert parallelism

    def test_sanitize_indivisible(self):
        from repro.distributed.sharding import ShardingPolicy
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        pol = ShardingPolicy(mesh=mesh, data_axes=("data",))
        # mesh axes are size 1 -> everything divides; simulate via spec
        spec = pol._sanitize(P("model", None), (7, 3))
        assert spec == P("model", None)  # size-1 axis divides 7


def test_cells_accounting():
    """40 cells; long_500k only for the sub-quadratic archs."""
    from repro.configs import cells
    all_cells = cells()
    assert len(all_cells) == 40
    skipped = [c for c in all_cells if not c.runnable]
    assert len(skipped) == 8
    assert all(c.shape == "long_500k" for c in skipped)
    runnable_long = [c for c in all_cells
                     if c.runnable and c.shape == "long_500k"]
    assert sorted(c.arch for c in runnable_long) == [
        "jamba_v01_52b", "rwkv6_3b"]
