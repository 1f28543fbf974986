"""Median host wall time of the window's step() calls; moves itl_p95_ms."""

from bench.lib import layers


def read(ctx):
    return layers.step_p50_ms(ctx)
