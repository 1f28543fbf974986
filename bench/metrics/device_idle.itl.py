"""Share of the traced window in which no operation ran on the device, in percent; moves itl_p95_ms."""

from bench.lib import layers


def read(ctx):
    return layers.device_idle_pct(ctx)
