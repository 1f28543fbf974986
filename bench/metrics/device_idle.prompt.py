"""Share of the traced window in which no operation ran on the device, in percent; moves prompt_tok_s."""

from bench.lib import layers


def read(ctx):
    return layers.device_idle_pct(ctx)
