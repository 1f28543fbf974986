"""Model operations the window's served tokens need (GEMMs and attention, from shapes) over the traced window at the bf16 peak, in percent; moves prompt_tok_s."""

from bench.lib import layers


def read(ctx):
    return layers.mfu_pct(ctx)
