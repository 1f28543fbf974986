"""Least time of the paged decode attention the window's steps needed (live contexts) over the device time of its gama_flash_paged_decode kernels in the trace, in percent; moves output_tok_s."""

from bench.lib import layers


def read(ctx):
    return layers.paged_decode_roofline_pct(ctx)
