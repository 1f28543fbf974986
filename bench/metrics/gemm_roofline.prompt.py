"""Least time of the GEMM work the window's steps needed over the device time of its gama_gemm kernels in the trace, in percent; moves prompt_tok_s."""

from bench.lib import layers


def read(ctx):
    return layers.gemm_roofline_pct(ctx)
