"""Least time of the latent paged decode the window's steps needed (live contexts, the architecture's paged_decode_cost) over the device time of its gama_latent_paged_decode kernels in the trace, in percent; moves output_tok_s."""

from bench.lib import layers


def read(ctx):
    p = ctx.peaks
    least = 0.0
    for c in ctx.calls():
        if c.kind == "decode":
            flops, nbytes = ctx.arch.paged_decode_cost(ctx.shape, c.contexts)
            least += max(flops / p["bf16_flops"],
                         nbytes / p["hbm_bytes_per_s"])
    return layers.kernel_roofline_pct(ctx, "gama_latent_paged_decode", least)
