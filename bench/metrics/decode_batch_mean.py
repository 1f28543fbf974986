"""Mean number of requests a decode step served, over the window's decode steps (step() events); moves output_tok_s."""

from bench.lib import layers


def read(ctx):
    return layers.decode_batch_mean(ctx)
