"""Device time of a step in instructions of layer kind ``short_conv``: the
gated short convolution with its two projections, both passes and what
the backward pass computes a second time. Nothing where the program built
no map, or has no instruction of the kind."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.kind_ms_per_step(ctx, "short_conv")
