"""Device time of a step in instructions of layer kind
``window_attention``: the attention sublayers that run under a sliding
window (scope ``attn_window``: projections, rotary, the windowed flash
kernels, the out-projection), both passes and what the backward pass
computes a second time. The trace's events joined by instruction name
with the program's instruction-to-layer map. Nothing where the program
built no map, or has no instruction of the kind."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.kind_ms_per_step(ctx, "window_attention")
