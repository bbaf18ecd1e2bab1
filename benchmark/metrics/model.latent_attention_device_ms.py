"""Device time of a step in instructions of layer kind
``latent_attention``: the multi-head latent attention sublayers (scope
``mla``: the query and latent projections, the latent's norm, the
up-projection, rotary, the flash kernels, the out-projection), both
passes and what the backward pass computes a second time. The trace's
events joined by instruction name with the program's instruction-to-layer
map (``benchmark/lib/scope_parts.py``, which prints the step's table by
kind, pass and part once a trace). Nothing where the program built no
map, or has no instruction of the kind."""

from benchmark.lib import scope_parts


def read(ctx):
    return scope_parts.ms_per_step(
        ctx, lambda name, e: e.kind == "latent_attention")
