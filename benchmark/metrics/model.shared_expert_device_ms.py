"""Device time of a step in instructions of layer kind ``shared_expert``:
the gated MLP that every token of an expert layer takes beside the routed
experts (scope ``moe/shared``), both passes and what the backward pass
computes a second time. The trace's events joined by instruction name with
the program's instruction-to-layer map. Nothing where the program built no
map, or has no instruction of the kind."""

from benchmark.lib import scope_parts


def read(ctx):
    return scope_parts.ms_per_step(
        ctx, lambda name, e: e.kind == "shared_expert")
