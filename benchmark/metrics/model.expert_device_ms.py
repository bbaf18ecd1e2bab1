"""Device time of a step in instructions of layer kind ``expert``: the
three grouped products of the experts held on this chip with the gate
between them, both passes and what the backward pass computes a second
time. The trace's events joined by instruction name with the program's
instruction-to-layer map. Nothing where the program built no map, or has
no instruction of the kind."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.kind_ms_per_step(ctx, "expert")
