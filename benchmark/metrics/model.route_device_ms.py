"""Device time of a step in instructions of layer kind ``route``: the
router with its choice of experts, the ordering and gathering of the rows
(dispatch) and the weighted adding back to their tokens (combine), both
passes. Nothing where the program built no map, or has no instruction of
the kind."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.kind_ms_per_step(ctx, "route")
