"""Share of the device's busy time in instructions of the backward pass
(a ``transpose(`` in the instruction's name-scope path)."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.pass_pct(ctx, "backward")
