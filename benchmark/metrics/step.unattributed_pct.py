"""Share of the device's busy time that the instruction-to-layer map
gives to no layer kind: the tracing's own error bar."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.unattributed_pct(ctx)
