"""Device time of a step in the data path on the device: the index
stream, the gather from the resident records and the in-scan decode
(layer kind ``decode`` of the program's instruction-to-layer map)."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.kind_ms_per_step(ctx, "decode")
