"""Share of the device's busy time in fusions of several layers, which
go whole to the layer of their root: how much of the per-kind split
(``model.conv_device_ms`` against ``model.norm_act_device_ms``) is the
compiler's fusion and not the layers'."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.mixed_pct(ctx)
