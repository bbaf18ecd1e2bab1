"""Device time under the step's ``optimizer`` scope for each step. Where
no instruction of the trace carries the scope (the update fused into its
neighbours as an XLA expression) there is nothing to read."""

from benchmark.lib import xplane


def read(ctx):
    try:
        return 1e6 * ctx["trace"].scope_s("optimizer") / ctx["steps"]
    except xplane.NotInTrace:
        return None
