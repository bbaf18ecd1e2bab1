"""Share of the window in which a collective ran on a device and nothing
else did there. A program on one chip has no collective to read."""

from benchmark.lib import xplane


def read(ctx):
    try:
        return 100.0 * ctx["trace"].exposed_collective_s() / ctx["window_s"]
    except xplane.NotInTrace:
        return None
