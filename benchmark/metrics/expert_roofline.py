"""The experts' grouped products' share of their roofline in the traced
window: the least time for the nine products of each expert layer on the
rows the program counted as routed to the experts held here, over the
device time of layer kind ``expert`` (``benchmark/lib/expert_costs.py``).
The sizes are those of the cell's own configuration. Nothing where the
program posts no such count or has no such kind."""

from benchmark.lib import expert_costs


def read(ctx):
    return expert_costs.expert_roofline_pct(ctx, ctx["config"])
