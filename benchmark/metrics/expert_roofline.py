"""The experts' grouped products' share of their roofline in the traced
window: the least time for the nine products of each expert layer on the
rows the program counted as routed to the experts held here, over the
device time of layer kind ``expert`` (``benchmark/lib/expert_costs.py``).
The sizes are those of the one configuration whose cell lists this
metric. Nothing where the program posts no such count or has no such
kind."""

import json
import os

from benchmark.lib import expert_costs

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(HERE, "configs", "lfm2_8b_a1b_l5_e8.json")


def read(ctx):
    with open(CONFIG) as f:
        spec = json.load(f)
    tokens = ctx["examples"] // ctx["steps"] * spec["sequence_length"]
    return expert_costs.expert_roofline_pct(ctx, spec, tokens)
