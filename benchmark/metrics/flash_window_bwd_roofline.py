"""The windowed causal flash-attention backward pass's share of its
roofline in the traced window: two and a half times the forward's
operations on the BAND's pairs over the bf16 peak over the time of the
kernels named ``flash_window_bwd_dq.<n>`` and ``flash_window_bwd_dkv.<n>``,
two launches a pass (``benchmark/lib/window_costs.py``). The window is
that of the one configuration whose cell lists this metric. Nothing where
the program has no such kernels."""

from benchmark.lib import window_costs

CONFIG = "mellum2_12b_a2p5b_l4_e8"


def read(ctx):
    return window_costs.window_roofline_pct(
        ctx, "flash_window_bwd_dq|flash_window_bwd_dkv",
        window_costs.flash_window_bwd, window_costs.config_window(CONFIG),
        launches_a_pass=2)
