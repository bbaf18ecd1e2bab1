"""The windowed causal flash-attention backward pass's share of its
roofline in the traced window: two and a half times the forward's
operations on the BAND's pairs over the bf16 peak over the time of the
kernels named ``flash_window_bwd_dq.<n>`` and ``flash_window_bwd_dkv.<n>``,
two launches a pass (``benchmark/lib/window_costs.py``). The window is
that of the cell's own configuration. Nothing where the program has no
such kernels."""

from benchmark.lib import window_costs


def read(ctx):
    return window_costs.window_roofline_pct(
        ctx, "flash_window_bwd_dq|flash_window_bwd_dkv",
        window_costs.flash_window_bwd, ctx["config"], launches_a_pass=2)
