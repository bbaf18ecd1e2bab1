"""The windowed causal flash-attention forward kernel's share of its
roofline in the traced window: the BAND's operations over the bf16 peak
(or the kernel's bytes over the memory's rate, whichever is larger) over
the time the kernels named ``flash_window_fwd.<n>`` took
(``benchmark/lib/window_costs.py``). The window is that of the cell's own
configuration, the other shapes the event's own. Nothing where the
program has no such kernel."""

from benchmark.lib import window_costs


def read(ctx):
    return window_costs.window_roofline_pct(
        ctx, "flash_window_fwd", window_costs.flash_window_fwd, ctx["config"])
