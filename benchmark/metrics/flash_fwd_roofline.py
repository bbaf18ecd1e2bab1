"""The causal flash-attention forward kernel's share of its roofline in
the traced window: the half square's operations over the bf16 peak (or
its bytes over the memory's rate, whichever is larger) over the time the
kernels named ``flash_fwd.<n>`` took, the backward pass's recomputed
forwards among them. Nothing where the program has no such kernel."""

from benchmark.lib import kernel_costs


def read(ctx):
    return kernel_costs.kernel_roofline_pct(ctx, "flash_fwd",
                                            kernel_costs.flash_fwd)
