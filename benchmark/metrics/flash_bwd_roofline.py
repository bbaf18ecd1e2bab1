"""The causal flash-attention backward pass's share of its roofline in
the traced window: two and a half times the forward's operations over the
bf16 peak over the time of the kernels named ``flash_bwd_dq.<n>`` and
``flash_bwd_dkv.<n>``, two launches a pass. Nothing where the program has
no such kernels."""

from benchmark.lib import kernel_costs


def read(ctx):
    return kernel_costs.kernel_roofline_pct(
        ctx, "flash_bwd_dq|flash_bwd_dkv", kernel_costs.flash_bwd,
        launches_a_pass=2)
