"""The forward kernel of causal flash attention with queries and keys of
one width and values of another (multi-head latent attention) against its
roofline in the traced window: ``2 B H pairs (d_qk + d_v)`` operations
over the bf16 peak (or the kernel's bytes over the memory's rate,
whichever is larger) over the time the kernels named ``flash_fwd.<n>``
took (``benchmark/lib/latent_costs.py``). The widths are those of the one
configuration whose cell lists this metric, ``B H`` and ``S`` the event's
own. Nothing where the program has no such kernel."""

from benchmark.lib import latent_costs

CONFIG = "deepseek_v2_lite_l5_e8"


def read(ctx):
    return latent_costs.latent_roofline_pct(
        ctx, "flash_fwd", latent_costs.flash_latent_fwd,
        latent_costs.config_widths(CONFIG))
