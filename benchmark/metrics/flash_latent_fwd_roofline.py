"""The forward kernel of causal flash attention with queries and keys of
one width and values of another (multi-head latent attention) against its
roofline in the traced window: ``2 B H pairs (d_qk + d_v)`` operations
over the bf16 peak (or the kernel's bytes over the memory's rate,
whichever is larger) over the time the kernels named ``flash_fwd.<n>``
took (``benchmark/lib/latent_costs.py``). The widths are those of the
cell's own configuration, ``B H`` and ``S`` the event's own. Nothing where
the program has no such kernel."""

from benchmark.lib import latent_costs


def read(ctx):
    return latent_costs.latent_roofline_pct(
        ctx, "flash_fwd", latent_costs.flash_latent_fwd, ctx["config"])
