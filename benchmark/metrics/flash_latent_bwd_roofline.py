"""The backward pass of causal flash attention with queries and keys of
one width and values of another (multi-head latent attention) against its
roofline in the traced window: ``2 B H pairs (3 d_qk + 2 d_v)``
operations over the bf16 peak over the time of the kernels named
``flash_bwd_dq.<n>`` and ``flash_bwd_dkv.<n>``, two launches a pass
(``benchmark/lib/latent_costs.py``). The widths are those of the cell's
own configuration. Nothing where the program has no such kernels."""

from benchmark.lib import latent_costs


def read(ctx):
    return latent_costs.latent_roofline_pct(
        ctx, "flash_bwd_dq|flash_bwd_dkv", latent_costs.flash_latent_bwd,
        ctx["config"], launches_a_pass=2)
