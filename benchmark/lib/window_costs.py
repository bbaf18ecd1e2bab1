"""What causal flash attention under a sliding window has to do, from its
shapes, and its kernels' share of the chip's roofline from the device
trace.

A query at position ``r`` attends to keys ``r - W + 1 .. r``: itself and
the ``W - 1`` before it. Over ``S`` tokens that is the band, ``W (W + 1) /
2 + (S - W) W`` pairs of a query and a key in each head (the half square
where ``W >= S``). The band is counted whatever implements it: what a
kernel multiplies beyond it (the masked part of a block on the diagonal or
on the band's far edge, a block it visits and need not) is not counted, so
a share cannot pass 100% by counting masked pairs.

Operations and bytes a pair and a row are ``kernel_costs``' own (forward
two products of ``head_dim`` a pair, backward five; ``q``, ``k``, ``v``,
``o`` and the rows' statistics once): the window changes how many pairs
there are, not what a pair or a row costs.
"""

from __future__ import annotations

from typing import Optional

from benchmark.lib import kernel_costs


def band_pairs(s: int, window: Optional[int]) -> int:
    w = min(window or s, s)
    return w * (w + 1) // 2 + (s - w) * w


def _scaled(cost: dict, s: int, window: Optional[int]) -> dict:
    """A causal kernel's cost with the half square's pairs made the
    band's; the bytes stay (every row is still read and written once)."""
    return {"flops": cost["flops"] * band_pairs(s, window)
            // kernel_costs.causal_pairs(s), "bytes": cost["bytes"]}


def flash_window_fwd(b: int, s: int, heads: int, head_dim: int,
                     window: Optional[int], itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of one windowed causal forward kernel."""
    return _scaled(kernel_costs.flash_fwd(b, s, heads, head_dim, itemsize),
                   s, window)


def flash_window_bwd(b: int, s: int, heads: int, head_dim: int,
                     window: Optional[int], itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of one windowed causal backward pass."""
    return _scaled(kernel_costs.flash_bwd(b, s, heads, head_dim, itemsize),
                   s, window)


def config_window(spec: dict) -> int:
    """``sliding_window`` of a configuration's file (the cell's own); a
    ``ValueError`` where it states none, since the band of an unknown
    window cannot be counted."""
    window = spec.get("sliding_window")
    if not window:
        raise ValueError(f"the configuration states no sliding_window: "
                         f"{window!r}")
    return window


def window_roofline_pct(ctx, names: str, cost, spec: dict,
                        launches_a_pass: int = 1) -> Optional[float]:
    """``kernel_costs.kernel_roofline_pct`` for the kernels a call with a
    window names (``flash_window_fwd.<n>`` etc.), their cost the band's
    at the window of ``spec`` (the cell's configuration), which is read
    only once such a kernel is found. Nothing where the trace has no such
    kernel."""
    return kernel_costs.kernel_roofline_pct(
        ctx, names,
        lambda b, s, heads, d, itemsize: cost(b, s, heads, d,
                                              config_window(spec), itemsize),
        launches_a_pass)
