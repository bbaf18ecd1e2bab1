"""What causal flash attention with queries and keys of one width and
values of another has to do (multi-head latent attention: ``d_qk`` 192
over ``d_v`` 128), from its shapes, and its kernels' share of the chip's
roofline from the device trace.

Pairs: the half square, ``S (S + 1) / 2`` a head (``kernel_costs``'), and
each product counted at its own width:

- forward: the score ``q k^T`` at ``d_qk`` and the value ``p v`` at
  ``d_v``: ``2 B H pairs (d_qk + d_v)`` operations. Bytes: ``q`` and
  ``k`` read at ``d_qk``, ``v`` read and ``o`` written at ``d_v``, in the
  operands' type, and the row's log-sum-exp in float32.
- backward: the score again, ``dQ = dS K`` and ``dK = dS^T Q`` at
  ``d_qk``, ``dP = dO V^T`` and ``dV = P^T dO`` at ``d_v``: ``2 B H pairs
  (3 d_qk + 2 d_v)``, which is ``kernel_costs``' two and a half times the
  forward only where the widths are one. The program's two kernels form
  the score and ``dP`` in each; the two extra are the implementation's and
  are not counted. Bytes: ``q``, ``k``, ``dq``, ``dk`` at ``d_qk``, ``v``,
  ``o``, ``do``, ``dv`` at ``d_v``, the log-sum-exp and ``delta`` rows in
  float32.

The widths are the configuration's (its file's ``qk_nope_head_dim +
qk_rope_head_dim`` and ``v_head_dim``); ``B H`` and ``S`` are read from the
kernel's own event, whose first 3-D array is a ``[B H, S, .]`` one either
way (the output leads a custom call's text, and it is ``d_v`` wide).
"""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark.lib import kernel_costs


def flash_latent_fwd(b: int, s: int, heads: int, d_qk: int, d_v: int,
                     itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of one causal forward kernel."""
    rows = b * heads * s
    return {"flops": 2 * b * heads * kernel_costs.causal_pairs(s)
            * (d_qk + d_v),
            "bytes": rows * (2 * d_qk + 2 * d_v) * itemsize + rows * 4}


def flash_latent_bwd(b: int, s: int, heads: int, d_qk: int, d_v: int,
                     itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of one causal backward pass (``dq`` and
    ``dk``/``dv``, however many kernels form them)."""
    rows = b * heads * s
    return {"flops": 2 * b * heads * kernel_costs.causal_pairs(s)
            * (3 * d_qk + 2 * d_v),
            "bytes": rows * (4 * d_qk + 4 * d_v) * itemsize + 2 * rows * 4}


def config_widths(spec: dict) -> Tuple[int, int]:
    """``(d_qk, d_v)`` of a configuration's file (the cell's own)."""
    return spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"], \
        spec["v_head_dim"]


def latent_roofline_pct(ctx, names: str, cost, spec: dict,
                        launches_a_pass: int = 1) -> Optional[float]:
    """``kernel_costs.kernel_roofline_pct`` for the kernels named
    ``<one of names>.<n>``, each product at the widths ``(d_qk, d_v)`` of
    ``spec`` (the cell's configuration), read only once such a kernel is
    found, and not at the width the event's first array has. Nothing where
    the trace has no such kernel."""
    return kernel_costs.kernel_roofline_pct(
        ctx, names,
        lambda b, s, heads, d, itemsize: cost(b, s, heads,
                                              *config_widths(spec),
                                              itemsize),
        launches_a_pass)
