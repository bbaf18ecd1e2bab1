"""What a kernel has to do, from its shapes, and its share of the chip's
roofline from the device trace.

Causal flash attention over ``B`` sequences of ``S`` tokens in ``heads``
heads of ``head_dim``. A query attends to itself and what comes before
it: ``S (S + 1) / 2`` pairs of a query and a key in each head, the half
square. What a kernel multiplies beyond that (the masked half of a block
on the diagonal) is not counted: a share of the roofline is of the work
that had to be done.

- forward: two products a pair (the score, the value), ``head_dim``
  multiply-adds each. Bytes: ``q``, ``k``, ``v`` read and ``o`` written
  once in the operands' type, the row's log-sum-exp in float32.
- backward: five products a pair (the score again, ``dP``, ``dV``,
  ``dQ``, ``dK``): two and a half times the forward. The program's two
  backward kernels form the score and ``dP`` in each, seven products in
  all; the two extra are the implementation's and are not counted. Bytes:
  ``q``, ``k``, ``v``, ``o``, ``do`` read, ``dq``, ``dk``, ``dv`` written,
  the log-sum-exp and ``delta`` rows read in float32.
"""

from __future__ import annotations

import re
from typing import Callable, Optional

_SHAPE3 = re.compile(r"\b(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4}


def causal_pairs(s: int) -> int:
    return s * (s + 1) // 2


def flash_fwd(b: int, s: int, heads: int, head_dim: int,
              itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of one causal forward kernel."""
    rows = b * heads * s
    return {"flops": 2 * 2 * head_dim * b * heads * causal_pairs(s),
            "bytes": 4 * rows * head_dim * itemsize + rows * 4}


def flash_bwd(b: int, s: int, heads: int, head_dim: int,
              itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of one causal backward pass (``dq`` and
    ``dk``/``dv``, however many kernels form them)."""
    rows = b * heads * s
    return {"flops": 5 * 2 * head_dim * b * heads * causal_pairs(s),
            "bytes": 8 * rows * head_dim * itemsize + 2 * rows * 4}


def least_seconds(cost: dict, peak: dict) -> float:
    """The roofline: the larger of operations over the bf16 peak and bytes
    over the memory's rate."""
    return max(cost["flops"] / peak["bf16_flops"],
               cost["bytes"] / peak["hbm_bytes_per_s"])


def kernel_roofline_pct(ctx, names: str, cost: Callable,
                        launches_a_pass: int = 1) -> Optional[float]:
    """The share of their roofline that the kernels whose instruction is
    named ``<one of names>.<n>`` reached in the traced window: the least
    time their launches could take over the time they took, on one device,
    the mean over the device planes. ``cost(b*heads, s, 1, head_dim,
    itemsize)`` counts one pass; a pass is ``launches_a_pass`` launches
    (the backward's two kernels). The shapes are read from the kernel's
    own event, ``<type>[b*heads, s, head_dim]``. Nothing where no event of
    the trace has such a name or states such a shape."""
    trace = ctx["trace"]
    if trace is None:
        return None
    named = re.compile(r"^(" + names + r")(\.\d+)?$")
    shares = []
    for plane in trace.planes:
        ops = plane.matching(lambda o: bool(named.match(o.name)))
        shape = next((m for m in (_SHAPE3.search(o.text) for o in ops)
                      if m), None)
        if not ops or shape is None:
            return None
        bh, s, d = (int(shape.group(i)) for i in (2, 3, 4))
        one = least_seconds(cost(bh, s, 1, d, _ITEM[shape.group(1)]),
                            ctx["peak"])
        took = sum(o.end - o.start for o in ops) / 1e9
        shares.append(100.0 * one * len(ops) / launches_a_pass / took)
    return sum(shares) / len(shares)
