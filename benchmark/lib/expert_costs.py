"""What the grouped products of an expert layer have to do, from the rows
routed to the experts held on this chip, and their share of the chip's
roofline from the device trace.

One expert is a gated SiLU MLP: ``(silu(m W1) * (m W3)) W2`` with ``W1``,
``W3`` ``[D, H]`` and ``W2`` ``[H, D]``. A layer that holds ``E`` experts
and is handed ``R`` rows forms three grouped products forward and six
backward (each forward product's two gradients), every one of ``R x D x
H`` multiply-adds. What the backward pass computes a second time is not
counted: a share of the roofline is of the work that had to be done.
Bytes, once a product: the ``E`` matrices of that product and its rows,
read or written, in the operands' type (the products' float32 results are
the implementation's choice and are not counted).

The rows are the program's own count (its ``moe_rows_here_frac`` gauge:
the slots that fell on experts held here over ``tokens x experts a
token``, the mean over the expert layers at the last metrics boundary), so
the share reads the same work whatever forms the products.
"""

from __future__ import annotations

from typing import Optional

from benchmark.lib import scopes

PRODUCTS_A_STEP = 9     # forward 3, backward 6


def grouped_products(rows: float, d: int, h: int, experts: int,
                     itemsize: int = 2) -> dict:
    """``{"flops", "bytes"}`` of one expert layer's nine products on
    ``rows`` rows in all."""
    return {"flops": PRODUCTS_A_STEP * 2 * rows * d * h,
            "bytes": PRODUCTS_A_STEP * itemsize
            * (experts * d * h + rows * (d + h))}


def rows_here_frac() -> Optional[float]:
    """The program's gauge, or nothing where it posts none."""
    try:
        from dml_cnn_cifar10_tpu.utils import metrics_registry
    except ImportError:
        return None
    family = metrics_registry.default_registry().get(
        "dml_moe_rows_here_frac")
    if family is None:
        return None
    return next(iter(family.values().values()), None)


def expert_roofline_pct(ctx, spec: dict, tokens_a_step: int
                        ) -> Optional[float]:
    """The least time one chip could take for the nine products of every
    expert layer on the rows really routed here (the larger of operations
    over the bf16 peak and bytes over the memory's rate) over the device
    time of the instructions of kind ``expert``. Nothing where the
    program posts no count of rows or maps no instruction to the kind."""
    took_ms = scopes.kind_ms_per_step(ctx, "expert")
    frac = rows_here_frac()
    if took_ms is None or not frac:
        return None
    layers = spec["num_hidden_layers"] - spec["num_dense_layers"]
    rows = frac * tokens_a_step * spec["num_experts_per_tok"]
    cost = grouped_products(rows, spec["hidden_size"],
                            spec["moe_intermediate_size"],
                            spec["num_experts"])
    least = layers * max(cost["flops"] / ctx["peak"]["bf16_flops"],
                         cost["bytes"] / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms / 1e3)
