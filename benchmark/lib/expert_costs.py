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

from typing import NamedTuple, Optional

from benchmark.lib import scopes

PRODUCTS_A_STEP = 9     # forward 3, backward 6
# keys of which any one says that a configuration has routed experts
EXPERT_KEYS = ("num_experts", "n_routed_experts", "num_experts_per_tok",
               "moe_intermediate_size")


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


class ExpertLayers(NamedTuple):
    """What a configuration's expert layers are, as far as their grouped
    products go."""

    layers: int      # layers with routed experts
    held: int        # experts held on this chip, in each of them
    per_token: int   # experts a token is routed to
    hidden: int      # a row's width, ``D``
    width: int       # an expert's inner width, ``H``


def _need(spec: dict, key: str):
    if spec.get(key) is None:
        raise ValueError(f"the configuration states routed experts but no "
                         f"{key!r}")
    return spec[key]


def expert_layers(spec: dict) -> Optional[ExpertLayers]:
    """The expert layers of a configuration's file, read from its own
    keys: which layers route (LFM2's ``num_dense_layers`` leading dense
    layers; Mellum2's ``mlp_layer_types``, ``dense`` or ``sparse`` a
    layer; DeepSeek-V2's ``first_k_dense_replace`` with ``moe_layer_freq``)
    and how many experts are held here (``num_experts`` or
    ``n_routed_experts``). Nothing for a configuration that states no
    routed experts. A ``ValueError`` where it states them and the layers or
    the held experts cannot be read, or two of its keys disagree: a
    reading of the wrong layers would cost the wrong work without a
    sign."""
    if not any(k in spec for k in EXPERT_KEYS):
        return None
    n = _need(spec, "num_hidden_layers")
    layers = set()
    if "mlp_layer_types" in spec:
        kinds = spec["mlp_layer_types"]
        if len(kinds) != n or not set(kinds) <= {"dense", "sparse"}:
            raise ValueError(f"mlp_layer_types {kinds!r} is not one of "
                             f"'dense' or 'sparse' for each of {n} layers")
        layers.add(kinds.count("sparse"))
    if "first_k_dense_replace" in spec:
        first, every = spec["first_k_dense_replace"], \
            _need(spec, "moe_layer_freq")
        layers.add(sum(1 for i in range(first, n) if i % every == 0))
    if "num_dense_layers" in spec:
        layers.add(n - spec["num_dense_layers"])
    held = {spec[k] for k in ("num_experts", "n_routed_experts")
            if k in spec}
    if len(layers) != 1:
        raise ValueError("the configuration's keys give no one count of "
                         f"its expert layers: {sorted(layers)}")
    if len(held) != 1:
        raise ValueError("the configuration's keys give no one count of "
                         f"the experts held: {sorted(held)}")
    return ExpertLayers(layers.pop(), held.pop(),
                        _need(spec, "num_experts_per_tok"),
                        _need(spec, "hidden_size"),
                        _need(spec, "moe_intermediate_size"))


def expert_roofline_pct(ctx, spec: dict) -> Optional[float]:
    """The least time one chip could take for the nine products of every
    expert layer of ``spec`` (the cell's configuration) on the rows really
    routed here (the larger of operations over the bf16 peak and bytes
    over the memory's rate) over the device time of the instructions of
    kind ``expert``. A step's tokens are its sequences (``examples`` over
    ``steps``) of the configuration's ``sequence_length``. Nothing where
    the configuration states no experts, the program posts no count of
    rows or maps no instruction to the kind."""
    experts = expert_layers(spec)
    took_ms = scopes.kind_ms_per_step(ctx, "expert")
    frac = rows_here_frac()
    if experts is None or took_ms is None or not frac:
        return None
    tokens = ctx["examples"] // ctx["steps"] * spec["sequence_length"]
    rows = frac * tokens * experts.per_token
    cost = grouped_products(rows, experts.hidden, experts.width,
                            experts.held)
    least = experts.layers * max(
        cost["flops"] / ctx["peak"]["bf16_flops"],
        cost["bytes"] / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (took_ms / 1e3)
