"""Share of the device's busy time in instructions of pass ``recompute``:
a layer's forward formed again in the backward pass under
``jax.checkpoint`` (a ``rematted_computation`` under a ``transpose(`` in
the instruction's name-scope path). What a ``custom_vjp`` forms again by
its own backward rule counts as ``backward``. Nothing where the program's
map tells no such pass, or holds no such instruction."""

from benchmark.lib import scope_parts


def read(ctx):
    return scope_parts.pct_of_busy(ctx, lambda name, e:
                                   e.pass_ == "recompute")
