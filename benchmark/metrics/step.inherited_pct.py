"""Share of the device's busy time in entries flagged ``inherited``:
copies the compiler put in, counted with the layer of their consumer. The
map's own error bar: that much of the split by kind is a guess. Nothing
where the program's map tells no part (an older program, whose flag also
covered the kernels the compiler renamed), or flags no instruction of the
trace."""

from benchmark.lib import scope_parts


def read(ctx):
    return scope_parts.pct_of_busy(ctx, lambda name, e: e.inherited)
