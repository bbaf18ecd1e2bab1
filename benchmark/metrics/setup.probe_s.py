"""Seconds inside ``setup_s`` in the program's ``flops_probe`` spans: its
FLOP-probe thread in every ``fit`` (the AOT compiles and the scope maps),
which the window waits for. From the program's span counter, which also
holds what ended before the window opened."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.span_seconds("flops_probe")
