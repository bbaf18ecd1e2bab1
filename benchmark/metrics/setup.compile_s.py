"""Seconds inside ``setup_s`` in the program's ``compile_first_dispatch``
spans: the first dispatch of every ``fit`` (trace and compile, or the
cache). From the program's span counter, which also holds what ended
before the window opened."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.span_seconds("compile_first_dispatch")
