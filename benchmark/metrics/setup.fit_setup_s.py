"""Seconds inside ``setup_s`` in the program's ``fit_setup`` spans: the
set-up of every ``fit`` before the window (restore, iterators, resident
arrays, step functions). From the program's span counter, which also
holds what ended before the window opened."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.span_seconds("fit_setup")
