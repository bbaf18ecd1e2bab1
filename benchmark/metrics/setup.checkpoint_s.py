"""Seconds inside ``setup_s`` in checkpoint saves: the program's
``checkpoint`` spans that ended before the window opened (the forced save
that ends the warm-up ``fit``)."""

from benchmark.lib import scopes


def read(ctx):
    total = scopes.span_seconds("checkpoint")
    if total is None:
        return None
    inside = sum(s.dur for s in ctx["spans"] if s.name == "checkpoint")
    return max(0.0, total - inside)
