"""Device time of a step in what a looped decoder adds to a plain one: the
exit heads, the exit gates and the loss over the exits (layer kinds
``exit_head`` and, for the scope ``loss``, ``dense``), both passes. Nothing
where the program built no map or has no exit head."""

from benchmark.lib import scopes


def read(ctx):
    head = scopes.kind_ms_per_step(ctx, "exit_head")
    if head is None:
        return None
    return head + (scopes.kind_ms_per_step(ctx, "dense") or 0.0)
