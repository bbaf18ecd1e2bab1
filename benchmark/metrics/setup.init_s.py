"""Seconds inside ``setup_s`` in the program's ``trainer_init`` and
``init_or_restore`` spans: the making of the ``Trainer`` (mesh, logger,
sharding tree, step builders) and the initial state with its restore walk,
both before the first ``fit``. From the program's span counter. Nothing
where the program posts neither span."""

from benchmark.lib import scopes


def read(ctx):
    found = [scopes.span_seconds(name)
             for name in ("trainer_init", "init_or_restore")]
    if all(s is None for s in found):
        return None
    return sum(s for s in found if s is not None)
