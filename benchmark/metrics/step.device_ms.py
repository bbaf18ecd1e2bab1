"""Device busy time of the traced window for each step in it (the union
of the intervals in which an operation ran, averaged over the chips)."""


def read(ctx):
    return 1e3 * ctx["trace"].busy_s() / ctx["steps"]
