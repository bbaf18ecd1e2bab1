"""Process start to the start of the window: imports, records, weights,
compilation or its cache, the first dispatch, the program's FLOP probe."""


def read(ctx):
    return ctx["setup_s"]
