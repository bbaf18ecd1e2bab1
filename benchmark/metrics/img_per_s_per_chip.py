"""Images trained in the window over its seconds and the chips: all the
work over all the time, stalls included."""


def read(ctx):
    return ctx["images"] / ctx["window_s"] / ctx["chips"]
