"""The step as one kernel: the least time one chip could take for its
share of the window's operations (operations over the bf16 peak: the
operation side bounds, the step's bytes are not counted) over the time
the device was busy."""


def read(ctx):
    least = ctx["flops_per_image"] * ctx["images"] / ctx["chips"] \
        / ctx["peak"]["bf16_flops"]
    return 100.0 * least / ctx["trace"].busy_s()
