"""The whole step's share of the chip's peak: operations the images of the
window need (the configuration's count from shapes) over the window's
seconds, the chips and the bf16 peak."""


def read(ctx):
    return 100.0 * ctx["flops_per_image"] * ctx["images"] \
        / ctx["window_s"] / ctx["chips"] / ctx["peak"]["bf16_flops"]
