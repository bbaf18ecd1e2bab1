"""Share of the window the host spent blocked in the loop's fused
boundary fetch, from the program's own ``boundary_drain`` span records.
Near 100 the device bounds the loop; what is missing is host work between
dispatches."""


def read(ctx):
    drains = [s.dur for s in ctx["spans"] if s.name == "boundary_drain"]
    if not drains:
        return None
    return 100.0 * sum(drains) / ctx["window_s"]
