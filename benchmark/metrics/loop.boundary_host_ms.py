"""Host time a metrics boundary costs outside its drain: the accuracy
pass's index draw and enqueue (``boundary_acc_dispatch``) before it, the
print, the ``train`` record and the flush with its observers
(``boundary_log``) after it; mean of each over the window's spans, summed.
The device waits for the second before its next dispatch. A window of one
boundary interval holds no whole ``boundary_log`` (the opening boundary's
began before it, the closing one's ends after it): nothing to read there,
so the cell's traffic has to trace two intervals or more."""


def read(ctx):
    means = []
    for name in ("boundary_acc_dispatch", "boundary_log"):
        durs = [s.dur for s in ctx["spans"] if s.name == name]
        if not durs:
            return None
        means.append(1e3 * sum(durs) / len(durs))
    return sum(means)
