"""Share of the traced window in which no operation ran on a device: one
minus the union of the ``XLA Ops`` intervals over the window, the mean
over the cell's devices, in percent."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return (1.0 - tr.mean_busy_s / tr.window_s) * 100.0
