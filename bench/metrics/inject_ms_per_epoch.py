"""Host time of the driver's inject stage (traffic generation and staging
of the segment's batches onto the device), per epoch: the ``inject``
stage timer over the traced run's window, divided by its epochs."""


def read(ctx):
    st = ctx["stages"].get("inject")
    if st is None or not ctx["epochs"]:
        return None
    return st["s"] / ctx["epochs"] * 1e3
