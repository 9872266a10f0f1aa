"""Host time of the discrete-event simulation (the native C core), per
epoch: the ``des`` stage timer over the traced run's window, divided by
its epochs."""


def read(ctx):
    st = ctx["stages"].get("des")
    if st is None or not ctx["epochs"]:
        return None
    return st["s"] / ctx["epochs"] * 1e3
