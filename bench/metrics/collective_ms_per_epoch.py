"""Device time of the collectives (the ``all-to-all`` and ``all-gather``
operations of the sharded data plane), per epoch, from the trace: summed
per device inside the traced window, the slowest device, divided by the
traced epochs."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["traced_epochs"] or not any(
            tr.collective_s.values()):
        return None
    return max(tr.collective_s.values()) / ctx["traced_epochs"] * 1e3
