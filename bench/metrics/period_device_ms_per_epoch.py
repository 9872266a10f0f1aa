"""Device time of the period program (route, store apply or the sharded
data plane, observe: ``jit_period`` on one chip, ``jit_period_device`` on
a mesh), per epoch, from the trace: the module's executions summed per
device inside the traced window, the slowest device, divided by the
traced epochs."""

MODULES = r"^jit_period"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["traced_epochs"]:
        return None
    per_dev = tr.modules_matching(MODULES)
    if not any(per_dev.values()):
        return None
    return max(per_dev.values()) / ctx["traced_epochs"] * 1e3
