"""Device time of the migration movers (``apply_migration`` and
``apply_reclaim``), per controller pull, from the trace: their executions
summed per device inside the traced window, the slowest device, divided
by the traced pulls.  A pull that moves nothing adds 0."""

MODULES = r"^jit_apply_(migration|reclaim)$"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["traced_pulls"]:
        return None
    per_dev = tr.modules_matching(MODULES)
    return max(per_dev.values()) / ctx["traced_pulls"] * 1e3
