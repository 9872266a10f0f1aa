"""Device time of the sharded data plane's exchange, per epoch, from the
trace: the self time of the period program's (``jit_period*``)
all-to-all and all-gather operations, summed per device inside the
traced window, the slowest device, divided by the traced epochs.  The
all-to-alls carry the read round, its replies and the chain's write
rounds; the all-gathers rebuild the batch's routing decision on every
device for the replicated observe.  A v5e names them ``all_to_all.N``
and ``all-gather.N`` (an asynchronous one would be a ``-start`` /
``-done`` pair); a program with none, as on one chip, reads nothing."""

import re

OP = re.compile(r"^jit_period[^:]*:(all[-_]to[-_]all|all[-_]gather)")


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["traced_epochs"]:
        return None
    per_dev = [sum(s for name, s in ops.items() if OP.match(name))
               for ops in tr.op_s.values()]
    if not any(per_dev):
        return None
    return max(per_dev) / ctx["traced_epochs"] * 1e3
