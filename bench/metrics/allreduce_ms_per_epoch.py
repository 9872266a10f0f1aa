"""Device time of the all-reduces of the period program (``jit_period``
on one chip, ``jit_period_device`` on a mesh), per epoch, from the trace:
the self time of its all-reduce operations, summed per device inside the
traced window, the slowest device, divided by the traced epochs.  On a
v5e the compiler names the psums it combines ``all-reduce.N`` and keeps
a reduction it does not combine under its JAX primitive's name
(``pmax.N``); an asynchronous one would be an ``all-reduce-start`` /
``-done`` pair.  On a mesh these keep the switch's replicated counters
consistent (the directory's read and write counts); they also carry the
telemetry the period program returns every epoch, timers on or off: the
psum of the slab-work and exchange counters, folded into the combined
all-reduce, and the ``pmax`` of the exchange's bucket peak, a collective
of its own.  A program with none reads nothing."""

import re

OP = re.compile(r"^jit_period[^:]*:(all-reduce|psum|pmax|pmin)")


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["traced_epochs"]:
        return None
    per_dev = [sum(s for name, s in ops.items() if OP.match(name))
               for ops in tr.op_s.values()]
    if not any(per_dev):
        return None
    return max(per_dev) / ctx["traced_epochs"] * 1e3
