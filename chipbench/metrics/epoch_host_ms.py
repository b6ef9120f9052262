"""Milliseconds per traced epoch in which the device ran nothing while
the host was in one of the epoch's own phases (``search.epoch.args``,
``.dispatch``, ``.readback``, ``.records``: the spans the program marks
in ``run_epoch``), inside the traced window."""
from chipbench import stages


def read(ctx):
    batches = ctx.counters.get("traced_batches", 0)
    per_epoch = ctx.traffic.get("batches_per_epoch")
    idle = stages.host_idle_s(ctx.trace)
    if idle is None or not batches or not per_epoch:
        return None
    return 1e3 * idle / (batches / per_epoch)
