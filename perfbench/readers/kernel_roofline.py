"""Reader ``kernel_roofline``: the least time the chip could take for
the work the kernels were given (the larger of FLOPs over peak FLOP/s
and bytes over peak bytes/s, from ``perfbench/costs.py`` and
``perfbench/peaks.py``) over the time the kernels took in the trace,
in percent. Spec: ``{"kernels": [names...], "cost": name}``; the
metric file's ``bound`` records which roof the share is held to."""
from perfbench import costs


def read(spec, ctx):
    seconds = sum(ctx.trace.kernel_seconds(k)[0] for k in spec["kernels"])
    if seconds <= 0:
        return None
    cost = costs.COSTS[spec["cost"]](ctx.config, ctx.work)
    least, _ = costs.roofline_seconds(cost, ctx.peaks)
    # the work counters cover the whole window on every chip; kernel
    # seconds are an average over chips
    return 100.0 * (least / ctx.chips) / seconds
