"""Reader ``kernel_roofline_mixed``: like ``kernel_roofline_counted``,
with the work taken from the generator's counters (``ctx.work``) and,
laid over them, from counts the PROGRAM gave its spans: the least time
the chip could take for that work over the time the kernels took in the
trace, in percent. Spec: ``{"kernels": [names...], "costs": module
under perfbench, "cost": key of its COSTS, "counts": {name: [span,
count key], ...} (optional)}``. A trace without the kernels, or without
a count the spec names, gives nothing to read."""
import importlib

from perfbench import costs, mxspans


def counted(spec, ctx):
    """``ctx.work`` with the spec's span counts (each summed over the
    window's spans of that name) laid over it; None where one is
    missing."""
    work = dict(ctx.work)
    for name, (span, key) in spec.get("counts", {}).items():
        got = [s.counts[key] for s in mxspans.of(ctx).named(span)
               if key in s.counts]
        if not got:
            return None
        work[name] = sum(got)
    return work


def read(spec, ctx):
    seconds = sum(ctx.trace.kernel_seconds(k)[0] for k in spec["kernels"])
    work = counted(spec, ctx) if seconds > 0 else None
    if work is None:
        return None
    table = importlib.import_module("perfbench." + spec["costs"]).COSTS
    least, _ = costs.roofline_seconds(table[spec["cost"]](ctx.config, work),
                                      ctx.peaks)
    return 100.0 * (least / ctx.chips) / seconds
