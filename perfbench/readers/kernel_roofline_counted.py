"""Reader ``kernel_roofline_counted``: like ``kernel_roofline``, with
the work taken from counts the PROGRAM gave its spans: the least time
the chip could take for that work over the time the kernels took in the
trace, in percent. Spec: ``{"kernels": [names...], "costs": module
under perfbench, "cost": key of its COSTS, "counts": {name: [span,
count key], ...}}``; each count is summed over the window's spans of
that name. A trace without the kernels or the spans (a program from
before they existed) gives nothing to read."""
import importlib

from perfbench import costs, mxspans


def read(spec, ctx):
    seconds = sum(ctx.trace.kernel_seconds(k)[0] for k in spec["kernels"])
    if seconds <= 0:
        return None
    spans = mxspans.of(ctx)
    counts = {}
    for name, (span, key) in spec["counts"].items():
        got = [s.counts[key] for s in spans.named(span)
               if key in s.counts]
        if got:
            counts[name] = sum(got)
    if not counts:
        return None
    table = importlib.import_module("perfbench." + spec["costs"]).COSTS
    cost = table[spec["cost"]](ctx.config, counts)
    least, _ = costs.roofline_seconds(cost, ctx.peaks)
    return 100.0 * (least / ctx.chips) / seconds
