"""Reader ``span_count_mean``: the mean of a count the program gave
its spans called ``span`` when it opened them, over a product of the
generator's counters. Spec: ``{"span": name, "count": key,
"under": [counter names or numbers...], "scale": factor}``."""
from perfbench import mxspans
from perfbench.readers import counter_ratio


def read(spec, ctx):
    mean = mxspans.of(ctx).count_mean(spec["span"], spec["count"])
    den = counter_ratio._product(spec.get("under", ()), ctx.counters)
    if mean is None or not den:
        return None
    return mean / den * spec.get("scale", 1.0)
