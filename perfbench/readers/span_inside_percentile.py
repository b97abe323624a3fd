"""Reader ``span_inside_percentile``: a percentile, over the program's
spans called ``span`` that hold at least ``min`` of them, of the number
of spans called ``inside`` within one.
Spec: ``{"span": name, "inside": name, "min": n, "percentile": q}``."""
from perfbench import mxspans, stats


def read(spec, ctx):
    sp = mxspans.of(ctx)
    xs = [len(sp.inside(s, spec["inside"])) for s in sp.named(spec["span"])]
    xs = [n for n in xs if n >= spec.get("min", 0)]
    if not xs:
        return None
    return stats.percentile(xs, spec["percentile"])
