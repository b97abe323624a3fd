"""Reader ``span_self_percentile``: a percentile, over the program's
spans called ``span`` that lie wholly in the window, of the span's
duration less the time of its children called any of ``less``.
Spec: ``{"span": name, "less": [names...], "percentile": q,
"scale": factor}`` (seconds x scale)."""
from perfbench import mxspans, stats


def read(spec, ctx):
    sp = mxspans.of(ctx)
    xs = [sp.self_ns(s, spec.get("less", ())) * 1e-9
          for s in sp.named(spec["span"], whole=True)]
    if not xs:
        return None
    return stats.percentile(xs, spec["percentile"]) * spec.get("scale", 1.0)
