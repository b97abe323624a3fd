"""Reader ``span_percentile``: a percentile of a sample of spans the
generator or the program recorded on the host clock.
Spec: ``{"span": name, "percentile": q, "scale": factor}``."""
from perfbench import stats


def read(spec, ctx):
    xs = ctx.spans.get(spec["span"])
    if not xs:
        return None
    return stats.percentile(xs, spec["percentile"]) * spec.get("scale", 1.0)
