"""Reader ``module_percentile``: a percentile of the device durations
of one executable (an event of the trace's "XLA Modules" line whose
name matches ``module`` and, with ``contains``, in which an operation
of that kernel name ran). Spec: ``{"module": regex, "contains": kernel,
"percentile": q, "scale": factor}``."""
from perfbench import stats


def read(spec, ctx):
    xs = ctx.trace.module_durations_s(spec["module"],
                                       spec.get("contains"))
    if not xs:
        return None
    return stats.percentile(xs, spec["percentile"]) * spec.get("scale", 1.0)
