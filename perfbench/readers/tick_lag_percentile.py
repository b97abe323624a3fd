"""Reader ``tick_lag_percentile``: a percentile, over the decode ticks
of the window joined to their executable on the device
(``perfbench/ticklag.py``), of ``read_after_done`` (end of the
``mx.serve_wait`` that read a tick less the end of its module: what the
read-back costs once the device is done) or of ``launch_lag`` (start of
its module less the later of the end of its ``mx.serve_dispatch`` and
the end of the executable before it: what the device waited for a tick
beyond what the host or the work before it explains).
Spec: ``{"of": "read_after_done" | "launch_lag", "module": regex,
"contains": kernel, "percentile": q, "scale": factor}`` (seconds x
scale). A trace of a program that numbers no tick gives nothing to
read."""
from perfbench import mxspans, stats, ticklag


def read(spec, ctx):
    ticks = ticklag.of(ctx, mxspans.of(ctx), spec["module"],
                       spec.get("contains"))
    xs = [getattr(t, spec["of"]) for t in ticks or ()]
    xs = [x * 1e-9 for x in xs if x is not None]
    if not xs:
        return None
    return stats.percentile(xs, spec["percentile"]) * spec.get("scale", 1.0)
