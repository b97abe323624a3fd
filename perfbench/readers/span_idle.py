"""Reader ``span_idle``: time in which no operation ran on the device
while the host was inside the program's spans called ``span`` (less
the parts inside spans called any of ``less``), or with ``outside``
while it was in none of them; divided by the number of ``per`` spans.
Spec: ``{"span": name, "less": [names...], "outside": bool,
"per": name, "scale": factor}`` (seconds x scale)."""
from perfbench import mxspans


def read(spec, ctx):
    sp = mxspans.of(ctx)
    per = len(sp.named(spec["per"]))
    if not per:
        return None
    where = sp.outside(spec["span"]) if spec.get("outside") \
        else sp.cover(spec["span"], spec.get("less", ()))
    idle = sp.idle_ns(where)
    if idle is None:
        return None
    return idle * 1e-9 / per * spec.get("scale", 1.0)
