"""Reader ``span_count_ratio``: over the program's spans called
``span`` that carry both counts, the summed count ``count`` over the
summed count ``of``, times ``scale``.
Spec: ``{"span": name, "count": key, "of": key, "scale": factor}``."""
from perfbench import mxspans


def read(spec, ctx):
    part, whole = spec["count"], spec["of"]
    both = [s.counts for s in mxspans.of(ctx).named(spec["span"])
            if part in s.counts and whole in s.counts]
    total = sum(c[whole] for c in both)
    if not total:
        return None
    return sum(c[part] for c in both) / total * spec.get("scale", 1.0)
