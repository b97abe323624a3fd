"""Reader ``counter_ratio``: a ratio of counters (the program's or the
generator's), each side a product of counter names and numbers.
Spec: ``{"over": [names...], "under": [names or numbers...],
"scale": factor}``."""


def _product(terms, counters):
    out = 1.0
    for t in terms:
        v = counters.get(t) if isinstance(t, str) else t
        if v is None:
            return None
        out *= v
    return out


def read(spec, ctx):
    num = _product(spec["over"], ctx.counters)
    den = _product(spec["under"], ctx.counters)
    if num is None or not den:
        return None
    return num / den * spec.get("scale", 1.0)
