"""Order statistics and the serving arithmetic, in one place.

Pure Python on lists of floats; nothing here touches JAX."""


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default "linear" rule). None for an empty
    sample."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def ttft_ms(due_s, first_token_s):
    """Time to first token of one request, from when it was DUE (open
    loop: the generator's lateness and the queue both count)."""
    return (first_token_s - due_s) * 1000.0


def tpot_ms(token_times_s, t0, t1, min_gaps):
    """Mean gap (ms) between consecutive output tokens of one request,
    over the gaps whose both ends fall inside the window [t0, t1].
    None when fewer than ``min_gaps`` such gaps exist."""
    inside = [t for t in token_times_s if t0 <= t <= t1]
    gaps = len(inside) - 1
    if gaps < max(1, min_gaps):
        return None
    return (inside[-1] - inside[0]) / gaps * 1000.0
