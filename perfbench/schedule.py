"""Traffic schedules: pure functions of a traffic file's parameters.

A schedule never depends on ``--seed``: every run of a cell offers the
identical arrivals, lengths and sampling flags (the traffic file's
``traffic_seed`` fixes them), so what varies between two runs of one
commit is the program. ``--seed`` makes the weights, the token ids and
the sampling keys.

Each request draws all of its fields from one stream, in arrival
order, so a shorter horizon is a prefix of a longer one.
"""
import math

import numpy as np


def _draw_length(rs, spec):
    """One length from a spec ``{"dist", ..., "min", "max"}``."""
    kind = spec["dist"]
    if kind == "lognormal":
        v = rs.lognormal(math.log(spec["median"]), spec["sigma"])
    elif kind == "uniform":
        v = rs.uniform(spec["min"], spec["max"])
    elif kind == "fixed":
        v = spec["value"]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(v), spec["min"]), spec["max"]))


def open_loop(traffic, seconds):
    """The replayed open-loop schedule of a traffic file.

    Returns a list of dicts ``{"due", "prompt", "output", "sampled"}``,
    ``due`` in seconds relative to the start of the timed window (the
    lead-in has negative due times), covering
    ``[-lead_in_s, seconds)``. Inter-arrival gaps are gamma-distributed
    with the file's coefficient of variation (CV 1 is Poisson, CV 2 the
    bursty arrivals of BurstGPT) around ``1 / rate_rps``."""
    rs = np.random.RandomState(traffic["traffic_seed"])
    rate = float(traffic["rate_rps"])
    cv = float(traffic["arrivals"]["cv"])
    shape = 1.0 / (cv * cv)
    scale = (1.0 / rate) / shape
    t = -float(traffic["lead_in_s"])
    out = []
    while True:
        # every request consumes the same five draws, so the stream is
        # prefix-stable whatever the horizon
        gap = rs.gamma(shape, scale)
        prompt = _draw_length(rs, traffic["prompt_tokens"])
        output = _draw_length(rs, traffic["output_tokens"])
        sampled = bool(rs.uniform() < traffic["sampled_share"])
        t += gap
        if t >= seconds:
            return out
        out.append({"due": t, "prompt": prompt, "output": output,
                    "sampled": sampled})


def closed_loop(traffic):
    """The sessions of a closed-loop, saturating traffic file.

    ``initial``: one session per client, in flight when the window
    opens: a cache of ``context`` tokens built during set-up (its
    prompt plus what it has already generated) and ``remaining``
    tokens still to generate. ``replacements``: the fresh sessions that
    take a finished session's slot, in order (prompt + whole output).
    The first ``finishing`` initial sessions are near their end
    (``finishing_remaining``) so that every window completes some
    requests for the output check; they are greedy."""
    rs = np.random.RandomState(traffic["traffic_seed"])
    n = int(traffic["clients"])
    fin = int(traffic["finishing"])
    initial = []
    for i in range(n):
        context = _draw_length(rs, traffic["context_tokens"])
        prompt = _draw_length(rs, traffic["prompt_tokens"])
        total = _draw_length(rs, traffic["output_tokens"])
        near = _draw_length(rs, traffic["finishing_remaining"])
        sampled = bool(rs.uniform() < traffic["sampled_share"])
        done = context - prompt
        # a session well short of its end; one whose drawn total is
        # already passed is given a total that much longer
        remaining = near if i < fin else max(
            total - done, traffic["min_remaining"] + near)
        initial.append({"context": context, "remaining": remaining,
                        "sampled": sampled and i >= fin})
    replacements = []
    for _ in range(int(traffic["replacement_pool"])):
        replacements.append({
            "context": _draw_length(rs, traffic["prompt_tokens"]),
            "remaining": _draw_length(rs, traffic["output_tokens"]),
            "sampled": bool(rs.uniform() < traffic["sampled_share"])})
    return {"initial": initial, "replacements": replacements}


def summarize(values):
    xs = sorted(values)
    if not xs:
        return {"n": 0}
    q = lambda p: xs[min(len(xs) - 1, int(p * (len(xs) - 1) + 0.5))]
    return {"n": len(xs), "min": xs[0], "p50": q(0.5), "p90": q(0.9),
            "max": xs[-1]}


def open_loop_summary(sched, seconds):
    """What the schedule offers, for the line printed before a run."""
    win = [r for r in sched if r["due"] >= 0]
    lead = [r for r in sched if r["due"] < 0]
    return {
        "requests_lead_in": len(lead), "requests_window": len(win),
        "prompt_tokens": summarize([r["prompt"] for r in win]),
        "output_tokens": summarize([r["output"] for r in win]),
        "sampled": sum(r["sampled"] for r in win),
        "offered_rps_window": len(win) / seconds if seconds else 0.0,
        "offered_output_tok_s_window":
            sum(r["output"] for r in win) / seconds if seconds else 0.0,
    }
