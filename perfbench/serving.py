"""What the two serving generators share: driving a ``Served`` system
tick by tick from one thread, stamping every output token on the
benchmark's own clock, and the output check against the plain
reference once the window has closed.
"""
import importlib
import time

import numpy as np

from perfbench import harness, stats


class Tracked:
    """One request as the benchmark sees it."""

    __slots__ = ("info", "due", "submitted", "req", "token_times",
                 "prompt_len", "greedy")

    def __init__(self, info, due, submitted, req, prompt_len, greedy):
        self.info, self.due, self.submitted = info, due, submitted
        self.req, self.prompt_len, self.greedy = req, prompt_len, greedy
        self.token_times = []


class Driver:
    """Single-threaded driver: submit, tick, stamp tokens."""

    def __init__(self, served, cfg, traffic, seed, tracer):
        self.served, self.cfg, self.traffic = served, cfg, traffic
        self.tracer = tracer
        self.rng = np.random.default_rng(seed)
        self.seed = seed
        self.live = []
        self.done = []
        self.n_submitted = 0
        self.counting = False
        self.counters = {"ticks": 0, "slot_ticks": 0,
                         "kv_blocks_used_sum": 0, "output_tokens": 0}
        self.work = {"context_tokens": 0}

    def submit(self, n_prompt, n_new, sampled, due=None, info=None):
        ids = self.rng.integers(0, self.cfg["vocab_size"], n_prompt,
                                dtype=np.int64).astype(np.int32)
        now = time.perf_counter()
        req = self.served.submit(
            ids, n_new, self.traffic["sampling"] if sampled else None,
            seed=self.seed * 1000003 + self.n_submitted)
        self.n_submitted += 1
        t = Tracked(info, now if due is None else due, now, req,
                    n_prompt, not sampled)
        self.live.append(t)
        return t

    def tick(self):
        """One ``server.step()``; every token it emitted is stamped with
        the host clock read right after it returns."""
        with self.tracer.span("server.step"):
            self.served.step()
        now = time.perf_counter()
        emitted = active = context = 0
        still = []
        for t in self.live:
            n = self.served.emitted(t.req)
            had = len(t.token_times)
            if n > had:
                t.token_times.extend([now] * (n - had))
                emitted += n - had
                active += 1
                context += t.prompt_len + had
            if self.served.done(t.req):
                self.done.append(t)
            else:
                still.append(t)
        self.live = still
        if self.counting:
            c = self.counters
            c["ticks"] += 1
            c["slot_ticks"] += active
            c["output_tokens"] += emitted
            c["kv_blocks_used_sum"] += self.served.kv_blocks_used()
            self.work["context_tokens"] += context
        return now

    def drain(self):
        while self.served.busy():
            self.tick()

    def warm(self):
        """Every executable and every small eager program the window
        will use: one greedy and one sampled request, run to the end."""
        self.submit(8, 4, False)
        self.submit(8, 4, True)
        self.drain()
        self.done.clear()


def sample_for_check(done, seed, traffic):
    """A sample, drawn from the seed, of the greedy requests the window
    finished, the one with most served tokens in it."""
    ok = [t for t in done if t.greedy and t.token_times]
    if not ok:
        return []
    ok.sort(key=lambda t: (-len(t.token_times), t.due))
    pick = [ok[0]]
    rest = ok[1:]
    rng = np.random.default_rng(seed + 1)
    n = min(len(rest), traffic["check_requests"] - 1)
    if n > 0:
        for i in rng.choice(len(rest), size=n, replace=False):
            pick.append(rest[int(i)])
    return pick


def check_outputs(checks, cfg, traffic, seed, served, sample, devices):
    """Run the reference once over each sampled prompt with its served
    tokens and compare how far the served tokens' logits lie below the
    reference's best: the widest gap (held against an altered token)
    and the mean gap over the served tokens (steady from seed to seed;
    the number the lower-precision control fails). Frees the program's
    state first. Returns the gaps' summary for the log."""
    ref = importlib.import_module("perfbench.reference." + cfg["family"])
    seqs = [served.tokens(t.req) for t in sample]
    served.free()
    t0 = time.perf_counter()
    gaps = ref.served_token_gaps(cfg, seed, seqs, devices[0]) \
        if seqs else []
    flat = np.concatenate(gaps) if gaps else np.zeros(0, np.float32)
    out = {"requests": len(seqs), "served_tokens": int(flat.size),
           "widest_gap": float(flat.max()) if flat.size else None,
           "mean_gap": float(flat.mean()) if flat.size else None,
           "p99_gap": stats.percentile(flat.tolist(), 99),
           "mismatch_share": float((flat > 0).mean())
           if flat.size else None,
           "reference_s": round(time.perf_counter() - t0, 2)}
    harness.say("reference", **out)
    checks.at_least("checked_served_tokens", out["served_tokens"],
                    traffic["limits"]["min_checked_tokens"])
    checks.at_most("mean_served_logit_gap", out["mean_gap"],
                   traffic["limits"]["mean_logit_gap"])
    checks.at_most("widest_served_logit_gap", out["widest_gap"],
                   traffic["limits"]["widest_logit_gap"])
    return out
