"""Traffic kind ``open_loop_replay``: requests arrive on a schedule
whether or not earlier ones have finished, and the schedule is
REPLAYED: arrivals, lengths and sampling flags are a pure function of
the traffic file (``traffic_seed``, ``rate_rps``), identical in every
run; ``--seed`` makes the weights, the token ids and the sampling keys.

The schedule starts ``lead_in_s`` before the timed window, so the
window opens at the occupancy it will end with; the lead-in is set-up
the traffic needs. Requests due before the window are served and not
counted; requests in flight at its end are cut, not drained.
"""
import time

from perfbench import harness, schedule, serving, stats

#: the profiler is switched on this long before the window opens, so
#: that starting it disturbs nothing that is measured
TRACE_LEAD_S = 3.0


def drive(served, drv, traffic, seconds, tracer, meter):
    """The lead-in and the timed window on a warm, idle server.
    Returns what the window held, before any arithmetic."""
    sched = schedule.open_loop(traffic, seconds)
    t_win = time.perf_counter() + traffic["lead_in_s"]
    t_end = t_win + seconds
    pending = list(sched)
    tracked, late = [], []

    def serve_until(t_stop):
        """Offer what is due and tick, or sleep to the next arrival,
        until the clock passes ``t_stop``."""
        while True:
            now = time.perf_counter()
            if now >= t_stop:
                return
            while pending and t_win + pending[0]["due"] <= now:
                r = pending.pop(0)
                t = drv.submit(r["prompt"], r["output"], r["sampled"],
                               due=t_win + r["due"], info=r)
                tracked.append(t)
                if r["due"] >= 0:
                    late.append(t.submitted - t.due)
            if served.busy():
                drv.tick()
            else:
                nxt = t_win + pending[0]["due"] if pending else t_stop
                time.sleep(max(0.0, min(nxt, t_stop)
                               - time.perf_counter()))

    serve_until(t_win - TRACE_LEAD_S)
    tracer.start()
    serve_until(t_win)
    with tracer.window():
        drv.counting = True
        mark = meter.mark()
        serve_until(t_end)
        window_s = time.perf_counter() - t_win
        drv.counting = False
    return {"t_win": t_win, "t_end": t_end, "window_s": window_s,
            "tracked": tracked, "late": late,
            "builds": meter.since(mark)["builds"]}


def measure(served, drv, res, traffic):
    """What the window holds: TTFT over every request DUE in it (one
    with no first token by its end has failed), TPOT over the gaps
    inside it (requests admitted in the lead-in count too), and every
    request that finished in it (a wrong token count fails it)."""
    t_win, t_end = res["t_win"], res["t_end"]
    # TTFT is counted over the requests due up to ``ttft_guard_s``
    # before the window's end: one due in its last moments cannot have
    # a first token by the cut and says nothing about the server (it is
    # offered all the same, like the lead-in's)
    last_due = t_end - t_win - traffic["ttft_guard_s"]
    in_win = [t for t in res["tracked"] if 0 <= t.info["due"] < last_due]
    ttft, failed = [], 0
    for t in in_win:
        if t.token_times and t.token_times[0] <= t_end:
            ttft.append(stats.ttft_ms(t.due, t.token_times[0]))
        else:
            failed += 1
    tpot = []
    for t in res["tracked"]:
        v = stats.tpot_ms(t.token_times, t_win, t_end,
                          traffic["tpot_min_gaps"])
        if v is not None:
            tpot.append(v)
    finished = [t for t in drv.done
                if t.token_times and t_win <= t.token_times[-1] <= t_end]
    bad = [t for t in finished if not served.ok(t.req)]
    queue_wait = [w for w in (served.queue_wait_s(t.req) for t in in_win)
                  if w is not None]
    return {"due": len(in_win), "ttft": ttft, "tpot": tpot,
            "failed": failed + len(bad), "finished": finished,
            "bad": bad, "queue_wait": queue_wait}


def run(cell, seed, seconds, tracer, meter, devices, t_start):
    cfg, traffic = cell.config, cell.traffic
    family = cell.family()
    checks = harness.Checks()
    harness.say("schedule", traffic_seed=traffic["traffic_seed"],
                rate_rps=traffic["rate_rps"],
                lead_in_s=traffic["lead_in_s"],
                **schedule.open_loop_summary(
                    schedule.open_loop(traffic, seconds), seconds))

    served = family.build(cfg, traffic["server"], seed, devices)
    drv = serving.Driver(served, cfg, traffic, seed, tracer)
    drv.warm()
    fallbacks0 = served.kernel_fallbacks()
    harness.say("warm", seconds=round(time.perf_counter() - t_start, 2),
                **meter.since((0, 0, 0)))

    res = drive(served, drv, traffic, seconds, tracer, meter)
    setup_s = res["t_win"] - t_start
    peak = harness.memory_peak_bytes(devices)
    counters = dict(drv.counters, **served.counters(),
                    slots=served.slots, memory_peak_bytes=peak)
    m = measure(served, drv, res, traffic)
    harness.say("window", seconds=round(res["window_s"], 3), due=m["due"],
                first_tokens=len(m["ttft"]), tpot_requests=len(m["tpot"]),
                finished=len(m["finished"]), failed=m["failed"],
                ticks=counters["ticks"],
                output_tokens=counters["output_tokens"],
                preemptions=counters["preemptions"],
                ttft_p50=stats.percentile(m["ttft"], 50),
                ttft_p80=stats.percentile(m["ttft"], 80),
                tpot_p50=stats.percentile(m["tpot"], 50),
                tpot_p80=stats.percentile(m["tpot"], 80))

    checks.equal("finished_with_wrong_token_count", len(m["bad"]), 0)
    checks.equal("kernel_fallbacks_in_run",
                 served.kernel_fallbacks() - fallbacks0, 0)
    checks.at_least("requests_with_first_token", len(m["ttft"]),
                    traffic["limits"]["min_requests"])
    checks.at_least("requests_with_tpot", len(m["tpot"]),
                    traffic["limits"]["min_requests"])
    sample = serving.sample_for_check(m["finished"], seed, traffic)
    ref = serving.check_outputs(checks, cfg, traffic, seed, served, sample,
                                devices)
    tracer.phases.add("reference", ref["reference_s"])

    return {
        "quantities": {
            "ttft_ms": m["ttft"], "tpot_ms": m["tpot"],
            "serve_tok_s": counters["output_tokens"] / res["window_s"]},
        "spans": {"ttft_ms": m["ttft"], "tpot_ms": m["tpot"],
                  "gen_late": res["late"], "queue_wait": m["queue_wait"]},
        "counters": counters,
        "work": drv.work,
        "attempted": m["due"],
        "failed": m["failed"],
        "checks": checks,
        "setup_s": setup_s,
        "window_s": res["window_s"],
        "window_builds": res["builds"],
        "memory_peak_bytes": peak,
    }
