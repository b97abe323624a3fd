"""Traffic kind ``closed_loop_saturate``: as many clients as slots,
each sending its next request the moment the previous one completes,
so the server is saturated from the first timed second.

Every slot is occupied when the window opens by a session whose cache
was built during set-up (its context: prompt plus what it has already
generated) and which goes on decoding; a finished session is replaced
at once. The sessions are a pure function of the traffic file;
``--seed`` makes the weights, the token ids and the sampling keys.
"""
import time

from perfbench import harness, schedule, serving


def run(cell, seed, seconds, tracer, meter, devices, t_start):
    cfg, traffic = cell.config, cell.traffic
    family = cell.family()
    checks = harness.Checks()
    sessions = schedule.closed_loop(traffic)
    init = sessions["initial"]
    harness.say("schedule", traffic_seed=traffic["traffic_seed"],
                clients=len(init),
                context_tokens=schedule.summarize(
                    [s["context"] for s in init]),
                remaining_tokens=schedule.summarize(
                    [s["remaining"] for s in init]),
                sampled=sum(s["sampled"] for s in init))

    served = family.build(cfg, traffic["server"], seed, devices)
    drv = serving.Driver(served, cfg, traffic, seed, tracer)
    drv.warm()
    fallbacks0 = served.kernel_fallbacks()
    # build every session's cache: set-up the traffic needs
    for s in init:
        drv.submit(s["context"], s["remaining"], s["sampled"], info=s)
    while any(not t.token_times for t in drv.live):
        drv.tick()
    replacements = list(sessions["replacements"])
    harness.say("setup", seconds=round(time.perf_counter() - t_start, 2),
                **meter.since((0, 0, 0)))
    tracer.start()

    drv.done.clear()
    mark = meter.mark()
    with tracer.window():
        t_win = time.perf_counter()
        setup_s = t_win - t_start
        t_end = t_win + seconds
        drv.counting = True
        while time.perf_counter() < t_end:
            while len(drv.live) < len(init) and replacements:
                s = replacements.pop(0)
                drv.submit(s["context"], s["remaining"], s["sampled"],
                           info=s)
            drv.tick()
        window_s = time.perf_counter() - t_win
    built = meter.since(mark)["builds"]
    peak = harness.memory_peak_bytes(devices)
    counters = dict(drv.counters, **served.counters(),
                    slots=served.slots, memory_peak_bytes=peak)

    finished = list(drv.done)
    bad = [t for t in finished if not served.ok(t.req)]
    harness.say("window", seconds=round(window_s, 3),
                ticks=counters["ticks"],
                output_tokens=counters["output_tokens"],
                finished=len(finished), preemptions=counters["preemptions"],
                replacements_left=len(replacements))
    checks.equal("finished_with_wrong_token_count", len(bad), 0)
    checks.equal("kernel_fallbacks_in_run",
                 served.kernel_fallbacks() - fallbacks0, 0)
    checks.equal("preemptions", counters["preemptions"], 0)
    sample = serving.sample_for_check(finished, seed, traffic)
    ref = serving.check_outputs(checks, cfg, traffic, seed, served, sample,
                                devices)
    tracer.phases.add("reference", ref["reference_s"])

    return {
        "quantities": {
            "serve_tok_s": counters["output_tokens"] / window_s},
        "spans": {},
        "counters": counters,
        "work": drv.work,
        "attempted": len(init) + len(sessions["replacements"])
        - len(replacements),
        "failed": len(bad),
        "checks": checks,
        "setup_s": setup_s,
        "window_s": window_s,
        "window_builds": built,
        "memory_peak_bytes": peak,
    }
