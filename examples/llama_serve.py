"""Continuous-batching serving demo: train a tiny Llama on a toy
pattern, then push a mixed batch of requests through
mx.serving.InferenceServer — paged KV cache, one shared decode
executable, per-request sampling params — and compare a greedy
request's output against one-shot generate(). Then the multi-LoRA
leg: a 'countdown' adapter trained against the frozen base is
hot-loaded into a warm server and served NEXT TO base requests in
one decode batch (per-slot adapter indices are traced operands —
zero extra compiles), with greedy parity checked against
merged-weights generate() and weighted-fair tenant accounting on
top. A second pass serves the base requests with chunked prefill +
self-drafting speculative decoding (the counting language is
maximally predictable, so n-gram drafts are mostly accepted) and
re-checks greedy parity. Then the same model goes behind a
2-replica mx.serving.FleetRouter (the resilient-fleet front door),
and ends self-scaling: a 1-replica fleet + FleetAutoscaler grows
under a burst (warm standby promotes first), shrinks back, and the
goodput ledger attributes the standby's warm-up to COMPILE time.

Usage: python examples/llama_serve.py [--cpu] [--steps 200]
                                      [--requests 8]
"""
import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models.llama_infer import generate
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep

    mx.random.seed(0)
    net = mx.models.get_model("llama_tiny")
    net.initialize()

    # toy language: sequences count upward mod 50 from a random start
    rs = np.random.RandomState(0)
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    def lm_loss(logits, labels):
        return ce(logits.reshape(-1, 256), labels.reshape(-1))

    step = FusedTrainStep(net, lm_loss,
                          mx.optimizer.AdamW(learning_rate=3e-3))
    for i in range(args.steps):
        start = rs.randint(0, 50, (16, 1))
        seq = (start + np.arange(33)) % 50
        l = step(mx.nd.array(seq[:, :-1], dtype="int32"),
                 mx.nd.array(seq[:, 1:], dtype="int32"))
        if (i + 1) % 50 == 0:
            print(f"step {i + 1}: loss {float(l.asscalar()):.4f}")
    step.sync_to_params()

    telemetry.enable()
    mx.goodput.enable()    # wall-clock attribution + tokens/s/chip
    server = mx.serving.InferenceServer(net, batch_slots=4, max_len=64,
                                        block_size=8,
                                        max_prompt_len=16)
    reqs = []
    for i in range(args.requests):
        start = int(rs.randint(0, 50))
        T = int(rs.randint(3, 9))
        prompt = (start + np.arange(T)) % 50
        # even requests greedy, odd ones sampled — both ride the SAME
        # compiled decode tick via per-row sampling params
        kw = {} if i % 2 == 0 else dict(temperature=0.7, top_k=5,
                                        seed=i)
        reqs.append((prompt, server.submit(prompt.astype(np.int32),
                                           max_new_tokens=10, **kw)))
    server.run()

    for prompt, r in reqs:
        kind = "greedy " if r.temperature == 0.0 else "sampled"
        print(f"req {r.id} ({kind}) {prompt.tolist()} -> "
              f"{r.output_tokens}  ttft={r.ttft * 1e3:.1f}ms")

    # the greedy rows are token-identical to one-shot generate()
    prompt, r = reqs[0]
    one = generate(net, prompt[None, :].astype(np.int32),
                   max_new_tokens=10, max_len=64)
    match = r.output_tokens == one[0, len(prompt):].tolist()
    print("parity with one-shot generate():", match)

    st = server.stats()
    print(f"stats: {st['ticks']} ticks, {st['tokens_generated']} "
          f"tokens, prefill_compiles={st['prefill_compiles']} "
          f"decode_compiles={st['decode_compiles']} "
          f"kv_utilization={st['kv_utilization']:.2f}")
    snap = telemetry.snapshot()
    ttft = snap["histograms"]["serving_ttft_seconds"]
    print(f"TTFT p50 {ttft['p50'] * 1e3:.1f}ms / p95 "
          f"{ttft['p95'] * 1e3:.1f}ms over {ttft['count']} requests")
    if not match:
        raise SystemExit("serving output diverged from generate()")

    # -- batched multi-LoRA + tenant QoS ------------------------------
    # train an adapter for a second dialect (counting DOWN mod 50) on
    # the frozen base, hot-load it into a running server, and serve
    # base and adapter requests side by side in the SAME decode batch:
    # per-slot adapter indices are traced operands, so the mix costs
    # zero extra compiles
    down = [(rs.randint(0, 50, (16, 1)) - np.arange(33)) % 50
            for _ in range(8)]
    adapter = mx.serving.lora.train_adapter(
        net, down, rank=8, steps=120, lr=0.3)
    print(f"lora: trained 'countdown' adapter, loss "
          f"{adapter['losses'][0]:.3f} -> {adapter['losses'][-1]:.3f}")
    lsrv = mx.serving.InferenceServer(
        net, batch_slots=4, max_len=64, block_size=8,
        max_prompt_len=16, lora={"capacity": 4, "rank": 8},
        tenants={"acme": {"weight": 2.0, "priority": "interactive"},
                 "bulk": {"weight": 1.0, "priority": "batch"}})
    warm = lsrv.submit(((7 + np.arange(5)) % 50).astype(np.int32), 6,
                       tenant="bulk")
    lsrv.run()                       # server is warm: both programs built
    cs0 = lsrv.compile_stats()
    lsrv.load_adapter("countdown", adapter)     # hot-load, no rebuild
    lreqs = []
    for i in range(args.requests):
        start = int(rs.randint(5, 50))
        direction = -1 if i % 2 else 1
        prompt = ((start + direction * np.arange(5)) % 50).astype(
            np.int32)
        lreqs.append((prompt, lsrv.submit(
            prompt, max_new_tokens=8,
            adapter="countdown" if i % 2 else None,
            tenant="acme" if i % 3 else "bulk")))
    lsrv.run()
    cs = lsrv.compile_stats()
    for prompt, r in lreqs:
        tag = r.adapter or "base"
        print(f"lora req {r.id} [{tag:9s} tenant={r.tenant}] "
              f"{prompt.tolist()} -> {r.output_tokens}")
    # greedy parity: adapter rows vs OFFLINE merged-weights generate()
    lmatch = True
    for prompt, r in lreqs:
        if r.adapter is None:
            one = generate(net, prompt[None, :], max_new_tokens=8,
                           max_len=64)
        else:
            with mx.serving.lora.merged_weights(net, adapter):
                one = generate(net, prompt[None, :], max_new_tokens=8,
                               max_len=64)
        lmatch &= r.output_tokens == one[0, len(prompt):].tolist()
    print(f"lora parity with merged-weights generate(): {lmatch}  "
          f"(compiles after hot-load: "
          f"+{cs['prefill_compiles'] - cs0['prefill_compiles']} "
          f"prefill, +{cs['decode_compiles'] - cs0['decode_compiles']} "
          f"decode)")
    lst = lsrv.stats()
    passes = {t: round(p, 1) for t, p in lst["tenant_passes"].items()}
    print(f"lora stats: adapters={lst['adapters']['loaded']} "
          f"tenant_passes={passes}")
    if not lmatch:
        raise SystemExit("LoRA serving diverged from merged weights")
    if cs["decode_compiles"] != cs0["decode_compiles"]:
        raise SystemExit("adapter hot-load triggered a recompile")

    # -- chunked prefill + speculative decoding -----------------------
    # same traffic through the tail-latency machinery: prefills land
    # in 4-token per-tick chunks and the counting pattern lets the
    # n-gram proposer draft 3 tokens per tick for one verify dispatch
    spec = mx.serving.InferenceServer(net, batch_slots=4, max_len=64,
                                      block_size=8, max_prompt_len=16,
                                      prefill_chunk_tokens=4,
                                      speculative=3)
    srs = []
    for i in range(args.requests):
        start = int(rs.randint(0, 50))
        prompt = ((start + np.arange(6)) % 50).astype(np.int32)
        srs.append((prompt, spec.submit(prompt, max_new_tokens=10)))
    spec.run()
    st = spec.stats()
    print(f"speculative: accept_rate={st['draft_accept_rate']:.2f} "
          f"accepted={st['spec_tokens_accepted']} "
          f"rejected={st['spec_tokens_rejected']} "
          f"ticks={st['ticks']} for {st['tokens_generated']} tokens")
    sp, sr = srs[0]
    one = generate(net, sp[None, :], max_new_tokens=10, max_len=64)
    smatch = sr.output_tokens == one[0, len(sp):].tolist()
    print("speculative parity with one-shot generate():", smatch)
    if not smatch:
        raise SystemExit("speculative output diverged from generate()")

    # -- resilient fleet: the same model behind a 2-replica router ----
    # (health-gated least-loaded routing; a replica loss mid-run would
    # fail over with no request lost — see docs/serving.md)
    fleet = mx.serving.FleetRouter(
        [mx.serving.LocalReplica(
            mx.serving.InferenceServer(net, batch_slots=4, max_len=64,
                                       block_size=8, max_prompt_len=16),
            name=f"r{i}") for i in range(2)],
        affinity_blocks=0)
    frs = []
    for i in range(args.requests):
        start = int(rs.randint(0, 50))
        prompt = ((start + np.arange(5)) % 50).astype(np.int32)
        frs.append((prompt, fleet.submit(prompt, 6)))
    fleet.run(timeout_s=300)
    for prompt, fr in frs:
        print(f"fleet {fr.token} via {fr.replica}: {prompt.tolist()} "
              f"-> {fr.output_tokens} ({fr.status})")
    fst = fleet.stats()
    print(f"fleet stats: {len(frs)} requests over "
          f"{sorted(fst['replicas'])}, retries={fst['retries']} "
          f"failovers={fst['failovers']} shed={fst['shed']}")
    p0, fr0 = frs[0]
    one = generate(net, p0[None, :], max_new_tokens=6, max_len=64)
    fmatch = fr0.output_tokens == one[0, len(p0):].tolist()
    print("fleet parity with one-shot generate():", fmatch)
    if not fmatch or any(fr.status != "ok" for _, fr in frs):
        raise SystemExit("fleet serving diverged or lost a request")

    # -- fleet observability: one merged timeline per request ---------
    # (router queue/attempt spans + the winning worker's prefill/decode
    # spans on one clock; chrome export puts the router and each
    # replica on their own pid — see docs/observability.md)
    tr = fleet.trace(fr0)
    spans = ", ".join(f"{e['name']}@{e['src']}" for e in tr["events"])
    print(f"fleet trace {fr0.token}: decision="
          f"{tr['attempts'][0]['decision']} [{spans}]")
    trace_path = os.path.join(tempfile.gettempdir(),
                              "llama_serve_fleet_trace.json")
    telemetry.export_chrome_trace(trace_path)
    print("chrome trace (router + replica pids):", trace_path)

    # -- goodput + memory pressure: where did the wall clock go, and
    # how much KV headroom is left? ------------------------------------
    mx.goodput.publish()
    print(mx.goodput.format_summary())
    tps = telemetry.read_gauge("goodput_serve_tokens_per_sec_per_chip")
    if tps is not None:
        print(f"serve throughput: {tps:.1f} tokens/s/chip")
    for rep in fleet._reps:
        det = rep.detail or {}   # the same heartbeat the router routes on
        eta = det.get("exhaust_in_s")
        print(f"kv pool {rep.name}: {det.get('blocks_free')} blocks "
              "free, "
              + (f"exhaustion forecast in {eta:.1f}s"
                 if eta is not None else "no exhaustion in sight"))

    # -- self-scaling fleet: one replica + a FleetAutoscaler ----------
    # A burst of requests ages the fleet queue past the scale-out
    # trigger, the autoscaler grows the fleet (a warm standby promotes
    # first — zero compile stall at promotion time), then load-driven
    # scale-in drains it back to one replica. The standby's warm-up
    # compile lands in the goodput ledger's COMPILE category, not
    # productive time — the ledger shows scaling's true overhead.
    compile_s0 = mx.goodput.snapshot()["seconds"]["compile"]

    def spare():
        # a shape this process has never compiled, so the standby
        # warm-up is a REAL compile the goodput ledger can attribute
        return mx.serving.InferenceServer(net, batch_slots=3,
                                          max_len=48, block_size=8,
                                          max_prompt_len=16)

    afleet = mx.serving.FleetRouter(
        [mx.serving.LocalReplica(
            mx.serving.InferenceServer(net, batch_slots=4, max_len=64,
                                       block_size=8, max_prompt_len=16),
            name="a0")],
        affinity_blocks=0)
    asc = afleet.attach_autoscale(
        provisioner=mx.serving.LocalProvisioner(spare),
        min_replicas=1, max_replicas=3, warm_standbys=1,
        queue_age_out_s=0.05, scale_in_load=0.8, scale_in_hold_s=0.3,
        cooldown_out_s=0.2, cooldown_in_s=0.2, tick_interval_s=0.02)
    afleet.step()                    # first tick spawns the standby
    afrs = []
    for i in range(args.requests * 8):
        start = int(rs.randint(0, 50))
        prompt = ((start + np.arange(5)) % 50).astype(np.int32)
        afrs.append(afleet.submit(prompt, 12))
    peak, t0 = 1, time.time()
    while any(not fr.terminal for fr in afrs):
        if afleet.step() == 0:
            time.sleep(0.002)
        peak = max(peak, asc.stats()["active"])
        if time.time() - t0 > 180:
            raise SystemExit("autoscale burst never finished")
    t0 = time.time()
    while (asc.stats()["active"] > 1 or asc.stats()["draining"]) \
            and time.time() - t0 < 60:
        if afleet.step() == 0:
            time.sleep(0.002)
    warm_compile_s = mx.goodput.snapshot()["seconds"]["compile"] \
        - compile_s0
    ast = asc.stats()
    print(f"autoscale: peak {peak} replicas over "
          f"{len(afrs)} burst requests, scale_outs={ast['scale_out']} "
          f"scale_ins={ast['scale_in']} "
          f"chip_seconds={ast['chip_seconds']}")
    print(f"autoscale: standby warm-up charged "
          f"{warm_compile_s:.2f}s to the goodput COMPILE category "
          "(scaling never counts as productive time)")
    if peak < 2 or ast["scale_in"] < 1 or asc.stats()["active"] != 1:
        raise SystemExit("autoscaler failed to grow and shrink")
    if any(fr.status != "ok" for fr in afrs):
        raise SystemExit("autoscale burst lost a request")
    if warm_compile_s <= 0:
        raise SystemExit("standby warm-up missing from the compile "
                         "ledger")


if __name__ == "__main__":
    main()
