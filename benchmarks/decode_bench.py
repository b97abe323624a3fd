"""Cached-decode generation throughput (tokens/sec/chip).

The inference twin of the training benches: greedy decode through the
Llama flash-decode path, bf16 cache vs int8-quantized cache (the
design claim is ~2x decode HBM-traffic reduction at large S, not
measured yet). It takes the platform JAX gives its own process: on the
CPU it runs a tiny config as a pipeline check, and what it prints there
are counts and host wall clocks, never device numbers (vs_baseline
0.0).

generate() now rides the persistent executable cache
(mxnet_tpu.serving.executables), so the second call at a signature is
genuinely warm — the bench times it directly instead of
difference-timing around a per-call retrace.

--serve runs the continuous-batching mode instead: Poisson arrivals
into mx.serving.InferenceServer, TTFT p50/p95 + aggregate
tokens/sec/chip, against a warmed sequential one-shot generate()
baseline over the identical workload (serve_speedup is the headline
comparison).

--fleet N runs the resilient-serving bench: the same Poisson workload
through 1 replica, then N subprocess replicas behind
mx.serving.FleetRouter (fleet TTFT p50/p95, tokens/sec per replica vs
single), then N replicas with one SIGKILLed mid-run — zero lost and
zero duplicated requests is the reported robustness claim. The fleet
workers are pinned to the CPU whatever the parent runs on (the chip
belongs to the parent process), so these legs yield COUNTS ONLY — lost,
duplicated, shed, failed-over requests — and their latencies and
tokens/sec are host wall clocks of a toy model, not serving numbers.
Adding
--slo appends two burn-rate legs: clean (the SLO alert must stay
silent) and with `replica.stall` armed in every worker (the alert
must fire, name the objective in health, and collect a cross-process
flight bundle the merge CLI stitches into one ordered timeline).

--tiering runs the KV memory-hierarchy bench, three legs: pressure (a
pool sized to force >=6 preemptions in a no-tier control must finish
with ZERO destructive preemptions tiered — evictions spill to host
RAM, tokens identical), warm restart (a fresh server over the
persistent prefix store must serve a >=75%-shared prompt at TTFT <=
0.6x cold — `kv_tier_warm_ttft_ratio` is the headline), and
disaggregation (1 prefill + 1 decode replica streaming blocks over
the router's kv channel, token-identical with zero extra decode
compiles). `tier_pass` ANDs the three.

--tenants runs the adversarial multi-tenant QoS leg: a batch-class
flooder is shed by priority class at its per-tenant queue bound while
the interactive victim must hold its TTFT/TPOT SLO end to end
(`tenant_pass`, headline `bench_tenant_victim_ttft_p95_ms`).

--lora runs the batched multi-LoRA leg: the same workload on a
base-only server and as a 3-way base/adapter mix through one rank-8
adapter table inside the SAME decode executable — headline
`bench_lora_mix_vs_base_ratio` gated >= 0.8x with zero compiles added
after the adapters hot-load.

One JSON line under a BudgetGuard, in the calling process; a failure
or the deadline ends the run with a non-zero exit code.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np

from bench import BudgetGuard

_guard = None


def _build_net(on_tpu, serve=False):
    import mxnet_tpu as mx
    from mxnet_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_layers=16,
                          num_heads=16, num_kv_heads=8,
                          max_seq_len=2048, dtype="bfloat16")
    elif serve:
        # compute-dominated small config: per-token model math has to
        # outweigh per-tick host dispatch for the batching comparison
        # to measure scheduling rather than Python overhead
        cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                          intermediate_size=1024, num_layers=4,
                          num_heads=8, num_kv_heads=4, max_seq_len=128,
                          dtype="float32")
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_layers=2,
                          num_heads=4, num_kv_heads=2, max_seq_len=128,
                          dtype="float32")
    mx.random.seed(0)
    net = LlamaForCausalLM(cfg)
    net.initialize()
    return cfg, net


def run_phase(on_tpu, guard):
    """Measure greedy decode tokens/sec for both cache dtypes into
    guard.best."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.llama_infer import generate

    cfg, net = _build_net(on_tpu)
    if on_tpu:
        batch, prompt_len, new_tokens = 8, 128, 256
    else:
        batch, prompt_len, new_tokens = 2, 16, 32

    def _fetch(out):
        return np.asarray(out.asnumpy() if hasattr(out, "asnumpy")
                          else out)

    rs = np.random.RandomState(0)
    prompt = mx.nd.array(rs.randint(0, cfg.vocab_size,
                                    (batch, prompt_len)),
                         dtype="int32")

    for cache_dtype in ("model", "int8"):
        if guard.remaining() < 30.0:
            break

        def timed():
            t0 = time.perf_counter()
            out = generate(net, prompt, max_new_tokens=new_tokens,
                           kv_cache_dtype=cache_dtype)
            _fetch(out)  # host fetch = honest sync
            return time.perf_counter() - t0

        # first call at a signature compiles the persistent
        # executables; the second is warm (and stays warm for every
        # later call — that is the thing this PR changed)
        dt_cold = timed()
        if guard.remaining() < 20.0:
            break
        dt_warm = timed()
        tps = batch * new_tokens / dt_warm
        key = "tokens_per_sec" if cache_dtype == "model" \
            else "tokens_per_sec_int8_cache"
        guard.best.update({
            key: round(tps, 2),
            f"compile_s_{cache_dtype}": round(max(0.0,
                                                  dt_cold - dt_warm), 1),
        })
        if cache_dtype == "model":
            guard.best.update({"value": round(tps, 2),
                               "phase": "decode",
                               "batch": batch,
                               "prompt_len": prompt_len,
                               "new_tokens": new_tokens})
        guard.emit()


def serve_phase(on_tpu, guard, num_requests=16, arrival_rate=None,
                seed=0):
    """Continuous-batching serving bench: Poisson arrivals through
    InferenceServer vs a warmed sequential one-shot generate()
    baseline over the same (prompt, max_new) workload."""
    import jax

    from mxnet_tpu import telemetry
    from mxnet_tpu.models.llama_infer import generate
    from mxnet_tpu.serving import InferenceServer

    cfg, net = _build_net(on_tpu, serve=True)
    if on_tpu:
        slots, max_len, block, mpl = 8, 512, 16, 128
        new_choices = (64, 128, 192)
        arrival_rate = arrival_rate or 64.0
    else:
        slots, max_len, block, mpl = 4, 64, 8, 16
        new_choices = (8, 16, 24)
        arrival_rate = arrival_rate or 200.0

    rs = np.random.RandomState(seed)
    workload = []
    for _ in range(num_requests):
        T = int(rs.randint(4, mpl + 1))
        p = rs.randint(0, cfg.vocab_size, T).astype(np.int32)
        workload.append((p, int(rs.choice(new_choices))))
    total_new = sum(n for _, n in workload)

    telemetry.enable()
    server = InferenceServer(net, batch_slots=slots, max_len=max_len,
                             block_size=block, max_prompt_len=mpl)
    # warm-up: one request compiles the prefill + decode executables
    # (they stay warm for the whole measured run)
    server.submit(workload[0][0], max_new_tokens=2)
    server.run()

    # Poisson arrivals against the real clock
    gaps = rs.exponential(1.0 / arrival_rate, num_requests)
    t_start = time.perf_counter()
    arrivals = t_start + np.cumsum(gaps)
    pending = list(zip(arrivals, workload))
    reqs = []
    while pending or server.queue or server.stats()["active"]:
        now = time.perf_counter()
        while pending and pending[0][0] <= now:
            _, (p, n) = pending.pop(0)
            reqs.append(server.submit(p, max_new_tokens=n))
        if server.step() == 0 and pending and not server.queue:
            time.sleep(max(0.0, pending[0][0] - time.perf_counter()))
    t_serve = time.perf_counter() - t_start

    ttfts = np.array([r.ttft for r in reqs])
    chips = max(1, jax.local_device_count())
    serve_tps = total_new / t_serve

    # sequential baseline over the identical workload: one-shot
    # generate() per request, warmed (pass 1 compiles each (prompt,
    # max_new) signature — prompts are padded to one length, so pass 2
    # times pure decode, the most charitable sequential number)
    if guard.remaining() > 20.0:
        def one_shot(p, n):
            ids = np.zeros((1, mpl), np.int32)
            ids[0, :len(p)] = p
            out = generate(net, ids, max_new_tokens=n,
                           valid_len=np.array([len(p)]),
                           max_len=max_len)
            np.asarray(out)

        for p, n in workload:          # warm every signature
            one_shot(p, n)
        t0 = time.perf_counter()
        for p, n in workload:
            one_shot(p, n)
        t_seq = time.perf_counter() - t0
        seq_tps = total_new / t_seq
    else:
        t_seq, seq_tps = 0.0, 0.0

    snap = telemetry.snapshot()
    guard.best.update({
        "value": round(serve_tps, 2),
        "phase": "serve",
        "requests": num_requests,
        "tokens_generated": total_new,
        "serve_wall_s": round(t_serve, 3),
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 2),
        "ttft_p95_ms": round(float(np.percentile(ttfts, 95)) * 1e3, 2),
        "tokens_per_sec_per_chip": round(serve_tps / chips, 2),
        "sequential_tokens_per_sec": round(seq_tps, 2),
        "serve_speedup": round(serve_tps / seq_tps, 2) if seq_tps
        else 0.0,
        "preemptions": int(sum(r.preemptions for r in reqs)),
        "kv_blocks_free_gauge": snap.get("gauges", {}).get(
            "serving_kv_blocks_free"),
        **{k: v for k, v in server.compile_stats().items()},
    })
    guard.emit()
    telemetry.disable()
    telemetry.reset()


def mixed_phase(on_tpu, guard, num_requests=24, seed=0):
    """--mixed: the tail-latency bench. Poisson arrivals of a
    heavy-tailed prompt mix (mostly short prompts, ~1/4 at the full
    max_prompt_len) through a ladder of server configs: a baseline
    server SIZED for short prompts only (the prefill executable pads
    to max_prompt_len, so the honest no-long-prompt floor needs a
    small-mpl server, not a big server fed small prompts), mixed
    without chunking (long prefills stall the tick), mixed WITH
    chunked prefill (the tick-time bound under test), and mixed with
    chunking + n-gram speculation (accept rate reported honestly —
    the untrained bench model's outputs are barely draftable).

    A fifth drain-mode leg isolates the verify mechanism: decode-heavy
    requests with speculation off vs ON with an oracle proposer
    (drafts precomputed from one-shot generate(), standing in for a
    strong draft model at accept rate 1.0) — TPOT there is pure
    mechanism cost, the ceiling a real proposer approaches.

    The headline claims: max tick wall-time with chunking <= 2x the
    short-sized baseline (`chunk_bound_ok`), and oracle-speculative
    TPOT >= 1.3x non-speculative (`spec_tpot_ok`) — both recorded as
    booleans, never a crash (bench contract: one JSON line, rc 0)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.models.llama_infer import generate
    from mxnet_tpu.serving import InferenceServer

    cfg, net = _build_net(on_tpu, serve=True)
    if on_tpu:
        slots, max_len, block, mpl, chunk = 8, 512, 16, 128, 32
        short_lo, short_hi, new_choices = 8, 24, (64, 96)
        arrival_rate, spec_new = 64.0, 128
    else:
        slots, max_len, block, mpl, chunk = 4, 128, 8, 64, 16
        short_lo, short_hi, new_choices = 4, 8, (8, 16, 24)
        arrival_rate, spec_new = 120.0, 48
    mpl_short = chunk      # baseline server padded to the chunk width

    rs = np.random.RandomState(seed)
    # heavy-tailed mix: ~1/4 of prompts at the full window. Half the
    # prompts are a tiled 3-token motif (retrieval/template traffic,
    # the shape prompt-lookup speculation feeds on); the rest random.
    def make_prompt(T):
        if rs.rand() < 0.5:
            motif = rs.randint(0, cfg.vocab_size, 3)
            return np.tile(motif, (T + 2) // 3)[:T].astype(np.int32)
        return rs.randint(0, cfg.vocab_size, T).astype(np.int32)

    mixed, short_only = [], []
    for i in range(num_requests):
        n = int(rs.choice(new_choices))
        T_short = int(rs.randint(short_lo, short_hi + 1))
        short_only.append((make_prompt(T_short), n))
        T = mpl if i % 4 == 0 else T_short
        mixed.append((make_prompt(T), n))

    def drive(workload, mpl=mpl, **server_kw):
        server = InferenceServer(net, batch_slots=slots,
                                 max_len=max_len, block_size=block,
                                 max_prompt_len=mpl, **server_kw)
        # warm every executable out of the measured window
        server.submit(workload[0][0], max_new_tokens=2)
        server.run()
        gaps = rs.exponential(1.0 / arrival_rate, len(workload))
        t_start = time.perf_counter()
        arrivals = t_start + np.cumsum(gaps)
        pending = list(zip(arrivals, workload))
        reqs, ticks = [], []
        while pending or server.queue or server.stats()["active"] \
                or server.stats()["prefilling"]:
            now = time.perf_counter()
            while pending and pending[0][0] <= now:
                _, (p, n) = pending.pop(0)
                reqs.append(server.submit(p, max_new_tokens=n))
            t0 = time.perf_counter()
            did = server.step()
            dt = time.perf_counter() - t0
            if did:
                ticks.append(dt)
            elif pending and not server.queue:
                time.sleep(max(0.0, pending[0][0] - time.perf_counter()))
        wall = time.perf_counter() - t_start
        stats = server.stats()
        return reqs, np.array(ticks), wall, stats

    def tails(reqs):
        ttfts = np.array([r.ttft for r in reqs if r.ttft is not None])
        tpots = np.array([
            (r.t_last_token - r.t_first_token) / (len(r.output_tokens) - 1)
            for r in reqs
            if r.t_first_token is not None and r.t_last_token is not None
            and len(r.output_tokens) > 1])
        pct = lambda a, q: round(float(np.percentile(a, q)) * 1e3, 3) \
            if a.size else 0.0
        return {"ttft_p50_ms": pct(ttfts, 50),
                "ttft_p95_ms": pct(ttfts, 95),
                "tpot_p50_ms": pct(tpots, 50),
                "tpot_p95_ms": pct(tpots, 95)}

    telemetry.enable()
    legs = {}
    # leg 1: short prompts through a server SIZED for short prompts —
    # the tick-time floor the chunking bound is judged against
    _, ticks_s, _, _ = drive(short_only, mpl=mpl_short)
    base_max_tick = float(np.max(ticks_s))
    # leg 2: heavy tail, monolithic prefill — the problem being fixed
    reqs_m, ticks_m, wall_m, _ = drive(mixed)
    legs["nochunk"] = tails(reqs_m)
    # leg 3: heavy tail, chunked prefill — the bound under test
    reqs_c, ticks_c, wall_c, _ = drive(mixed,
                                       prefill_chunk_tokens=chunk)
    legs["chunk"] = tails(reqs_c)
    ratio_nochunk = float(np.max(ticks_m)) / base_max_tick
    ratio_chunk = float(np.max(ticks_c)) / base_max_tick
    chunk_bound_ok = ratio_chunk <= 2.0
    # leg 4: chunking + n-gram speculation on the same mixed traffic —
    # the honest self-drafting number (untrained model, low accept)
    reqs_x, ticks_x, wall_x, stats_x = drive(
        mixed, prefill_chunk_tokens=chunk, speculative=4)
    legs["chunk_spec"] = tails(reqs_x)
    accept_rate = stats_x.get("draft_accept_rate", 0.0)

    # leg 5: the verify-mechanism TPOT, isolated. Decode-heavy drain
    # runs (no arrivals jitter), speculation off vs oracle drafts of
    # the precomputed greedy continuation — accept rate 1.0 by
    # construction, so the speedup measures what the single-dispatch
    # k-position verify actually buys per tick.
    spec_prompts = [rs.randint(0, cfg.vocab_size,
                               short_hi).astype(np.int32)
                    for _ in range(slots * 2)]
    oracle_seq = {}
    for p in spec_prompts:
        out = np.asarray(generate(net, p[None, :],
                                  max_new_tokens=spec_new,
                                  max_len=max_len))
        oracle_seq[p.tobytes()] = np.concatenate(
            [p, out[0, len(p):len(p) + spec_new]]).astype(np.int32)

    class _Oracle:
        k = 4

        def propose(self, tokens):
            t = np.asarray(tokens, np.int32)
            seq = oracle_seq.get(t[:short_hi].tobytes())
            if seq is None:
                return np.zeros(0, np.int32)
            return seq[len(t):len(t) + self.k + 1]

    spec_walls = {}
    for name, spec in (("off", None), ("oracle", _Oracle())):
        srv = InferenceServer(net, batch_slots=slots, max_len=max_len,
                              block_size=block,
                              max_prompt_len=mpl_short,
                              speculative=spec)
        srv.submit(spec_prompts[0], max_new_tokens=2)
        srv.run()                            # warm
        srs = [srv.submit(p, max_new_tokens=spec_new)
               for p in spec_prompts]
        t0 = time.perf_counter()
        srv.run()
        spec_walls[name] = time.perf_counter() - t0
        if name == "oracle":
            oracle_accept = srv.stats()["draft_accept_rate"]
            spec_parity = all(
                list(r.output_tokens)
                == oracle_seq[p.tobytes()][len(p):].tolist()
                for p, r in zip(spec_prompts, srs))
    spec_speedup = spec_walls["off"] / spec_walls["oracle"] \
        if spec_walls["oracle"] else 0.0
    spec_tokens = len(spec_prompts) * spec_new

    total_new = sum(n for _, n in mixed)
    guard.best.update({
        "value": round(ratio_chunk, 3),
        "phase": "mixed",
        "requests": num_requests,
        "tokens_generated": total_new,
        "prompt_mix": {"short": [short_lo, short_hi], "long": mpl,
                       "long_fraction": 0.25},
        "chunk_tokens": chunk,
        "base_max_tick_ms": round(base_max_tick * 1e3, 3),
        "max_tick_gap_ratio_nochunk": round(ratio_nochunk, 3),
        "max_tick_gap_ratio_chunk": round(ratio_chunk, 3),
        "chunk_bound_ok": bool(chunk_bound_ok),
        "legs": legs,
        "mixed_tokens_per_sec": round(total_new / wall_c, 2),
        "ngram_draft_accept_rate": round(float(accept_rate), 3),
        "ngram_tokens_accepted": stats_x.get("spec_tokens_accepted",
                                             0),
        "ngram_tokens_rejected": stats_x.get("spec_tokens_rejected",
                                             0),
        "spec_leg": {"requests": len(spec_prompts),
                     "new_tokens_each": spec_new,
                     "tpot_off_ms": round(
                         spec_walls["off"] / spec_tokens * 1e3, 3),
                     "tpot_oracle_ms": round(
                         spec_walls["oracle"] / spec_tokens * 1e3, 3),
                     "oracle_accept_rate": round(float(oracle_accept),
                                                 3),
                     "oracle_parity": bool(spec_parity)},
        "spec_tpot_speedup": round(spec_speedup, 3),
        "spec_tpot_ok": bool(spec_speedup >= 1.3 and spec_parity),
    })
    for k, v in (("bench_mixed_max_tick_gap_ratio", ratio_chunk),
                 ("bench_mixed_max_tick_gap_ratio_nochunk",
                  ratio_nochunk),
                 ("bench_mixed_ttft_p95_ms",
                  legs["chunk"]["ttft_p95_ms"]),
                 ("bench_mixed_tpot_p50_ms",
                  legs["chunk"]["tpot_p50_ms"]),
                 ("bench_mixed_spec_tpot_speedup", spec_speedup),
                 ("bench_mixed_draft_accept_rate", accept_rate)):
        telemetry.set_gauge(k, float(v), bench="decode_mixed")
    guard.emit()
    telemetry.disable()
    telemetry.reset()


def _fleet_spawn(d, name, cfg_json, fault=None, max_wall_s=300,
                 extra_env=None):
    """One subprocess fleet replica over the FileKV channel. Workers
    always run on CPU: the chip belongs to the parent process, and
    this phase counts what the ROUTER does (failover, shedding, lost
    and duplicated requests), not chip throughput. `extra_env` rides
    into the worker (the --slo legs use it to enable telemetry +
    flight recorder)."""
    import subprocess

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_TPU_FAULTS", None)
    env["JAX_PLATFORMS"] = "cpu"
    if extra_env:
        env.update(extra_env)
    if fault:
        env["MXNET_TPU_FAULTS"] = fault
    log = open(os.path.join(d, f"{name}.log"), "w")
    return subprocess.Popen(
        [sys.executable, "-u", "-m", "mxnet_tpu.serving.router",
         "--dir", d, "--name", name, "--config", cfg_json,
         "--slots", "4", "--max-len", "64", "--block", "8",
         "--max-prompt", "16", "--max-wall-s", str(max_wall_s)],
        stdout=log, stderr=log, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fleet_leg(d, n_workers, cfg_json, workload, arrival_rate, rs,
               kill=False, faults=None, slo=False, router_kw=None):
    """Poisson-drive `workload` through an N-replica subprocess fleet;
    returns (requests, wall_s, router_stats, worker_rcs, final_stats,
    slo_info). `faults` maps worker name -> MXNET_TPU_FAULTS spec;
    `slo=True` enables telemetry + flight in the workers, attaches a
    burn-rate SLOEngine over the fleet-merged registry, and collects a
    flight bundle into `d` on the alert's rising edge."""
    import signal as _signal

    from mxnet_tpu.serving.router import FileKV, FleetRouter, ProcReplica

    faults = dict(faults or {})
    if kill:
        faults.setdefault("w0", "replica.kill:at=8")
    extra_env = {"MXNET_TPU_TELEMETRY": "1",
                 "MXNET_TPU_FLIGHT": "1",
                 "MXNET_TPU_FLIGHT_DIR": d} if slo else None
    kv = FileKV(d)
    procs = [_fleet_spawn(d, f"w{i}", cfg_json,
                          fault=faults.get(f"w{i}"),
                          extra_env=extra_env)
             for i in range(n_workers)]
    engine = None
    slo_info = {}
    try:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 240:
            if all(kv.get(f"fleet/w{i}/hb") is not None
                   for i in range(n_workers)):
                break
            for i, p in enumerate(procs):
                if p.poll() is not None:
                    raise RuntimeError(
                        f"fleet worker w{i} died during warmup "
                        f"(rc={p.returncode}), see {d}/w{i}.log")
            time.sleep(0.05)
        else:
            raise RuntimeError("fleet workers never became healthy")

        fleet_kw = dict(affinity_blocks=0, backoff_base_s=0.01,
                        heartbeat_timeout_s=2.0)
        fleet_kw.update(router_kw or {})
        fleet = FleetRouter(
            [ProcReplica(kv, f"w{i}") for i in range(n_workers)],
            **fleet_kw)
        if slo:
            from mxnet_tpu import flight as _flight
            from mxnet_tpu import telemetry as _telemetry
            from mxnet_tpu.slo import Objective

            _telemetry.enable()
            _flight.enable()
            fired_health = []
            engine = fleet.attach_slo(
                objectives=[Objective("ttft_under_500ms",
                                      metric="serving_ttft_seconds",
                                      target=0.7, threshold_s=0.5)],
                fast_window_s=1.0, slow_window_s=4.0,
                burn_threshold=1.0, tick_interval_s=0.05,
                bundle_dir=d,
                on_alert=lambda name, info:
                    fired_health.append(fleet._slo.health()[1]))
            slo_info["fired_health"] = fired_health
        gaps = rs.exponential(1.0 / arrival_rate, len(workload))
        t_start = time.perf_counter()
        arrivals = t_start + np.cumsum(gaps)
        pending = list(zip(arrivals, workload))
        frs = []
        while pending or fleet._queue or fleet._inflight:
            now = time.perf_counter()
            while pending and pending[0][0] <= now:
                _, (p, n) = pending.pop(0)
                frs.append(fleet.submit(p, n))
            if fleet.step() == 0:
                time.sleep(0.002)
        wall = time.perf_counter() - t_start
        stats = fleet.stats()
        if engine is not None:
            slo_info["alerts"] = engine.alerts_total
            slo_info["bundle"] = fleet.last_bundle_path
        final = fleet.stop_fleet(timeout_ms=30_000)
        rcs = []
        for p in procs:
            try:
                rcs.append(p.wait(timeout=60))
            except Exception:
                p.kill()
                rcs.append(p.wait(timeout=30))
        return frs, wall, stats, rcs, final, slo_info
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
        if slo:
            from mxnet_tpu import flight as _flight
            from mxnet_tpu import telemetry as _telemetry
            if engine is not None:
                _telemetry.unregister_health_source(engine)
            _telemetry.set_fleet_metrics_provider(None)
            _flight.disable()
            _flight.clear()
            _telemetry.disable()
            _telemetry.reset()


def fleet_phase(on_tpu, guard, fleet_n=2, num_requests=16,
                arrival_rate=None, seed=0, slo=False):
    """--fleet N: the resilient-serving bench. Three legs over the same
    Poisson workload of subprocess replicas on the FileKV channel:
    one replica (the scaling baseline), N replicas (fleet TTFT p50/p95
    + tokens/sec per replica vs 1), and N replicas with one SIGKILLed
    mid-run by `replica.kill` — the robustness claim is ZERO lost and
    ZERO duplicated requests across the failover.

    --slo adds two SLO legs over a small trickle workload with a
    burn-rate SLOEngine attached to the router's fleet-merged registry:
    a clean leg where the alert must stay SILENT, and a leg with
    `replica.stall` armed in every worker where the multi-window burn
    alert must FIRE, flip health to the violated objective's name, and
    collect a cross-process flight bundle that the merge CLI stitches
    into one ordered timeline."""
    import tempfile

    from mxnet_tpu import telemetry

    # must match _build_net(serve=True)'s CPU config — the workers
    # rebuild it from this JSON with the same seed
    cfg_kw = dict(vocab_size=2048, hidden_size=256,
                  intermediate_size=1024, num_layers=4, num_heads=8,
                  num_kv_heads=4, max_seq_len=128, dtype="float32")
    cfg_json = json.dumps(cfg_kw)
    arrival_rate = arrival_rate or 200.0
    mpl, new_choices = 16, (8, 16, 24)

    rs = np.random.RandomState(seed)
    workload = []
    for _ in range(num_requests):
        T = int(rs.randint(4, mpl + 1))
        p = rs.randint(0, cfg_kw["vocab_size"], T).astype(np.int32)
        workload.append((p, int(rs.choice(new_choices))))
    total_new = sum(n for _, n in workload)

    def leg(n_workers, kill):
        d = tempfile.mkdtemp(prefix="fleet_bench_")
        return _fleet_leg(d, n_workers, cfg_json, workload,
                          arrival_rate, np.random.RandomState(seed),
                          kill=kill)

    # leg 1: single replica (the baseline the fleet is judged against)
    frs1, wall1, _, _, _, _ = leg(1, kill=False)
    single_tps = total_new / wall1

    # leg 2: N replicas, clean — the headline fleet number
    frsN, wallN, statsN, _, _, _ = leg(fleet_n, kill=False)
    fleet_tps = total_new / wallN
    ttfts = [fr.ttft_s for fr in frsN if fr.ttft_s is not None]
    ttft_p50 = float(np.percentile(ttfts, 50)) if ttfts else 0.0
    ttft_p95 = float(np.percentile(ttfts, 95)) if ttfts else 0.0

    # leg 3: N replicas, one SIGKILLed mid-run
    kill_ok = lost = dup = failovers = 0
    kill_rc0 = None
    if guard.remaining() > 30.0:
        frsK, _, statsK, rcsK, _, _ = leg(fleet_n, kill=True)
        kill_ok = sum(1 for fr in frsK if fr.status == "ok")
        lost = len(workload) - len(frsK) \
            + sum(1 for fr in frsK if fr.status != "ok")
        dup = statsK["duplicates"]
        failovers = statsK["failovers"]
        kill_rc0 = rcsK[0]

    # --slo legs: burn-rate alerting end to end on a trickle workload
    slo_res = {}
    if slo and guard.remaining() > 60.0:
        from mxnet_tpu import flight as _flight

        rsS = np.random.RandomState(seed + 1)
        slo_workload = [(rsS.randint(0, cfg_kw["vocab_size"],
                                     8).astype(np.int32), 4)
                        for _ in range(10)]
        # hedging off + a heartbeat timeout above the stall so the
        # stalled workers stay "healthy but slow" — the burn-rate case,
        # not the failover case
        slo_router_kw = dict(hedge_after_s=30.0,
                             heartbeat_timeout_s=5.0)

        def slo_leg(faults):
            d = tempfile.mkdtemp(prefix="fleet_slo_")
            *_, info = _fleet_leg(
                d, fleet_n, cfg_json, slo_workload, 8.0,
                np.random.RandomState(seed + 1), faults=faults,
                slo=True, router_kw=slo_router_kw)
            return info

        clean = slo_leg(None)
        # every worker sleeps ~1s after each productive tick: almost
        # every TTFT lands over the 0.5s objective, so BOTH burn
        # windows blow past the threshold
        stall = slo_leg({f"w{i}": "replica.stall:ms=1000"
                         for i in range(fleet_n)})
        health = (stall.get("fired_health") or [""])[0]
        merged_events, ordered, n_sources = 0, False, 0
        bundle = stall.get("bundle")
        if bundle:
            merged = _flight.merge([bundle])
            with open(merged) as f:
                lines = [ln for ln in f.read().splitlines()
                         if ln.strip()]
            n_sources = len(json.loads(lines[0])["sources"])
            ts = [json.loads(ln)["t_unix"] for ln in lines[1:]]
            merged_events = len(ts)
            ordered = ts == sorted(ts)
        slo_res = {
            "slo_clean_alerts": clean.get("alerts", 0),
            "slo_stall_alerts": stall.get("alerts", 0),
            "slo_alert_fired": bool(stall.get("alerts", 0)),
            "slo_health_reason": health[:160],
            "slo_bundle_sources": n_sources,
            "slo_merged_events": merged_events,
            "slo_merged_ordered": ordered,
            "slo_pass": bool(stall.get("alerts", 0)
                             and clean.get("alerts", 0) == 0
                             and "ttft_under_500ms" in health
                             and n_sources >= 1 + fleet_n
                             and merged_events > 0 and ordered),
        }

    guard.best.update(slo_res)
    guard.best.update({
        "value": round(fleet_tps, 2),
        "phase": "fleet",
        "fleet_n": fleet_n,
        "requests": num_requests,
        "tokens_generated": total_new,
        "workers_backend": "cpu",
        "fleet_wall_s": round(wallN, 3),
        "fleet_ttft_p50_ms": round(ttft_p50 * 1e3, 2),
        "fleet_ttft_p95_ms": round(ttft_p95 * 1e3, 2),
        "single_tokens_per_sec": round(single_tps, 2),
        "fleet_tokens_per_sec": round(fleet_tps, 2),
        "fleet_tokens_per_sec_per_replica": round(fleet_tps / fleet_n,
                                                  2),
        "fleet_speedup_vs_single": round(fleet_tps / single_tps, 2)
        if single_tps else 0.0,
        "fleet_retries": statsN["retries"],
        "fleet_hedges": statsN["hedges"],
        "kill_leg_ok": kill_ok,
        "kill_leg_lost_requests": lost,
        "kill_leg_duplicates": dup,
        "kill_leg_failovers": failovers,
        "kill_leg_worker0_rc": kill_rc0,  # -9 = SIGKILL landed
        "fleet_zero_lost": bool(kill_rc0 is not None and lost == 0
                                and dup == 0),
    })
    telemetry.enable()
    for k, v in (("bench_fleet_tokens_per_sec", fleet_tps),
                 ("bench_fleet_ttft_p50_ms", ttft_p50 * 1e3),
                 ("bench_fleet_ttft_p95_ms", ttft_p95 * 1e3),
                 ("bench_fleet_speedup_vs_single",
                  fleet_tps / single_tps if single_tps else 0.0),
                 ("bench_fleet_lost_requests", float(lost)),
                 ("bench_fleet_failovers", float(failovers))):
        telemetry.set_gauge(k, float(v), bench="decode_fleet")
    if slo_res:
        for k, v in (("bench_slo_alert_fired",
                      slo_res["slo_alert_fired"]),
                     ("bench_slo_clean_alerts",
                      slo_res["slo_clean_alerts"]),
                     ("bench_slo_bundle_sources",
                      slo_res["slo_bundle_sources"]),
                     ("bench_slo_merged_events",
                      slo_res["slo_merged_events"]),
                     ("bench_slo_pass", slo_res["slo_pass"])):
            telemetry.set_gauge(k, float(v), bench="decode_fleet")
    guard.emit()
    telemetry.disable()
    telemetry.reset()


def canary_phase(on_tpu, guard, seed=0):
    """--canary: the canary-gated rolling-restart acceptance. Two legs
    over the same up-front workload through 2 subprocess replicas on
    the FileKV channel (worker telemetry + flight shipped via
    heartbeats, an AnomalyEngine attached to the router):

    - degrade leg: `replica.degrade:ms=300` armed in w0's env — alive,
      heartbeating, ~30x slower between decode ticks. The canaried
      restart re-admits w0 at 0.5 routing weight; the analysis catches
      its inter-token latency drifting whole log2 buckets past the
      fleet peer, rolls it back out of rotation
      (router_canary_rollbacks_total >= 1) and collects
      flight-bundle-canary_fail with evidence from >= 2 processes —
      while every request still completes and the victim traffic on
      the healthy peer holds its TPOT SLO.
    - clean leg: no fault. The identical restart must promote the
      canary with ZERO rollbacks and ZERO anomaly alerts (the engine
      forgets the restarted replica's compile/clock anchors, so the
      rebuild's recompiles don't read as a storm)."""
    import tempfile

    from mxnet_tpu import flight as _flight
    from mxnet_tpu import telemetry as _telemetry
    from mxnet_tpu.anomaly import CanarySpec
    from mxnet_tpu.serving.router import FileKV, FleetRouter, ProcReplica

    cfg_kw = dict(vocab_size=2048, hidden_size=256,
                  intermediate_size=1024, num_layers=4, num_heads=8,
                  num_kv_heads=4, max_seq_len=128, dtype="float32")
    cfg_json = json.dumps(cfg_kw)

    def leg(degrade):
        d = tempfile.mkdtemp(prefix="fleet_canary_")
        kv = FileKV(d)
        extra_env = {"MXNET_TPU_TELEMETRY": "1",
                     "MXNET_TPU_FLIGHT": "1",
                     "MXNET_TPU_FLIGHT_DIR": d}
        procs = [_fleet_spawn(
            d, f"w{i}", cfg_json,
            fault="replica.degrade:ms=300" if degrade and i == 0
            else None,
            extra_env=extra_env) for i in range(2)]
        engine = None
        try:
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 240:
                if all(kv.get(f"fleet/w{i}/hb") is not None
                       for i in range(2)):
                    break
                for i, p in enumerate(procs):
                    if p.poll() is not None:
                        raise RuntimeError(
                            f"canary worker w{i} died during warmup "
                            f"(rc={p.returncode}), see {d}/w{i}.log")
                time.sleep(0.05)
            else:
                raise RuntimeError(
                    "canary workers never became healthy")

            _telemetry.enable()
            _flight.enable()
            _flight.clear()
            fleet = FleetRouter(
                [ProcReplica(kv, f"w{i}") for i in range(2)],
                affinity_blocks=0, backoff_base_s=0.01,
                heartbeat_timeout_s=5.0, hedge_after_s=30.0)
            # rate detectors stay off for this phase: the restart
            # deliberately reshapes fleet throughput (drain halves
            # it, promotion doubles it) and any z-score worth having
            # would flag exactly that
            engine = fleet.attach_anomaly(bundle_dir=d,
                                          rate_metrics=())
            # enough queued work to outlast drain + restart + canary
            # window: the analysis needs live traffic through BOTH
            # the canary and the peer after the restart
            rs = np.random.RandomState(seed)
            frs = [fleet.submit(
                rs.randint(1, cfg_kw["vocab_size"], 6).astype(np.int32),
                6) for _ in range(80)]
            res = fleet.rolling_restart(
                drain_timeout_s=90.0, restart_timeout_s=90.0,
                replicas=["w0"],
                canary=CanarySpec(weight=0.5, min_samples=4,
                                  window_s=60.0, drift_buckets=2,
                                  metrics=("serving_tpot_seconds",)),
                canary_timeout_s=120.0, bundle_dir=d)
            # snapshot at the verdict: the acceptance window is the
            # restart itself, not the tail drain after it
            alerts = engine.alerts_total
            rollbacks = fleet.n_canary_rollbacks
            promotions = fleet.n_canary_promotions
            n_sources = 0
            man = os.path.join(d, "flight-bundle-canary_fail",
                               "manifest.json")
            if os.path.exists(man):
                with open(man) as f:
                    n_sources = len(json.load(f)["sources"])
            fleet.run(timeout_s=240)
            ok = sum(1 for fr in frs if fr.status == "ok")
            # victim traffic = requests the healthy peers served; TPOT
            # strips the router queue wait, so its p95 shows whether
            # the degradation leaked past the canary's weighted slice
            tpots = [(fr.t_finish - fr.t_submit - fr.ttft_s)
                     / max(len(fr.output_tokens) - 1, 1)
                     for fr in frs
                     if fr.status == "ok" and fr.replica != "w0"
                     and fr.ttft_s is not None
                     and fr.t_finish is not None
                     and len(fr.output_tokens) > 1]
            victim_p95 = float(np.percentile(tpots, 95)) if tpots \
                else 0.0
            fleet.stop_fleet(timeout_ms=30_000)
            return {"verdict": res[0]["canary"],
                    "reason": str((res[0]["report"] or {})
                                  .get("reason", "")),
                    "rollbacks": rollbacks, "promotions": promotions,
                    "alerts": alerts, "bundle_sources": n_sources,
                    "ok": ok, "n": len(frs),
                    "victim_tpot_p95_ms": victim_p95 * 1e3}
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)
            if engine is not None:
                _telemetry.unregister_health_source(engine)
            _telemetry.set_fleet_metrics_provider(None)
            _flight.disable()
            _flight.clear()
            _telemetry.disable()
            _telemetry.reset()

    bad = leg(degrade=True)
    clean = leg(degrade=False)

    victim_slo_ms = 250.0   # the fault inflates canary TPOT to 300ms+
    canary_pass = bool(
        bad["verdict"] == "rolled_back" and bad["rollbacks"] >= 1
        and bad["bundle_sources"] >= 2 and bad["ok"] == bad["n"]
        and bad["victim_tpot_p95_ms"] <= victim_slo_ms
        and clean["verdict"] == "promoted"
        and clean["rollbacks"] == 0 and clean["alerts"] == 0
        and clean["ok"] == clean["n"])
    guard.best.update({
        "value": 1.0 if canary_pass else 0.0,
        "phase": "canary",
        "workers_backend": "cpu",
        "canary_pass": canary_pass,
        "canary_degrade_verdict": bad["verdict"],
        "canary_degrade_reason": bad["reason"][:120],
        "canary_rollbacks": bad["rollbacks"],
        "canary_bundle_sources": bad["bundle_sources"],
        "canary_victim_tpot_p95_ms":
            round(bad["victim_tpot_p95_ms"], 2),
        "canary_victim_tpot_slo_ms": victim_slo_ms,
        "canary_degrade_completed": f'{bad["ok"]}/{bad["n"]}',
        "canary_clean_verdict": clean["verdict"],
        "canary_clean_alerts": clean["alerts"],
        "canary_clean_rollbacks": clean["rollbacks"],
        "canary_clean_promotions": clean["promotions"],
        "canary_clean_completed": f'{clean["ok"]}/{clean["n"]}',
    })
    _telemetry.enable()
    for k, v in (("bench_canary_pass", canary_pass),
                 ("bench_canary_rollbacks", bad["rollbacks"]),
                 ("bench_canary_bundle_sources",
                  bad["bundle_sources"]),
                 ("bench_canary_victim_tpot_p95_ms",
                  bad["victim_tpot_p95_ms"]),
                 ("bench_canary_clean_alerts", clean["alerts"]),
                 ("bench_canary_clean_rollbacks",
                  clean["rollbacks"])):
        _telemetry.set_gauge(k, float(v), bench="decode_canary")
    guard.emit()
    _telemetry.disable()
    _telemetry.reset()


def paged_kernel_phase(on_tpu, guard):
    """--paged-kernel: decode HBM bytes for the three decode-tick
    attention variants — contiguous flash-decode (the floor), the
    gather fallback (pool copy -> contiguous sweep), and the in-kernel
    paged path (scalar-prefetch block table, blocks DMA'd per grid
    cell). Floor: in-kernel <= 1.2x contiguous bytes, with the
    gather's pool-sized copy gone.

    Byte sources: `memory_analysis()` on the compiled executables is
    reported verbatim for all three. The floor verdict uses those
    measured numbers when the kernel compiles natively (TPU); on CPU
    the in-kernel path runs under the Pallas INTERPRETER, whose
    simulation temps say nothing about the kernel's HBM behavior, so
    the verdict falls back to the exact analytic traffic model and
    `bytes_source` says so."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import telemetry
    from mxnet_tpu.kernels import flash_decode as fd
    from mxnet_tpu.serving import InferenceServer

    if on_tpu:
        B, H, K, d, bs, dtype = 8, 16, 8, 64, 32, jnp.bfloat16
        S = 2048
    else:
        B, H, K, d, bs, dtype = 4, 8, 4, 32, 16, jnp.float32
        S = 128
    nb = S // bs
    N = B * nb + 1                       # + scratch block 0
    itemsize = jnp.dtype(dtype).itemsize
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, H, d) * 0.1, dtype)
    kc = jnp.asarray(rs.randn(B, K, S, d) * 0.1, dtype)
    vc = jnp.asarray(rs.randn(B, K, S, d) * 0.1, dtype)
    kp = jnp.asarray(rs.randn(N, K, bs, d) * 0.1, dtype)
    vp = jnp.asarray(rs.randn(N, K, bs, d) * 0.1, dtype)
    bt = jnp.arange(1, N, dtype=jnp.int32).reshape(B, nb)
    vl = jnp.full((B,), S, jnp.int32)

    mode = fd.paged_kernel_mode(kp)
    if mode is None and not on_tpu:
        os.environ["MXNET_TPU_FLASH_INTERPRET"] = "1"
        mode = fd.paged_kernel_mode(kp)

    def mem(f, *args):
        ma = jax.jit(f).lower(*args).compile().memory_analysis()
        return {"temp": int(ma.temp_size_in_bytes),
                "args": int(ma.argument_size_in_bytes),
                "out": int(ma.output_size_in_bytes)}

    measured = {
        "contiguous": mem(lambda q_, k_, v_, l_:
                          fd.flash_decode(q_, k_, v_, l_),
                          q, kc, vc, vl),
        "paged_gather": mem(lambda q_, k_, v_, b_, l_:
                            fd.flash_decode_paged(q_, k_, v_, b_, l_,
                                                  use_flash=False),
                            q, kp, vp, bt, vl),
        "paged_inkernel": mem(lambda q_, k_, v_, b_, l_:
                              fd.flash_decode_paged(q_, k_, v_, b_, l_),
                              q, kp, vp, bt, vl),
    }
    # exact analytic decode-attention traffic at these shapes: every
    # path reads q + the B*K*S*d k/v tokens and writes the output; the
    # gather additionally WRITES the contiguous (B, K, S, d) view and
    # reads it back in the sweep — the pool-sized round trip the
    # in-kernel path deletes (paged_gather_bytes counts exactly it)
    view = 2 * B * K * S * d * itemsize
    qio = 2 * B * H * d * itemsize
    gather_extra = fd.paged_gather_bytes(kp.shape, bt.shape, itemsize)
    analytic = {"contiguous": view + qio,
                "paged_inkernel": view + qio,
                "paged_gather": view + qio + 2 * gather_extra}

    native = on_tpu and mode == "compiled"
    src = {k: (v["temp"] + v["args"] + v["out"])
           for k, v in measured.items()} if native else analytic
    ratio = src["paged_inkernel"] / max(src["contiguous"], 1)
    copy_gone = (src["paged_gather"] - src["paged_inkernel"]) >= view
    floor_ok = ratio <= 1.2 and copy_gone

    # the serving acceptance rider: the kernel plugs into the server's
    # persistent decode program with ZERO extra compiles
    cfg, net = _build_net(on_tpu, serve=True)
    server = InferenceServer(net, batch_slots=4,
                             max_len=128 if on_tpu else 64,
                             block_size=16, max_prompt_len=16)
    for i in range(6):
        server.submit(rs.randint(0, cfg.vocab_size, 8 + i).astype(
            np.int32), max_new_tokens=8)
    server.run()
    cs = server.compile_stats()

    guard.best.update({
        "value": round(ratio, 4),
        "phase": "paged_kernel",
        "kernel_mode": mode or "gather-fallback",
        "bytes_source": "memory_analysis" if native else "analytic",
        "shape": [B, H, K, d, S, bs],
        "measured_bytes": measured,
        "analytic_bytes": analytic,
        "inkernel_vs_contiguous": round(ratio, 4),
        "gather_copy_bytes_per_call": int(gather_extra),
        "gather_copy_gone": bool(copy_gone),
        "floor_ok": bool(floor_ok),
        "paged_fallbacks": fd._paged_fallback.count,
        "serve_decode_compiles": cs["decode_compiles"],
        "serve_prefill_compiles": cs["prefill_compiles"],
    })
    telemetry.enable()
    for k, v in (("bench_paged_contig_bytes", src["contiguous"]),
                 ("bench_paged_gather_bytes", src["paged_gather"]),
                 ("bench_paged_inkernel_bytes", src["paged_inkernel"]),
                 ("bench_paged_bytes_ratio", ratio)):
        telemetry.set_gauge(k, float(v), bench="decode_paged")
    guard.emit()
    telemetry.disable()
    telemetry.reset()


def oom_forecast_phase(on_tpu, guard, seed=0):
    """--oom-forecast: memory-pressure steering end to end. Two
    in-process replicas behind FleetRouter — r0 with a deliberately
    tight KV pool (and a slow background decode whose block burn feeds
    its PoolForecaster a declining free-blocks trend), r1 roomy but
    more loaded (so least-loaded routing would pack r0). The same long
    prompts run twice:

    - control leg (`exhaust_window_s=None`): the router packs r0, whose
      pool exhausts mid-decode — preemptions land (>0).
    - forecast leg (`exhaust_window_s` armed): r0's heartbeat carries
      `exhaust_in_s` from the goodput forecaster, the router diverts
      the long prompts to r1 BEFORE r0 has to preempt — zero
      preemptions, diverted counter > 0.

    The headline `value` is control preemptions minus forecast
    preemptions (positive = the forecaster bought real headroom);
    `forecast_pass` is the acceptance boolean."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import InferenceServer
    from mxnet_tpu.serving.router import FleetRouter, LocalReplica

    cfg, net = _build_net(on_tpu, serve=True)
    slots, block, mpl = 4, 8, 16
    tight_blocks = 14      # ballast + two long decodes overflow this
    long_T = 2 * block     # >= long_prompt_blocks * block -> "long"
    n_long, long_new = 4, 24

    def run_leg(use_forecast):
        telemetry.enable()
        telemetry.reset()
        rs = np.random.RandomState(seed)
        s0 = InferenceServer(net, batch_slots=slots, max_len=64,
                             block_size=block, max_prompt_len=mpl,
                             num_blocks=tight_blocks)
        # r1's block size exceeds its max_len: every sequence lives in
        # one block forever, so active decodes never allocate — its
        # blocks_free trace is FLAT and the forecaster reads "no
        # exhaustion in sight" even while it carries load. That is the
        # honest roomy-replica shape; r0 is the one burning blocks.
        s1 = InferenceServer(net, batch_slots=slots, max_len=128,
                             block_size=128, max_prompt_len=mpl,
                             num_blocks=8)
        for s in (s0, s1):     # warm the executables out of the window
            s.submit(rs.randint(0, cfg.vocab_size, 4).astype(np.int32),
                     max_new_tokens=2)
            s.run()

        def ballast(server, n, max_new):
            return [server.submit(
                rs.randint(0, cfg.vocab_size, 4).astype(np.int32),
                max_new_tokens=max_new) for _ in range(n)]

        # r0: one long slow decode — the declining blocks_free trend
        # its forecaster projects to exhaustion. r1: two (still active
        # at dispatch time), so least-loaded routing packs r0 with the
        # long prompts in the control leg.
        ball = ballast(s0, 1, 48) + ballast(s1, 2, 100)
        # r1 steps until its forecaster window (64 samples) holds only
        # flat post-allocation samples; r0 joins late so its ballast is
        # still mid-burn (declining trend) when the router first probes.
        for i in range(68):
            s1.step()
            if i >= 44:
                s0.step()
        eta0 = s0.health_detail().get("exhaust_in_s")
        eta1 = s1.health_detail().get("exhaust_in_s")
        # the window only needs to cover r0's measured time-to-exhaust
        # (r1 forecasts none) — self-calibrate so CPU tick-speed
        # variance can't push eta0 past a hard-coded horizon
        window = None
        if use_forecast:
            window = max(30.0, 4.0 * eta0) if eta0 is not None else 30.0

        fleet = FleetRouter(
            [LocalReplica(s0, name="tight"),
             LocalReplica(s1, name="roomy")],
            affinity_blocks=0, block_size=block, backoff_base_s=0.01,
            exhaust_window_s=window, long_prompt_blocks=2)
        frs = [fleet.submit(
            rs.randint(0, cfg.vocab_size, long_T).astype(np.int32),
            long_new) for _ in range(n_long)]
        fleet.run(timeout_s=120)
        s0.run()
        s1.run()               # drain the ballast decodes
        snap = telemetry.snapshot()
        out = {
            "preemptions": int(snap["counters"].get(
                "serving_preemptions_total", 0)),
            "diverted": int(snap["counters"].get(
                "router_exhaust_diverted_total", 0)),
            "ok": sum(1 for fr in frs if fr.status == "ok")
            + sum(1 for r in ball if r.status == "ok"),
            "eta0_s": round(eta0, 3) if eta0 is not None else None,
            "eta1_s": round(eta1, 3) if eta1 is not None else None,
            "window_s": round(window, 3) if window is not None else None,
        }
        for s in (s0, s1):
            telemetry.unregister_health_source(s._forecaster)
            telemetry.unregister_health_source(s)
        telemetry.disable()
        telemetry.reset()
        return out

    control = run_leg(False)
    forecast = run_leg(True)
    forecast_pass = bool(control["preemptions"] > 0
                         and forecast["preemptions"] == 0
                         and forecast["diverted"] > 0)
    guard.best.update({
        "value": control["preemptions"] - forecast["preemptions"],
        "phase": "oom_forecast",
        "tight_blocks": tight_blocks,
        "long_prompts": n_long,
        "control_preemptions": control["preemptions"],
        "forecast_preemptions": forecast["preemptions"],
        "forecast_diverted": forecast["diverted"],
        "control_ok": control["ok"],
        "forecast_ok": forecast["ok"],
        "control_eta0_s": control["eta0_s"],
        "forecast_eta0_s": forecast["eta0_s"],
        "forecast_eta1_s": forecast["eta1_s"],
        "forecast_window_s": forecast["window_s"],
        "forecast_pass": forecast_pass,
    })
    guard.emit()


def tiering_phase(on_tpu, guard, seed=0):
    """--tiering: the KV-block memory hierarchy end to end, three legs.

    - **pressure**: a pool self-calibrated to force >= 6 preemptions in
      a control (no-tiering) run must complete with ZERO destructive
      preemptions once the tier is on — evictions become host-RAM
      spills, re-admissions become restores, tokens are unchanged.
    - **warm restart**: a server persists its prefix chains on
      shutdown; a fresh server over the same store must serve a
      >=75%-shared prompt with TTFT <= 0.6x the cold-prefill TTFT
      (`tier_warm_ttft_ratio` is the headline value, lower = better).
    - **disaggregation**: a 1-prefill + 1-decode LocalReplica fleet
      must be token-identical to one combined replica, with
      `serving_blocks_streamed_total` > 0 and zero extra compiles on
      the decode replica after warm-up.

    `tier_pass` ANDs the three leg verdicts; per-leg detail and
    `bench_tier_*` gauges ride the JSON line for the sentinel."""
    import tempfile

    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import InferenceServer
    from mxnet_tpu.serving.router import FleetRouter, LocalReplica

    cfg, net = _build_net(on_tpu, serve=True)
    rs = np.random.RandomState(seed)

    def prompts(n, T):
        return [rs.randint(0, cfg.vocab_size, T).astype(np.int32)
                for _ in range(n)]

    # -- leg 1: pressure — spill instead of preempt ---------------------
    work = prompts(8, 12)

    def pressure_leg(num_blocks, tiered):
        telemetry.enable()
        telemetry.reset()
        s = InferenceServer(
            net, batch_slots=4, max_len=32, block_size=4,
            max_prompt_len=16, num_blocks=num_blocks,
            max_preemptions=20,
            kv_tiering=tiered, prefix_cache=True)
        reqs = [s.submit(p, 12, seed=i) for i, p in enumerate(work)]
        s.run()
        snap = telemetry.snapshot()["counters"]
        out = {"ok": sum(1 for r in reqs if r.status == "ok"),
               "preemptions": int(snap.get(
                   "serving_preemptions_total", 0)),
               "spill_preemptions": int(snap.get(
                   "serving_spill_preemptions_total", 0)),
               "spill_bytes": s.tier.spill_bytes if tiered else 0,
               "restore_bytes": s.tier.restore_bytes if tiered else 0}
        if tiered:
            s.cache.check()
        telemetry.unregister_health_source(s._forecaster)
        telemetry.unregister_health_source(s)
        telemetry.disable()
        telemetry.reset()
        return out

    # self-calibrate the pool: tighten until the control leg preempts
    # >= 6 times (CPU tick-speed variance can't shift this — it is a
    # pure allocator-pressure property of the workload)
    control = None
    pool = None
    for num_blocks in (17, 13, 11, 9):
        control = pressure_leg(num_blocks, tiered=False)
        pool = num_blocks
        if control["preemptions"] >= 6:
            break
    tiered = pressure_leg(pool, tiered=True)
    # token-parity under spill x preempt churn is owned by the unit
    # fuzz test (pinned schedule); under the bench's live schedules
    # the two legs preempt different victims, so the leg verdict is
    # the ISSUE contract: preemption counts + tier byte flow + no
    # failed requests
    pressure_pass = bool(
        control["preemptions"] >= 6
        and control["ok"] == len(work)
        and tiered["preemptions"] == 0
        and tiered["spill_preemptions"] > 0
        and tiered["ok"] == len(work)
        and tiered["spill_bytes"] > 0
        and tiered["restore_bytes"] > 0)

    # -- leg 2: warm restart from the persistent prefix store -----------
    block, T = 16, 64
    shared = 48                         # 75% of the probe prompt
    base = prompts(1, T)[0]
    probes = [np.concatenate([base[:shared],
                              p[:T - shared]]).astype(np.int32)
              for p in prompts(3, T)]

    def restart_server(store):
        return InferenceServer(
            net, batch_slots=2, max_len=96, block_size=block,
            max_prompt_len=T, prefill_chunk_tokens=block,
            kv_tiering=True, prefix_store_dir=store)

    def first_ttft(store, probe):
        # a FRESH server per probe: only the first request ever seen
        # by a server is honestly cold/warm — later ones ride its
        # on-device prefix cache either way. The process-wide
        # executable cache keeps this free of compile noise.
        s = restart_server(store)
        s.warm_tier()
        r = s.submit(probe, 4)
        s.run()
        assert r.status == "ok", r.status
        return float(r.ttft), s

    with tempfile.TemporaryDirectory() as cold_dir, \
            tempfile.TemporaryDirectory() as warm_dir:
        sa = restart_server(warm_dir)
        sa.warm_tier()                  # absorb spill/restore compiles
        sa.submit(base, 4)
        sa.run()
        sa.shutdown()                   # persists the prefix chains
        # warm: fresh servers over the same store restore the shared
        # blocks at admit — chunked prefill starts at the 48-token
        # frontier instead of zero
        warm, cold = [], []
        restored_bytes = disk_hits = 0
        for p in probes:
            t, sb = first_ttft(warm_dir, p)
            warm.append(t)
            restored_bytes += sb.tier.restore_bytes
            disk_hits += sb.tier.hits["disk"]
            sb.cache.check()
            t, _sc = first_ttft(cold_dir, p)
            cold.append(t)
    warm_ttft = float(np.median(warm))
    cold_ttft = float(np.median(cold))
    ttft_ratio = warm_ttft / max(cold_ttft, 1e-9)
    warm_pass = bool(ttft_ratio <= 0.6 and restored_bytes > 0
                     and disk_hits > 0)

    # -- leg 3: disaggregated prefill -> decode streaming ---------------
    telemetry.enable()
    telemetry.reset()
    disagg_work = prompts(4, 12)

    def combined_server():
        s = InferenceServer(net, batch_slots=4, max_len=64,
                            block_size=4, max_prompt_len=16,
                            kv_tiering=True)
        s.warm_tier()
        return s

    sg = combined_server()
    want = []
    for p in disagg_work:
        r = sg.submit(p, 8)
        sg.run()
        want.append([int(t) for t in r.output_tokens])
    sp, sd = combined_server(), combined_server()
    cs0 = dict(sd.compile_stats())
    fleet = FleetRouter(
        [LocalReplica(sp, name="prefill", role="prefill"),
         LocalReplica(sd, name="decode", role="decode")],
        disaggregate=True, affinity_blocks=0)
    frs = [fleet.submit(p, 8) for p in disagg_work]
    fleet.run(timeout_s=120)
    snap = telemetry.snapshot()["counters"]
    streamed = int(snap.get("serving_blocks_streamed_total", 0))
    cs1 = dict(sd.compile_stats())
    extra_compiles = sum(
        cs1[k] - cs0.get(k, 0) for k in cs1 if k.endswith("_compiles"))
    disagg_pass = bool(
        all(fr.status == "ok" for fr in frs)
        and [list(fr.output_tokens) for fr in frs] == want
        and streamed > 0 and extra_compiles == 0
        and fleet.stats()["disagg_fallbacks"] == 0)
    # bench_tier_* gauges ride the (enabled) registry for scrapes of a
    # bench-in-progress; the JSON line below is the canonical record
    telemetry.set_gauge("bench_tier_warm_ttft_ratio", ttft_ratio)
    telemetry.set_gauge("bench_tier_spill_bytes",
                        tiered["spill_bytes"])
    telemetry.set_gauge("bench_tier_restore_bytes",
                        tiered["restore_bytes"])
    telemetry.set_gauge("bench_tier_streamed_blocks", streamed)
    for s in (sg, sp, sd):
        telemetry.unregister_health_source(s._forecaster)
        telemetry.unregister_health_source(s)
    telemetry.disable()
    telemetry.reset()

    guard.best.update({
        "value": round(ttft_ratio, 4),
        "phase": "tiering",
        "tier_pass": bool(pressure_pass and warm_pass and disagg_pass),
        "pressure_pass": pressure_pass,
        "pressure_pool_blocks": pool,
        "control_preemptions": control["preemptions"],
        "tiered_preemptions": tiered["preemptions"],
        "tiered_spill_preemptions": tiered["spill_preemptions"],
        "tier_spill_bytes": tiered["spill_bytes"],
        "tier_restore_bytes": tiered["restore_bytes"],
        "warm_pass": warm_pass,
        "warm_ttft_s": round(warm_ttft, 6),
        "cold_ttft_s": round(cold_ttft, 6),
        "tier_warm_ttft_ratio": round(ttft_ratio, 4),
        "warm_restored_bytes": restored_bytes,
        "disagg_pass": disagg_pass,
        "disagg_streamed_blocks": streamed,
        "disagg_extra_compiles": extra_compiles,
    })
    guard.emit()


def _bench_factors(net, rank, seed, targets=("wq", "wv")):
    """Strong random (A, B) LoRA factors sized off the live params —
    the bench measures the gather/matmul cost of a REAL adapter mix,
    not the training quality of the factors."""
    rng = np.random.RandomState(seed)
    name_map = {"wq": "q_proj", "wv": "v_proj"}
    params = net.collect_params()
    factors = []
    for li in range(net.model.cfg.num_layers):
        lf = {}
        for t in targets:
            W = params[f"model.layers.{li}.self_attn."
                       f"{name_map[t]}.weight"]
            dout, din = np.asarray(W.data()._data).shape
            lf[t] = (rng.normal(0, 0.05, (din, rank)).astype(np.float32),
                     rng.normal(0, 0.05, (rank, dout)).astype(np.float32))
        factors.append(lf)
    return factors


def tenants_phase(on_tpu, guard, num_requests=24, seed=0):
    """--tenants: the adversarial multi-tenant QoS leg. A batch-class
    flooder hammers the server far past its per-tenant queue bound
    while an interactive victim trickles requests under a real
    TTFT/TPOT SLO. Pass = the flood is shed by priority class
    (serve_shed_total{class="batch"} matches), the victim is NEVER
    shed, and every victim request lands inside its SLO — weighted-
    fair scheduling is what keeps the victim's tokens flowing while
    the flooder's queue slots churn."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import InferenceServer

    cfg, net = _build_net(on_tpu, serve=True)
    if on_tpu:
        slots, max_len, block, mpl, new = 8, 256, 16, 32, 32
        ttft_slo, tpot_slo = 1.0, 0.05
    else:
        slots, max_len, block, mpl, new = 4, 64, 8, 16, 12
        ttft_slo, tpot_slo = 5.0, 0.5

    telemetry.enable()
    server = InferenceServer(
        net, batch_slots=slots, max_len=max_len, block_size=block,
        max_prompt_len=mpl,
        tenants={"victim": {"weight": 4.0, "priority": "interactive",
                            "ttft_slo_s": ttft_slo,
                            "tpot_slo_s": tpot_slo},
                 "flood": {"weight": 1.0, "priority": "batch",
                           "max_queued": slots}})
    rs = np.random.RandomState(seed)
    server.submit(rs.randint(0, cfg.vocab_size, 8).astype(np.int32), 2,
                  tenant="victim")
    server.run()                         # warm: both executables built

    flood, victim = [], []
    rounds = max(4, num_requests // 4)
    t0 = time.perf_counter()
    for _ in range(rounds):
        # the flooder bursts 4x the victim's rate every round; its
        # per-tenant bound sheds the excess at admission
        for _ in range(4):
            p = rs.randint(0, cfg.vocab_size, 8).astype(np.int32)
            flood.append(server.submit(p, max_new_tokens=new,
                                       tenant="flood"))
        p = rs.randint(0, cfg.vocab_size, 8).astype(np.int32)
        victim.append(server.submit(p, max_new_tokens=new,
                                    tenant="victim"))
        for _ in range(3):
            server.step()
    server.run()
    wall = time.perf_counter() - t0

    v_ok = [r for r in victim if r.status == "ok"]
    v_ttft = np.array([r.ttft for r in v_ok]) if v_ok else np.zeros(1)
    v_tpot = np.array([(r.t_finish - r.t_first_token)
                       / max(1, len(r.output_tokens) - 1)
                       for r in v_ok]) if v_ok else np.zeros(1)
    flood_shed = sum(1 for r in flood if r.status == "rejected")
    flood_ok = sum(1 for r in flood if r.status == "ok")
    victim_shed = sum(1 for r in victim if r.status == "rejected")
    slo_ok = sum(1 for tt, tp in zip(v_ttft, v_tpot)
                 if tt <= ttft_slo and tp <= tpot_slo)
    attainment = (slo_ok / len(victim)) if victim else 0.0
    fam = telemetry._REGISTRY.get("serve_shed_total")
    by_class = {dict(k).get("class"): c.value
                for k, c in (fam.children.items() if fam else ())
                if k}
    class_ordered = (by_class.get("batch", 0) == flood_shed
                     and "interactive" not in by_class)
    tenant_pass = bool(flood_shed > 0 and victim_shed == 0
                       and attainment == 1.0 and class_ordered
                       and flood_ok > 0)
    telemetry.set_gauge("bench_tenant_victim_ttft_p95_ms",
                        float(np.percentile(v_ttft, 95)) * 1e3)
    telemetry.set_gauge("bench_tenant_flood_shed_total", flood_shed)
    telemetry.unregister_health_source(server)
    telemetry.disable()
    telemetry.reset()

    guard.best.update({
        "value": round(float(np.percentile(v_ttft, 95)) * 1e3, 2),
        "phase": "tenants",
        "tenant_pass": tenant_pass,
        "bench_tenant_victim_ttft_p95_ms":
            round(float(np.percentile(v_ttft, 95)) * 1e3, 2),
        "bench_tenant_victim_tpot_p95_ms":
            round(float(np.percentile(v_tpot, 95)) * 1e3, 2),
        "bench_tenant_victim_slo_attainment": round(attainment, 4),
        "bench_tenant_flood_shed_total": flood_shed,
        "bench_tenant_victim_shed_total": victim_shed,
        "shed_by_class": {k: int(v) for k, v in by_class.items()},
        "flood_served": flood_ok,
        "victim_requests": len(victim),
        "wall_s": round(wall, 3),
        **{k: v for k, v in server.compile_stats().items()},
    })
    guard.emit()


def lora_phase(on_tpu, guard, num_requests=16, seed=0):
    """--lora: batched multi-LoRA throughput leg. The identical
    closed-loop workload runs on a base-only server and again as a
    3-way base/adapter-1/adapter-2 mix through one rank-8 adapter
    table (per-slot indices traced into the SAME decode executable).
    Headline bench_lora_mix_vs_base_ratio = mixed tokens/sec / base
    tokens/sec — the gate is >= 0.8x at rank <= 8 with ZERO compiles
    added after the adapters hot-load."""
    import jax

    from mxnet_tpu.serving import InferenceServer

    cfg, net = _build_net(on_tpu, serve=True)
    if on_tpu:
        slots, max_len, block, mpl, new = 8, 256, 16, 32, 64
    else:
        slots, max_len, block, mpl, new = 4, 64, 8, 16, 16
    rank = 8
    rs = np.random.RandomState(seed)
    workload = [rs.randint(0, cfg.vocab_size,
                           int(rs.randint(4, mpl + 1))).astype(np.int32)
                for _ in range(num_requests)]
    total_new = num_requests * new

    def timed_run(server, adapters):
        for i, p in enumerate(workload):
            server.submit(p, max_new_tokens=new,
                          adapter=adapters[i % len(adapters)])
        t0 = time.perf_counter()
        server.run()
        return time.perf_counter() - t0

    base = InferenceServer(net, batch_slots=slots, max_len=max_len,
                           block_size=block, max_prompt_len=mpl)
    base.submit(workload[0], max_new_tokens=2)
    base.run()                                  # warm
    base_tps = total_new / timed_run(base, [None])

    lsrv = InferenceServer(net, batch_slots=slots, max_len=max_len,
                           block_size=block, max_prompt_len=mpl,
                           lora={"capacity": 4, "rank": rank})
    lsrv.submit(workload[0], max_new_tokens=2)
    lsrv.run()                                  # warm BEFORE hot-load
    cs0 = dict(lsrv.compile_stats())
    lsrv.load_adapter("a1", _bench_factors(net, rank, seed + 1))
    lsrv.load_adapter("a2", _bench_factors(net, rank, seed + 2))
    mix_tps = total_new / timed_run(lsrv, [None, "a1", "a2"])
    cs1 = dict(lsrv.compile_stats())
    extra = sum(cs1[k] - cs0.get(k, 0) for k in cs1
                if k.endswith("_compiles"))

    chips = max(1, jax.local_device_count())
    ratio = mix_tps / base_tps if base_tps else 0.0
    guard.best.update({
        "value": round(ratio, 4),
        "phase": "lora",
        "lora_pass": bool(ratio >= 0.8 and extra == 0),
        "bench_lora_mix_vs_base_ratio": round(ratio, 4),
        "bench_lora_base_tokens_per_sec": round(base_tps, 2),
        "bench_lora_mix_tokens_per_sec": round(mix_tps, 2),
        "bench_lora_mix_tokens_per_sec_per_chip":
            round(mix_tps / chips, 2),
        "bench_lora_extra_compiles": int(extra),
        "lora_rank": rank,
        "adapters_loaded": lsrv.stats()["adapters"]["loaded"],
        "requests": num_requests,
    })
    guard.emit()


def autoscale_phase(on_tpu, guard, seed=0):
    """--autoscale: the self-scaling-fleet bench. One diurnal Poisson
    arrival curve (burst -> trough -> burst) replayed through three
    fleets of in-process LocalReplica servers sharing one net (and so
    one executable cache — respawns warm-compile against jit's own
    shape-keyed cache):

    - autoscale leg: one warm replica + FleetAutoscaler with
      min_replicas=0. Queue-age scale-out (sized by tokens/sec) grows
      the fleet under each burst, load-driven scale-in drains it back,
      and the fleet parks to ZERO through the trough — scale-from-zero
      revives it for the second burst. A burn-rate SLOEngine rides the
      leg and must stay SILENT (this is the clean leg).
    - static N=min(=1) and static N=max legs: the same curve on fixed
      fleets; their chip-seconds are N x wall by definition.

    Pass = zero requests lost, >=1 scale-out AND >=1 scale-in, zero
    SLO alerts, and the autoscaler's own chip-seconds ledger BEATING
    both static fleets (the trough is where a fixed fleet burns chips
    for nothing). A flood leg then maxes a max_replicas=1 fleet until
    the admission floor rises to shed_below="standard": only
    batch-class requests are shed at the door while every interactive
    request completes inside its SLO (attainment 1.0)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import InferenceServer, LocalProvisioner
    from mxnet_tpu.serving.router import FleetRouter, LocalReplica
    from mxnet_tpu.slo import Objective

    cfg, net = _build_net(on_tpu, serve=True)
    if on_tpu:
        slots, max_len, block, mpl, new = 8, 256, 16, 32, 16
        ttft_slo, rate, nb, trough_s, tps0 = 2.0, 40.0, 16, 6.0, 400.0
    else:
        slots, max_len, block, mpl, new = 4, 64, 8, 16, 8
        ttft_slo, rate, nb, trough_s, tps0 = 10.0, 20.0, 12, 6.0, 60.0
    n_max = 3

    # one deterministic diurnal curve, replayed identically per leg
    rs = np.random.RandomState(seed)

    def burst(t0):
        ts = t0 + np.cumsum(rs.exponential(1.0 / rate, nb))
        reqs = []
        for t in ts:
            T = int(rs.randint(4, mpl + 1))
            p = rs.randint(0, cfg.vocab_size, T).astype(np.int32)
            reqs.append((float(t), p, new))
        return reqs, float(ts[-1])

    b1, t_end1 = burst(0.0)
    b2, _ = burst(t_end1 + trough_s)
    curve = b1 + b2

    def factory():
        return InferenceServer(net, batch_slots=slots, max_len=max_len,
                               block_size=block, max_prompt_len=mpl)

    def drive(fleet):
        frs, pending = [], list(curve)
        t0 = time.perf_counter()
        while pending or fleet._queue or fleet._inflight:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                _, p, n = pending.pop(0)
                frs.append(fleet.submit(p, n))
            if fleet.step() == 0:
                time.sleep(0.002)
        return frs, time.perf_counter() - t0

    # -- autoscale leg (the clean SLO leg) --
    telemetry.enable()
    seed_srv = factory()
    seed_srv.warmup()
    fleet = FleetRouter([LocalReplica(seed_srv, factory=factory,
                                      name="r0")], affinity_blocks=0)
    engine = fleet.attach_slo(
        objectives=[Objective("autoscale_ttft",
                              metric="serving_ttft_seconds",
                              target=0.7, threshold_s=ttft_slo)],
        fast_window_s=2.0, slow_window_s=8.0, burn_threshold=1.0,
        tick_interval_s=0.1)
    asc = fleet.attach_autoscale(
        provisioner=LocalProvisioner(factory),
        min_replicas=0, max_replicas=n_max,
        queue_age_out_s=0.25, drain_target_s=1.0,
        default_tokens_per_s=tps0, scale_in_load=0.5,
        scale_in_hold_s=0.5, cooldown_out_s=1.0, cooldown_in_s=0.4,
        tick_interval_s=0.05)
    frsA, wallA = drive(fleet)
    chip_auto = asc.chip_seconds()
    lostA = sum(1 for fr in frsA if fr.status != "ok")
    scale_outs, scale_ins = asc.n_scale_out, asc.n_scale_in
    clean_alerts = engine.alerts_total
    usageA = asc.usage()
    telemetry.unregister_health_source(engine)
    telemetry.set_fleet_metrics_provider(None)
    telemetry.disable()
    telemetry.reset()

    # -- static legs: chip-seconds are N x wall by definition --
    def static_leg(n):
        srvs = [factory() for _ in range(n)]
        for s in srvs:
            s.warmup()
        f = FleetRouter([LocalReplica(s, factory=factory, name=f"s{i}")
                         for i, s in enumerate(srvs)],
                        affinity_blocks=0)
        frs, wall = drive(f)
        return wall, sum(1 for fr in frs if fr.status != "ok")

    wall1, lost1 = static_leg(1)
    wallM, lostM = static_leg(n_max)
    chip_min, chip_max = 1 * wall1, n_max * wallM
    savings = (chip_min - chip_auto) / chip_min if chip_min else 0.0
    lost_total = lostA + lost1 + lostM
    autoscale_pass = bool(lostA == 0 and scale_outs >= 1
                          and scale_ins >= 1 and clean_alerts == 0
                          and chip_auto < chip_min
                          and chip_auto < chip_max)

    # -- flood leg: maxed fleet raises the class-aware admission floor
    flood_res = {}
    if guard.remaining() > 30.0:
        telemetry.enable()
        fsrv = factory()
        fsrv.warmup()
        ffleet = FleetRouter([LocalReplica(fsrv, factory=factory,
                                           name="f0")],
                             affinity_blocks=0)
        fasc = ffleet.attach_autoscale(
            provisioner=LocalProvisioner(factory),
            min_replicas=1, max_replicas=1,
            queue_age_out_s=0.1, shed_below="standard",
            overload_hold_s=0.1, scale_in_hold_s=1e9,
            cooldown_in_s=1e9, tick_interval_s=0.02)
        rsF = np.random.RandomState(seed + 1)
        batch_frs, inter_frs, floor_seen = [], [], False
        # prime: an up-front flood deep enough that queue-age p95
        # crosses the trigger and holds — the floor must rise before
        # the measured rounds below
        for _ in range(30):
            p = rsF.randint(0, cfg.vocab_size, 8).astype(np.int32)
            batch_frs.append(ffleet.submit(p, 2 * new,
                                           priority="batch"))
        t_r = time.perf_counter()
        while time.perf_counter() - t_r < 0.5:
            if ffleet.step() == 0:
                time.sleep(0.002)
            floor_seen |= ffleet.admission_floor is not None
        for _ in range(8):
            for _ in range(4):
                p = rsF.randint(0, cfg.vocab_size, 8).astype(np.int32)
                batch_frs.append(ffleet.submit(p, new,
                                               priority="batch"))
            p = rsF.randint(0, cfg.vocab_size, 8).astype(np.int32)
            inter_frs.append(ffleet.submit(p, new,
                                           priority="interactive"))
            t_r = time.perf_counter()
            while time.perf_counter() - t_r < 0.25:
                if ffleet.step() == 0:
                    time.sleep(0.002)
                floor_seen |= ffleet.admission_floor is not None
        while ffleet._queue or ffleet._inflight:
            if ffleet.step() == 0:
                time.sleep(0.002)
        batch_shed = sum(1 for fr in batch_frs
                         if fr.status == "rejected")
        inter_shed = sum(1 for fr in inter_frs
                         if fr.status == "rejected")
        inter_ok = sum(1 for fr in inter_frs if fr.status == "ok")
        ttfts = [fr.ttft_s for fr in inter_frs
                 if fr.ttft_s is not None]
        slo_ok = sum(1 for t in ttfts if t <= ttft_slo)
        attainment = (slo_ok / len(inter_frs)) if inter_frs else 0.0
        fam = telemetry._REGISTRY.get("serve_shed_total")
        by_class = {dict(k).get("class"): c.value
                    for k, c in (fam.children.items() if fam else ())
                    if k and dict(k).get("class")}
        class_ordered = ("interactive" not in by_class
                         and by_class.get("batch", 0) == batch_shed)
        flood_res = {
            "flood_floor_engaged": floor_seen,
            "flood_batch_shed": batch_shed,
            "flood_interactive_shed": inter_shed,
            "flood_interactive_ok": inter_ok,
            "flood_interactive_slo_attainment": round(attainment, 4),
            "flood_shed_by_class": {k: int(v)
                                    for k, v in by_class.items()},
            "flood_pass": bool(floor_seen and batch_shed > 0
                               and inter_shed == 0
                               and inter_ok == len(inter_frs)
                               and class_ordered
                               and attainment == 1.0),
        }
        telemetry.disable()
        telemetry.reset()

    attain = flood_res.get("flood_interactive_slo_attainment", 0.0)
    guard.best.update(flood_res)
    guard.best.update({
        "value": round(chip_auto, 3),
        "phase": "autoscale",
        "autoscale_pass": autoscale_pass,
        "bench_autoscale_chip_seconds": round(chip_auto, 3),
        "bench_autoscale_chip_savings_frac": round(savings, 4),
        "bench_autoscale_slo_attainment": attain,
        "bench_autoscale_scale_outs": scale_outs,
        "bench_autoscale_scale_ins": scale_ins,
        "bench_autoscale_lost": lost_total,
        "bench_autoscale_clean_alerts": clean_alerts,
        "static_min_chip_seconds": round(chip_min, 3),
        "static_max_chip_seconds": round(chip_max, 3),
        "autoscale_wall_s": round(wallA, 3),
        "static_min_wall_s": round(wall1, 3),
        "static_max_wall_s": round(wallM, 3),
        "autoscale_spawned": usageA["spawned"],
        "autoscale_reaped": usageA["reaped"],
        "requests_per_leg": len(curve),
        "trough_s": trough_s,
    })
    telemetry.enable()
    for k in ("bench_autoscale_chip_seconds",
              "bench_autoscale_chip_savings_frac",
              "bench_autoscale_slo_attainment",
              "bench_autoscale_scale_outs",
              "bench_autoscale_scale_ins",
              "bench_autoscale_lost",
              "bench_autoscale_clean_alerts"):
        telemetry.set_gauge(k, float(guard.best[k]),
                            bench="decode_autoscale")
    guard.emit()
    telemetry.disable()
    telemetry.reset()


def main():
    global _guard
    ap = argparse.ArgumentParser()
    ap.add_argument("--serve", action="store_true",
                    help="continuous-batching serving bench instead of "
                         "the batch decode bench")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="decode HBM bytes: in-kernel paged attention "
                         "vs gather fallback vs contiguous flash-decode")
    ap.add_argument("--mixed", action="store_true",
                    help="tail-latency bench: heavy-tailed prompt mix "
                         "under Poisson arrivals with chunked prefill "
                         "and speculative decoding toggled")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="resilient-fleet bench: N subprocess replicas "
                         "behind FleetRouter, incl. a kill-one-replica "
                         "leg asserting zero lost requests")
    ap.add_argument("--oom-forecast", action="store_true",
                    help="memory-pressure steering bench: router must "
                         "divert long prompts off a replica forecast "
                         "to exhaust its KV pool (0 preemptions) vs a "
                         "control leg without forecasting (>0)")
    ap.add_argument("--tiering", action="store_true",
                    help="KV memory-hierarchy bench: pressure leg "
                         "(spill-to-host instead of preempting), "
                         "warm-restart leg (persistent prefix store, "
                         "TTFT ratio vs cold), and a disaggregated "
                         "prefill->decode streaming leg")
    ap.add_argument("--tenants", action="store_true",
                    help="adversarial multi-tenant QoS bench: a "
                         "batch-class flooder is shed by priority "
                         "class while the interactive victim must "
                         "hold its TTFT/TPOT SLO")
    ap.add_argument("--lora", action="store_true",
                    help="batched multi-LoRA bench: a 3-way "
                         "base/adapter mix through one rank-8 adapter "
                         "table vs the base-only server (>=0.8x "
                         "tokens/sec gate, zero extra compiles)")
    ap.add_argument("--canary", action="store_true",
                    help="canary-gated rolling-restart bench: a "
                         "replica.degrade restart must auto-roll-back "
                         "with a cross-process evidence bundle; a "
                         "clean restart must promote with zero "
                         "anomaly alerts and zero rollbacks")
    ap.add_argument("--autoscale", action="store_true",
                    help="self-scaling fleet bench: a diurnal arrival "
                         "curve where the autoscaled fleet (incl. "
                         "scale-to-zero through the trough) must beat "
                         "BOTH static N=min and N=max on chip-seconds "
                         "with zero lost requests and a silent SLO, "
                         "plus a flood leg shedding only batch class")
    ap.add_argument("--slo", action="store_true",
                    help="with --fleet: add SLO legs — a clean leg "
                         "where the burn-rate alert must stay silent "
                         "and a replica.stall leg where it must fire, "
                         "flip health, and collect a flight bundle")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="Poisson arrival rate, requests/sec")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.paged_kernel:
        metric, unit = "paged_decode_bytes_ratio", "x"
    elif args.canary:
        metric, unit = "bench_canary_pass", "bool"
    elif args.autoscale:
        metric, unit = "bench_autoscale_chip_seconds", "chip-s"
    elif args.tenants:
        metric, unit = "bench_tenant_victim_ttft_p95_ms", "ms"
    elif args.lora:
        metric, unit = "bench_lora_mix_vs_base_ratio", "x"
    elif args.oom_forecast:
        metric, unit = "oom_forecast_preemptions_avoided", "count"
    elif args.tiering:
        metric, unit = "kv_tier_warm_ttft_ratio", "x"
    elif args.mixed:
        metric, unit = "mixed_max_tick_gap_ratio", "x"
    elif args.fleet:
        metric, unit = "llama_fleet_tokens_per_sec", "tokens/sec"
    elif args.serve:
        metric, unit = "llama_serve_tokens_per_sec", "tokens/sec"
    else:
        metric, unit = "llama_decode_tokens_per_sec", "tokens/sec"
    import jax

    from mxnet_tpu import tracing

    _guard = guard = BudgetGuard(metric, unit).install()
    backend = jax.default_backend()
    on_tpu = backend not in ("cpu",)
    tracing.enable_compile_cache()
    guard.best.update({"backend": backend, "phase": "backend_acquired",
                       "vs_baseline": 0.0})
    guard.emit()
    if args.paged_kernel:
        paged_kernel_phase(on_tpu, guard)
    elif args.canary:
        canary_phase(on_tpu, guard, seed=args.seed)
    elif args.autoscale:
        autoscale_phase(on_tpu, guard, seed=args.seed)
    elif args.tenants:
        tenants_phase(on_tpu, guard, num_requests=args.requests,
                      seed=args.seed)
    elif args.lora:
        lora_phase(on_tpu, guard, num_requests=args.requests,
                   seed=args.seed)
    elif args.oom_forecast:
        oom_forecast_phase(on_tpu, guard, seed=args.seed)
    elif args.tiering:
        tiering_phase(on_tpu, guard, seed=args.seed)
    elif args.mixed:
        mixed_phase(on_tpu, guard, num_requests=args.requests,
                    seed=args.seed)
    elif args.fleet:
        fleet_phase(on_tpu, guard, fleet_n=args.fleet,
                    num_requests=args.requests,
                    arrival_rate=args.arrival_rate, seed=args.seed,
                    slo=args.slo)
    elif args.serve:
        serve_phase(on_tpu, guard, num_requests=args.requests,
                    arrival_rate=args.arrival_rate, seed=args.seed)
    else:
        run_phase(on_tpu, guard)

    # regression-sentinel verdict vs the BENCH_*.json trajectory
    # (advisory here — `python -m mxnet_tpu.goodput check` gates)
    from mxnet_tpu import goodput
    hist_dir = os.environ.get(
        "BENCH_HISTORY_DIR",
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    metrics = {k: float(v) for k, v in guard.best.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    try:
        v = goodput.check_against_history(metrics, hist_dir)
        guard.best["sentinel"] = {"ok": v["ok"], "compared": v["compared"],
                                  "regressions": v["regressions"][:5]}
    except Exception as e:  # the sentinel must never sink the bench
        guard.best["sentinel"] = {"ok": True,
                                  "error": f"{type(e).__name__}: {e}"[:120]}
    guard.emit()


if __name__ == "__main__":
    main()
