"""Eager optimizer-step wall time: per-parameter loop vs the fused
multi-tensor path (multi_tensor.py), 200 mixed-shape parameters.

This is the dispatch-bound regime the reference fork's multi_mp_sgd /
multi_lars kernels attack: the per-param loop pays one jitted dispatch
(plus hyper scalar churn) per tensor per step, the multi-tensor path one
executable per dtype group. Runs honestly on CPU — dispatch overhead is
host-side — so this bench produces a MEASURED number every round.

One JSON line under a BudgetGuard like every other benchmark here.
`value` is the speedup (per-param ms / fused ms); the acceptance floor
for the multi-tensor PR is 3x.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np

from bench import BudgetGuard

#: the PR's acceptance floor: fused path must be >= 3x the loop
SPEEDUP_FLOOR = 3.0

#: disabled-telemetry overhead ceiling on the fused step (ISSUE 4
#: acceptance: <= 2% — i.e. ratio <= 1.02)
TM_OVERHEAD_CEILING = float(os.environ.get("BENCH_TM_CEILING", "1.02"))

_guard = None


def _mirror_to_telemetry(guard, prefix):
    """Publish the BudgetGuard headline numbers through the telemetry
    registry and write the full snapshot JSON next to the bench's JSON
    line (every bench emits through telemetry.dump_json too)."""
    from mxnet_tpu import telemetry
    if not telemetry.enabled():
        telemetry.enable()
    for k, v in guard.best.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            telemetry.set_gauge(f"bench_{k}", float(v), bench=prefix)
    path = os.environ.get("BENCH_TELEMETRY_JSON",
                          f"/tmp/{prefix}_telemetry.json")
    guard.best["telemetry_json"] = telemetry.dump_json(path)
    guard.best["sentinel"] = _sentinel_verdict(guard)
    guard.emit()


def _sentinel_verdict(guard):
    """Regression-sentinel verdict for this run's numeric metrics vs
    the BENCH_*.json trajectory at the repo root (same check the
    standalone `python -m mxnet_tpu.goodput check` runs). Advisory in
    the emitted JSON — the sentinel CLI is where it gates."""
    from mxnet_tpu import goodput
    hist_dir = os.environ.get(
        "BENCH_HISTORY_DIR",
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    metrics = {k: float(v) for k, v in guard.best.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    try:
        v = goodput.check_against_history(metrics, hist_dir)
    except Exception as e:  # the sentinel must never sink the bench
        return {"ok": True, "error": f"{type(e).__name__}: {e}"[:120]}
    return {"ok": v["ok"], "compared": v["compared"],
            "regressions": v["regressions"][:5]}


def _make_trainer(mx, jnp, shapes, multi_tensor, optimizer="sgd",
                  opt_kwargs=None, zero1=False):
    from mxnet_tpu.gluon.parameter import Parameter
    rs = np.random.RandomState(0)
    params = {}
    for i, s in enumerate(shapes):
        p = Parameter(f"p{i:03d}", shape=s)
        p.initialize()
        p.set_data(rs.randn(*s).astype(np.float32))
        p.data()._grad._data = jnp.asarray(
            rs.randn(*s).astype(np.float32))
        params[f"p{i:03d}"] = p
    tr = mx.gluon.Trainer(params, optimizer,
                          opt_kwargs or {"learning_rate": 0.1,
                                         "momentum": 0.9},
                          multi_tensor=multi_tensor, zero1=zero1)
    return params, tr


def _time_steps(mx, tr, steps):
    mx.nd.waitall()
    t0 = time.perf_counter()
    for _ in range(steps):
        tr.step(batch_size=32)
    mx.nd.waitall()
    return (time.perf_counter() - t0) / steps * 1e3  # ms/step


def main():
    global _guard
    _guard = guard = BudgetGuard(
        "eager_optimizer_step_speedup_multi_tensor", "x").install()
    import jax

    jax.config.update("jax_platforms", "cpu")  # dispatch-bound host bench

    import jax.numpy as jnp
    import mxnet_tpu as mx

    n_params = int(os.environ.get("BENCH_OPT_PARAMS", "200"))
    steps = int(os.environ.get("BENCH_OPT_STEPS", "10"))
    base_shapes = [(512,), (256, 64), (64, 32, 3), (128,),
                   (32, 16, 3, 3), (1024,)]
    shapes = [base_shapes[i % len(base_shapes)] for i in range(n_params)]

    results = {}
    for label, mt in (("per_param_loop", False), ("multi_tensor", True)):
        params, tr = _make_trainer(mx, jnp, shapes, mt)
        tr.step(batch_size=32)  # warmup: compile
        mx.nd.waitall()
        results[label] = _time_steps(mx, tr, steps)
        if mt:
            results["fused_compiles"] = tr._mt_updater.compiles
            results["fused_cache_size"] = tr._mt_updater.cache_size
        guard.best["phase"] = label

    speedup = results["per_param_loop"] / results["multi_tensor"]
    guard.best.update({
        "value": round(speedup, 2),
        "vs_baseline": round(speedup / SPEEDUP_FLOOR, 3),
        "phase": "done",
        "num_params": n_params,
        "steps_timed": steps,
        "per_param_loop_ms_per_step": round(results["per_param_loop"], 3),
        "multi_tensor_ms_per_step": round(results["multi_tensor"], 3),
        "fused_compiles": results["fused_compiles"],
        "fused_cache_size": results["fused_cache_size"],
    })
    guard.emit()

    # a couple of instrumented steps populate the step-time breakdown
    # before the snapshot dump (the gauges mirror the headline figures)
    from mxnet_tpu import telemetry
    telemetry.enable()
    telemetry.reset()
    for _ in range(2):
        tr.step(batch_size=32)
    mx.nd.waitall()
    _mirror_to_telemetry(guard, "optimizer_bench")


def _fused_step_ms(mx, jax, mesh, zero1, zero=None, batch=256,
                   hidden=1024, nlayers=3, classes=32, reps=8):
    """ms/step of FusedTrainStep (fwd + bwd + sharded optimizer) on an
    MLP big enough that the step, not dispatch, dominates."""
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep
    rs = np.random.RandomState(2)
    X = rs.rand(batch, 256).astype(np.float32)
    y = rs.randint(0, classes, size=batch)
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    for _ in range(nlayers):
        net.add(mx.gluon.nn.Dense(hidden, activation="relu"))
    net.add(mx.gluon.nn.Dense(classes))
    net.initialize()
    step = FusedTrainStep(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                          mx.optimizer.Adam(learning_rate=1e-3),
                          mesh=mesh, zero1=zero1, zero=zero)
    xs, ys = mx.nd.array(X), mx.nd.array(y)
    for _ in range(3):
        step(xs, ys)
    jax.block_until_ready(step._tr)
    t0 = time.perf_counter()
    for _ in range(reps):
        step(xs, ys)
    jax.block_until_ready(step._tr)
    return (time.perf_counter() - t0) / reps * 1e3


def main_zero1():
    """`--zero1`: ZeRO-1 sharded update vs the unsharded fused path.

    Headline `value` is the per-replica optimizer-state shrink factor
    (unsharded bytes / zero1 bytes per replica — the arXiv:2004.13336
    memory claim, ~N on N shards). `zero1_latency_ratio` is the
    acceptance metric (<= 1.15x): FusedTrainStep ms/step with zero1
    against the unsharded fused (GSPMD allreduce) train step — the
    regime the paper claims, where reduce-scatter + all-gather replace
    the grad allreduce inside one compiled step. The EAGER updater is
    also timed (`eager_*_ms_per_step`); on a 1-core host with 8
    virtual devices it double-charges every collective as serialized
    memcpy and its scatter/gather cannot overlap anything, so its
    ratio is reported for reference, not gated.
    """
    global _guard
    # the virtual 8-device mesh must exist before jax initializes
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    _guard = guard = BudgetGuard(
        "zero1_optimizer_state_shrink_per_replica", "x").install()
    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh

    n_params = int(os.environ.get("BENCH_ZERO1_PARAMS", "12"))
    steps = int(os.environ.get("BENCH_ZERO1_STEPS", "10"))
    base_shapes = [(1 << 18,), (512, 512), (1024, 256), (1 << 16,)]
    shapes = [base_shapes[i % len(base_shapes)] for i in range(n_params)]
    opt_kwargs = {"learning_rate": 1e-3}

    results, state_bytes = {}, {}
    for label, z1 in (("unsharded", False), ("zero1", True)):
        params, tr = _make_trainer(mx, jnp, shapes, True, "adam",
                                   opt_kwargs, zero1=z1)
        tr.step(batch_size=32)  # warmup: compile
        mx.nd.waitall()
        results[label] = _time_steps(mx, tr, steps)
        if z1:
            assert tr._zero1_active, "zero1 did not engage"
            tot, per = tr._mt_updater.zero1_state_nbytes()
            state_bytes[label] = {"total": tot, "per_replica": per}
            state_bytes["num_shards"] = tr._mt_updater.num_shards
        else:
            tot = sum(l.nbytes for l in
                      jax.tree_util.tree_leaves(tr._states))
            # unsharded: every replica holds the FULL state
            state_bytes[label] = {"total": tot, "per_replica": tot}
        guard.best["phase"] = label

    mesh = make_mesh([jax.device_count()], ["dp"])
    guard.best["phase"] = "fused_unsharded"
    fused_base = _fused_step_ms(mx, jax, mesh, zero1=False)
    guard.best["phase"] = "fused_zero1"
    fused_z1 = _fused_step_ms(mx, jax, mesh, zero1=True)

    shrink = (state_bytes["unsharded"]["per_replica"]
              / max(1, state_bytes["zero1"]["per_replica"]))
    n = state_bytes["num_shards"]
    guard.best.update({
        "value": round(shrink, 2),
        "vs_baseline": round(shrink / n, 3),  # 1.0 == the full N-fold
        "phase": "done",
        "num_params": n_params,
        "num_shards": n,
        "steps_timed": steps,
        "param_bytes": sum(int(np.prod(s)) * 4 for s in shapes),
        "state_bytes_unsharded": state_bytes["unsharded"]["total"],
        "state_bytes_zero1_per_replica":
            state_bytes["zero1"]["per_replica"],
        "fused_unsharded_ms_per_step": round(fused_base, 3),
        "fused_zero1_ms_per_step": round(fused_z1, 3),
        "zero1_latency_ratio": round(fused_z1 / fused_base, 3),
        "eager_unsharded_ms_per_step": round(results["unsharded"], 3),
        "eager_zero1_ms_per_step": round(results["zero1"], 3),
        "eager_zero1_latency_ratio":
            round(results["zero1"] / results["unsharded"], 3),
    })
    guard.emit()
    _mirror_to_telemetry(guard, "optimizer_bench_zero1")


def _eager_zero_run(mx, stage, shapes, steps):
    """Real-backward eager loop at a given ZeRO stage: the loss touches
    every parameter, so backward drives the stage-2 autograd hooks (the
    resident-bytes numbers are honest, not synthetic) and stage-3
    re-materializes released weights every forward."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.parameter import Parameter
    rs = np.random.RandomState(0)
    params = {}
    for i, s in enumerate(shapes):
        p = Parameter(f"p{i:03d}", shape=s)
        p.initialize()
        p.set_data(rs.randn(*s).astype(np.float32) * 0.01)
        params[f"p{i:03d}"] = p
    tr = mx.gluon.Trainer(params, "adam", {"learning_rate": 1e-3},
                          zero=stage)

    def backward_only():
        with autograd.record():
            tot = None
            for p in params.values():
                t = (p.data() * p.data()).sum()
                tot = t if tot is None else tot + t
        tot.backward()

    def one_step():
        backward_only()
        tr.step(batch_size=32)

    one_step()  # warmup: compile
    mx.nd.waitall()
    t0 = time.perf_counter()
    for _ in range(steps):
        one_step()
    mx.nd.waitall()
    ms = (time.perf_counter() - t0) / steps * 1e3
    # steady-state residency: after a backward (grad shards live),
    # before the step consumes them
    backward_only()
    mx.nd.waitall()
    rb = tr._mt_updater.zero_resident_bytes()
    hook_flushes = tr._mt_updater.hook_flushes
    tr.step(batch_size=32)
    return ms, rb, hook_flushes, tr


def main_zero(stage):
    """`--zero {2,3}`: per-replica resident training bytes (weights +
    grads + optimizer state, measured via the profiler memory-provider
    accounting) and step latency for ZeRO stage 2/3 against the ZeRO-1
    baseline. Headline `value` is the resident-bytes shrink vs zero-1;
    the acceptance floors are 1.5x (stage 2) and 3x (stage 3)."""
    global _guard
    # the virtual 8-device mesh must exist before jax initializes
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    _guard = guard = BudgetGuard(
        f"zero{stage}_resident_bytes_shrink_vs_zero1", "x").install()
    import jax

    jax.config.update("jax_platforms", "cpu")

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh

    n_params = int(os.environ.get("BENCH_ZERO_PARAMS", "8"))
    steps = int(os.environ.get("BENCH_ZERO_STEPS", "5"))
    base_shapes = [(1 << 16,), (256, 256), (512, 128), (1 << 14,)]
    shapes = [base_shapes[i % len(base_shapes)] for i in range(n_params)]

    rows = {}
    for s in dict.fromkeys((1, stage)):
        guard.best["phase"] = f"eager_zero{s}"
        ms, rb, flushes, tr = _eager_zero_run(mx, s, shapes, steps)
        rows[s] = {"ms": ms, "resident": rb, "hook_flushes": flushes}
    nshards = tr._mt_updater.num_shards

    def resident_total(rb):
        return rb["weights"] + rb["grads"] + rb["opt_state"]

    shrink = (resident_total(rows[1]["resident"])
              / max(1, resident_total(rows[stage]["resident"])))
    floor = 1.5 if stage == 2 else 3.0

    mesh = make_mesh([jax.device_count()], ["dp"])
    guard.best["phase"] = "fused_unsharded"
    fused_base = _fused_step_ms(mx, jax, mesh, zero1=False)
    guard.best["phase"] = f"fused_zero{stage}"
    fused_z = _fused_step_ms(mx, jax, mesh, zero1=False, zero=stage)

    guard.best.update({
        "value": round(shrink, 2),
        "vs_baseline": round(shrink / floor, 3),
        "phase": "done",
        "zero_stage": stage,
        "num_shards": nshards,
        "num_params": n_params,
        "steps_timed": steps,
        "hook_flushes": rows[stage]["hook_flushes"],
        "resident_bytes_zero1": rows[1]["resident"],
        f"resident_bytes_zero{stage}": rows[stage]["resident"],
        "eager_zero1_ms_per_step": round(rows[1]["ms"], 3),
        f"eager_zero{stage}_ms_per_step": round(rows[stage]["ms"], 3),
        "fused_unsharded_ms_per_step": round(fused_base, 3),
        f"fused_zero{stage}_ms_per_step": round(fused_z, 3),
        f"zero{stage}_latency_ratio": round(fused_z / fused_base, 3),
    })
    guard.emit()
    _mirror_to_telemetry(guard, f"optimizer_bench_zero{stage}")


#: telemetry's public hot helpers — the ones instrumented call sites
#: invoke on the fused-step path (read_gauge feeds TrainLoop's auto-K)
_TM_HOT = ("phase", "mark_phase", "step_done", "inc", "set_gauge",
           "observe", "read_gauge")

#: the flight recorder's hot helpers — B-side no-ops these too, so the
#: measured A/B gap covers flight recording compiled in but disabled
_FL_HOT = ("record", "dump")

#: goodput's hot feeders — the fused-step path calls these behind
#: `_gp._ENABLED` gates; B-side no-ops them (and clears the telemetry/
#: flight consumption hooks goodput.enable() would install) so the gap
#: also covers the goodput ledger compiled in but disabled
_GP_HOT = ("charge_span", "charge_gap", "note_compile", "note_tokens",
           "note_tenant_tokens", "note_train_step", "publish")


class _NullCtx:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


def main_telemetry_overhead():
    """`--telemetry-overhead`: cost of DISABLED telemetry on the fused
    train step. Interleaved A/B rounds over one compiled FusedTrainStep:
    A runs the instrumented code as shipped (telemetry disabled, so
    every hot site is one module-flag check and phase() yields
    immediately); B additionally monkeypatches the public hot helpers
    to true no-ops — as close to "instrumentation deleted" as a
    measurement gets without a second build. min-of-rounds cancels
    scheduler noise. The asserted ceiling (1.02x) is a tripwire: new
    instrumentation that does dict/string work BEFORE checking _ENABLED
    fails this bench instead of silently taxing every training step."""
    global _guard
    _guard = guard = BudgetGuard("telemetry_disabled_overhead_ratio",
                                 "x").install()
    import jax

    jax.config.update("jax_platforms", "cpu")

    import mxnet_tpu as mx
    from mxnet_tpu import flight, goodput, telemetry
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep

    telemetry.disable()
    telemetry.reset()
    flight.disable()
    goodput.disable()  # enabled-but-idle is what the A side measures

    batch = int(os.environ.get("BENCH_TM_BATCH", "64"))
    hidden = int(os.environ.get("BENCH_TM_HIDDEN", "256"))
    reps = int(os.environ.get("BENCH_TM_REPS", "30"))
    rounds = int(os.environ.get("BENCH_TM_ROUNDS", "5"))

    rs = np.random.RandomState(3)
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(hidden, activation="relu"))
    net.add(mx.gluon.nn.Dense(hidden, activation="relu"))
    net.add(mx.gluon.nn.Dense(16))
    net.initialize()
    step = FusedTrainStep(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                          mx.optimizer.Adam(learning_rate=1e-3),
                          mesh=None)
    xs = mx.nd.array(rs.rand(batch, 128).astype(np.float32))
    ys = mx.nd.array(rs.randint(0, 16, batch))
    for _ in range(5):  # warmup: compile + allocator steady state
        step(xs, ys)
    jax.block_until_ready(step._tr)

    def timed():
        jax.block_until_ready(step._tr)
        t0 = time.perf_counter()
        for _ in range(reps):
            step(xs, ys)
        jax.block_until_ready(step._tr)
        return (time.perf_counter() - t0) / reps * 1e3

    saved = {n: getattr(telemetry, n) for n in _TM_HOT}
    saved_fl = {n: getattr(flight, n) for n in _FL_HOT}
    saved_gp = {n: getattr(goodput, n) for n in _GP_HOT}
    # the consumption hooks goodput.enable() installs into telemetry/
    # flight — cleared on the B side so a mark_phase that slipped past
    # the no-op patch still cannot reach the ledger
    saved_gp_hooks = {"tm_note": telemetry._goodput_note,
                      "tm_section": telemetry._goodput_section,
                      "fl_note": flight._note_hook}
    null = _NullCtx()
    noops = {
        "phase": lambda name, device=False: null,
        "mark_phase": lambda *a, **k: None,
        "step_done": lambda *a, **k: None,
        "inc": lambda *a, **k: None,
        "set_gauge": lambda *a, **k: None,
        "observe": lambda *a, **k: None,
        "read_gauge": lambda *a, **k: None,
    }
    fl_noops = {"record": lambda *a, **k: None,
                "dump": lambda *a, **k: None}
    gp_noops = {n: (lambda *a, **k: None) for n in _GP_HOT}

    # the fleet-observability hooks ride the same cost contract: B-side
    # no-ops the SLO engine tick and the router's trace-propagation
    # hook too, so the measured gap covers them compiled in but idle
    from mxnet_tpu import slo as _slo
    from mxnet_tpu.serving import autoscale as _asc
    from mxnet_tpu.serving import kv_tier as _kvt
    from mxnet_tpu.serving import router as _router

    from mxnet_tpu import anomaly as _anom

    saved_hooks = {(_slo.SLOEngine, "tick"): _slo.SLOEngine.tick,
                   # the autoscaler tick rides every router step (it is
                   # deliberately UNgated — capacity control, not
                   # observability), so the overhead gate must cover it
                   (_asc.FleetAutoscaler, "tick"):
                       _asc.FleetAutoscaler.tick,
                   (_router.FleetRouter, "_note_result"):
                       _router.FleetRouter._note_result,
                   # the anomaly engine rides the router step loop the
                   # same way the SLO engine does — tick is its only
                   # hot entry, and the baseline observers are the
                   # only per-sample work inside it
                   (_anom.AnomalyEngine, "tick"):
                       _anom.AnomalyEngine.tick,
                   (_anom.BaselineStore, "observe_counter"):
                       _anom.BaselineStore.observe_counter,
                   (_anom.BaselineStore, "observe_histogram"):
                       _anom.BaselineStore.observe_histogram}
    hook_noops = {(_slo.SLOEngine, "tick"):
                      lambda self, now=None: None,
                  (_asc.FleetAutoscaler, "tick"):
                      lambda self, now=None: None,
                  (_router.FleetRouter, "_note_result"):
                      lambda self, *a, **k: None,
                  (_anom.AnomalyEngine, "tick"):
                      lambda self, now=None: None,
                  (_anom.BaselineStore, "observe_counter"):
                      lambda self, *a, **k: None,
                  (_anom.BaselineStore, "observe_histogram"):
                      lambda self, *a, **k: None}
    # the KV-tier telemetry funnels (spill/restore/stream/persist
    # accounting) ride the same contract — no-op them on the B side
    for _hook in ("_note_spill", "_note_restore", "_note_restore_failed",
                  "_note_restore_timeout", "_note_stream",
                  "_note_persist"):
        saved_hooks[(_kvt.KVTierManager, _hook)] = \
            getattr(_kvt.KVTierManager, _hook)
        hook_noops[(_kvt.KVTierManager, _hook)] = \
            lambda self, *a, **k: None
    # the multi-LoRA tenancy funnels (shed/TTFT/TPOT/finish/token/
    # gauge publishes in serving/lora.py) are module-level hooks on
    # the same contract — no-op them on the B side too
    from mxnet_tpu.serving import lora as _lsrv
    for _hook in ("_note_adapter", "_note_shed", "_note_ttft",
                  "_note_tpot", "_note_finish", "_note_tokens",
                  "_note_tenant_gauges"):
        saved_hooks[(_lsrv, _hook)] = getattr(_lsrv, _hook)
        hook_noops[(_lsrv, _hook)] = lambda *a, **k: None

    a_ms, b_ms = [], []
    for _ in range(rounds):
        if a_ms and guard.remaining() < 15.0:
            break
        a_ms.append(timed())  # A: shipped disabled path (tm+fl+gp)
        for name, fn in noops.items():
            setattr(telemetry, name, fn)
        for name, fn in fl_noops.items():
            setattr(flight, name, fn)
        for name, fn in gp_noops.items():
            setattr(goodput, name, fn)
        telemetry._goodput_note = None
        telemetry._goodput_section = None
        flight._note_hook = None
        for (cls, name), fn in hook_noops.items():
            setattr(cls, name, fn)
        try:
            b_ms.append(timed())  # B: helpers are true no-ops
        finally:
            for name, fn in saved.items():
                setattr(telemetry, name, fn)
            for name, fn in saved_fl.items():
                setattr(flight, name, fn)
            for name, fn in saved_gp.items():
                setattr(goodput, name, fn)
            telemetry._goodput_note = saved_gp_hooks["tm_note"]
            telemetry._goodput_section = saved_gp_hooks["tm_section"]
            flight._note_hook = saved_gp_hooks["fl_note"]
            for (cls, name), fn in saved_hooks.items():
                setattr(cls, name, fn)

    ratio = min(a_ms) / min(b_ms)
    guard.best.update({
        "value": round(ratio, 4),
        # >= 1.0 means "within the ceiling" (lower ratio is better)
        "vs_baseline": round(TM_OVERHEAD_CEILING / max(ratio, 1e-9), 3),
        "phase": "done",
        "reps": reps, "rounds": len(b_ms),
        "disabled_ms_per_step": round(min(a_ms), 4),
        "noop_ms_per_step": round(min(b_ms), 4),
        "overhead_pct": round((ratio - 1.0) * 100.0, 2),
        "ceiling": TM_OVERHEAD_CEILING,
    })
    _mirror_to_telemetry(guard, "telemetry_overhead")
    assert ratio <= TM_OVERHEAD_CEILING, (
        f"disabled-telemetry overhead {ratio:.4f}x exceeds the "
        f"{TM_OVERHEAD_CEILING}x ceiling")


def main_loop_k():
    """`--loop-k`: whole-loop compilation sweep (ISSUE 8). One
    dispatch-bound MLP step (small batch/hidden — the regime where the
    per-step Python round-trip, not the math, is the bottleneck) run
    three ways: K=1 single dispatches, and K∈{4,16} steps per lax.scan
    dispatch via FusedTrainStep.run_steps. `value` is ms/step(K=1) /
    ms/step(K=16); the asserted floor is > 1.0 — whole-loop compilation
    must beat per-step dispatch on CPU where dispatch dominates."""
    global _guard
    _guard = guard = BudgetGuard("train_loop_k16_speedup", "x").install()
    import jax

    jax.config.update("jax_platforms", "cpu")

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep

    batch = int(os.environ.get("BENCH_LOOPK_BATCH", "16"))
    hidden = int(os.environ.get("BENCH_LOOPK_HIDDEN", "64"))
    reps = int(os.environ.get("BENCH_LOOPK_REPS", "64"))  # steps per K

    rs = np.random.RandomState(4)
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(hidden, activation="relu"),
            mx.gluon.nn.Dense(hidden, activation="relu"),
            mx.gluon.nn.Dense(8))
    net.initialize()
    step = FusedTrainStep(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                          mx.optimizer.Adam(learning_rate=1e-3),
                          mesh=None)
    xs = mx.nd.array(rs.rand(batch, 32).astype(np.float32))
    ys = mx.nd.array(rs.randint(0, 8, batch))

    def time_k(k):
        if k == 1:
            for _ in range(4):
                step(xs, ys)
            jax.block_until_ready(step._tr)
            t0 = time.perf_counter()
            for _ in range(reps):
                step(xs, ys)
            jax.block_until_ready(step._tr)
            return (time.perf_counter() - t0) / reps * 1e3
        win = [(xs, ys)] * k
        step.run_steps(win)  # compile + first exec
        jax.block_until_ready(step._tr)
        wins = max(1, reps // k)
        t0 = time.perf_counter()
        for _ in range(wins):
            step.run_steps(win)
        jax.block_until_ready(step._tr)
        return (time.perf_counter() - t0) / (wins * k) * 1e3

    ms = {k: time_k(k) for k in (1, 4, 16)}
    ratio = ms[1] / ms[16]

    # double-buffered feed (ISSUE 17): distinct K-windows driven with
    # next_batches= stage window i+1 (host stack + device_put) while
    # the async dispatch of window i still runs on the device. The
    # train_feed_* telemetry reports how much host feed work left the
    # critical path; every staged window must be consumed.
    from mxnet_tpu import telemetry as tm

    kf = 16
    nwin = max(2, min(8, reps // kf))

    def _windows():
        return [[(mx.nd.array(rs.rand(batch, 32).astype(np.float32)),
                  mx.nd.array(rs.randint(0, 8, batch)))
                 for _ in range(kf)] for _ in range(nwin)]

    def _drive(staged):
        wins = _windows()
        t0 = time.perf_counter()
        for i, w in enumerate(wins):
            nxt = wins[i + 1] if staged and i + 1 < len(wins) else None
            step.run_steps(w, next_batches=nxt)
        jax.block_until_ready(step._tr)
        return (time.perf_counter() - t0) / (nwin * kf) * 1e3

    _drive(False)  # warm the window-shape executable
    tm.reset()
    tm.enable()
    try:
        feed_unstaged = _drive(False)
        feed_staged = _drive(True)
        snap = tm.snapshot()
    finally:
        tm.disable()
        tm.reset()
    overlap_ms = float(snap["gauges"].get("train_feed_overlap_ms", 0.0))
    staged_n = int(snap["counters"].get(
        "train_feed_windows_staged_total", 0))
    hits = int(snap["counters"].get("train_feed_window_hits_total", 0))
    assert staged_n == nwin - 1 and hits == staged_n, (
        f"every staged window must be consumed: staged={staged_n} "
        f"hits={hits} (expected {nwin - 1})")

    guard.best.update({
        "feed_overlap_ms_per_window": round(overlap_ms, 3),
        "feed_windows_staged": staged_n,
        "feed_window_hits": hits,
        "feed_ms_per_step_unstaged": round(feed_unstaged, 3),
        "feed_ms_per_step_staged": round(feed_staged, 3),
        "feed_speedup": round(feed_unstaged / feed_staged, 3),
    })
    guard.best.update({
        "value": round(ratio, 3),
        "vs_baseline": round(ratio, 3),  # floor is 1.0
        "phase": "done",
        "batch": batch, "hidden": hidden, "steps_per_k": reps,
        "ms_per_step_k1": round(ms[1], 3),
        "ms_per_step_k4": round(ms[4], 3),
        "ms_per_step_k16": round(ms[16], 3),
        "speedup_k4": round(ms[1] / ms[4], 3),
        "dispatch_overhead_ms_per_step": round(ms[1] - ms[16], 3),
        "floor": 1.0,
    })
    _mirror_to_telemetry(guard, "loop_k")
    assert ratio > 1.0, (
        f"K=16 whole-loop path ({ms[16]:.3f} ms/step) must beat K=1 "
        f"single dispatches ({ms[1]:.3f} ms/step) on CPU; ratio "
        f"{ratio:.3f}")


if __name__ == "__main__":
    try:
        if "--telemetry-overhead" in sys.argv:
            main_telemetry_overhead()
        elif "--loop-k" in sys.argv:
            main_loop_k()
        elif "--zero" in sys.argv:
            _stage = int(sys.argv[sys.argv.index("--zero") + 1])
            main_zero1() if _stage == 1 else main_zero(_stage)
        elif "--zero1" in sys.argv:
            main_zero1()
        else:
            main()
    except Exception as e:  # always emit a JSON line; rc stays 0
        import traceback

        traceback.print_exc()
        best = dict(_guard.best) if _guard is not None else {
            "metric": "eager_optimizer_step_speedup_multi_tensor",
            "value": 0.0, "unit": "x", "vs_baseline": 0.0}
        best["error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps(best))
