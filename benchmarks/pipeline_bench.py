"""Pipeline-parallel train step: 1F1B stash footprint and step latency.

Two claims from the pipeline PR are measured here (SURVEY §4, the
PipeDream-flush / Megatron-LM 1F1B schedule):

1. **Stash bytes.** GPipe differentiated with plain `jax.grad` keeps
   every microbatch's stage input alive until the backward pass — the
   activation stash grows O(M).  The 1F1B schedule drains backward
   work as soon as the last stage produces a loss, so each stage holds
   at most S = 2n-1 stage inputs regardless of M (recompute-vjp: only
   the stage INPUT is stashed, the vjp is rebuilt at backward time).
   At M=16, n=4 the analytic ratio is 16/7 ≈ 2.3x; the acceptance
   floor for the headline `value` is 2x.  We read the compiled
   executable's `memory_analysis().temp_size_in_bytes` when the
   backend provides it and fall back to the analytic slot count
   (S·mb_bytes vs M·mb_bytes) when it does not.

2. **Step latency + bubble.** FusedTrainStep(pipeline=M) on a
   pp=4 x dp=2 virtual-device mesh against the unpipelined dp=8 fused
   step on the same model/batch; the telemetry gauges
   (`pipeline_bubble_ratio`, fill/steady/drain phases) ride into the
   snapshot JSON.  On a 1-core CPU host the pipelined step cannot be
   faster — every "parallel" stage serializes — so latency is reported
   for the record, not gated.

One JSON line under a BudgetGuard like every other benchmark here.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np

from bench import BudgetGuard

#: acceptance floor: 1F1B stash must be >= 2x smaller than gpipe+AD
STASH_SHRINK_FLOOR = 2.0

_guard = None


def _mirror_to_telemetry(guard, prefix):
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


def _measure_stash(jax, jnp, mesh, n, M, mb, d, hidden):
    """Temp bytes of the compiled 1f1b step vs gpipe forward + jax.grad,
    same stages / microbatching.  Returns (f1b, gpipe, source)."""
    from mxnet_tpu.parallel.pipeline import (gpipe, one_f_one_b,
                                             stack_stage_params,
                                             stash_slots)

    def stage(p, h):
        h = jnp.tanh(h @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]

    rs = np.random.RandomState(0)
    params = stack_stage_params(
        [{"w1": jnp.asarray(rs.randn(d, hidden), jnp.float32) * 0.3,
          "b1": jnp.asarray(rs.randn(hidden), jnp.float32) * 0.1,
          "w2": jnp.asarray(rs.randn(hidden, d), jnp.float32) * 0.3,
          "b2": jnp.asarray(rs.randn(d), jnp.float32) * 0.1}
         for _ in range(n)])
    x = jnp.asarray(rs.rand(M * mb, d), jnp.float32)
    y = jnp.asarray(rs.rand(M * mb, d), jnp.float32)

    def mse(out, t):
        return ((out - t) ** 2).mean()

    def f1b(p, x_, y_):
        return one_f_one_b(stage, p, x_, y_, mse, M, mesh=mesh)

    def gpipe_ad(p, x_, y_):
        # the baseline the paper's 1F1B replaces: GPipe forward, stash
        # handled by plain reverse-mode AD over the whole schedule
        return jax.grad(
            lambda q: mse(gpipe(stage, q, x_, M, mesh=mesh), y_))(p)

    def temp_bytes(fn, *args):
        comp = jax.jit(fn).lower(*args).compile()
        ma = comp.memory_analysis()
        t = getattr(ma, "temp_size_in_bytes", None)
        if t is None and isinstance(ma, (list, tuple)) and ma:
            t = getattr(ma[0], "temp_size_in_bytes", None)
        return t

    try:
        t_f1b = temp_bytes(f1b, params, x, y)
        t_gp = temp_bytes(gpipe_ad, params, x, y)
        if t_f1b and t_gp:
            return t_f1b, t_gp, "memory_analysis"
    except Exception:
        pass
    # analytic fallback: per-stage activation stash, mb bytes each.
    # 1F1B keeps at most S=2n-1 stage inputs in its rotating stash;
    # AD through GPipe keeps all M microbatch inputs per stage.
    mb_bytes = mb * d * 4
    return stash_slots(n) * mb_bytes, M * mb_bytes, "analytic"


def _fused_pipeline_ms(mx, jax, jnp, mesh, pipeline, zero, batch,
                       n_blocks, width, reps):
    from mxnet_tpu.gluon.loss import L2Loss
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    for _ in range(n_blocks):
        net.add(mx.gluon.nn.Dense(width, activation="tanh",
                                  in_units=width, flatten=False))
    net.initialize()
    step = FusedTrainStep(net, L2Loss(),
                          mx.optimizer.Adam(learning_rate=1e-3),
                          mesh=mesh, pipeline=pipeline, zero=zero)
    rs = np.random.RandomState(1)
    x = mx.nd.NDArray(jnp.asarray(rs.rand(batch, width), jnp.float32))
    y = mx.nd.NDArray(jnp.asarray(rs.rand(batch, width), jnp.float32))
    for _ in range(3):
        step(x, y)
    jax.block_until_ready(step._tr)
    t0 = time.perf_counter()
    for _ in range(reps):
        step(x, y)
    jax.block_until_ready(step._tr)
    return (time.perf_counter() - t0) / reps * 1e3, step


def main():
    global _guard
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    _guard = guard = BudgetGuard(
        "pipeline_1f1b_stash_shrink_vs_gpipe_ad", "x").install()
    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel.mesh import hybrid_mesh, local_mesh
    from mxnet_tpu.parallel.pipeline import bubble_ratio, stash_slots
    from mxnet_tpu.parallel import make_mesh

    n = int(os.environ.get("BENCH_PP_STAGES", "4"))
    M = int(os.environ.get("BENCH_PP_MICROBATCHES", "16"))
    mb = int(os.environ.get("BENCH_PP_MBSIZE", "8"))
    reps = int(os.environ.get("BENCH_PP_REPS", "5"))
    width = int(os.environ.get("BENCH_PP_WIDTH", "64"))

    pp_mesh = make_mesh([n], ["pp"])
    guard.best["phase"] = "stash"
    t_f1b, t_gp, source = _measure_stash(jax, jnp, pp_mesh, n, M, mb,
                                         d=width, hidden=width)
    shrink = t_gp / max(1, t_f1b)

    guard.best["phase"] = "fused_pipelined"
    telemetry.enable()
    telemetry.reset()
    batch = 2 * M * 4  # dp=2, microbatch size 4
    pp_ms, step = _fused_pipeline_ms(mx, jax, jnp,
                                     hybrid_mesh(dp=2, pp=n), M, 1,
                                     batch, n_blocks=2 * n, width=width,
                                     reps=reps)
    snap = telemetry.snapshot()
    telemetry.disable()

    guard.best["phase"] = "fused_unpipelined"
    base_ms, _ = _fused_pipeline_ms(mx, jax, jnp, local_mesh(8), None,
                                    None, batch, n_blocks=2 * n,
                                    width=width, reps=reps)

    guard.best.update({
        "value": round(shrink, 2),
        "vs_baseline": round(shrink / STASH_SHRINK_FLOOR, 3),
        "phase": "done",
        "num_stages": n,
        "num_microbatches": M,
        "stash_source": source,
        "stash_bytes_1f1b": int(t_f1b),
        "stash_bytes_gpipe_ad": int(t_gp),
        "stash_slots_1f1b": stash_slots(n),
        "bubble_ratio": round(bubble_ratio(n, M), 4),
        "bubble_ratio_gauge":
            snap["gauges"].get("pipeline_bubble_ratio"),
        "pipelined_ms_per_step": round(pp_ms, 3),
        "unpipelined_ms_per_step": round(base_ms, 3),
        "zero_stage": step.zero_stage,
    })
    guard.emit()
    telemetry.enable()
    _mirror_to_telemetry(guard, "pipeline_bench")
    assert shrink >= STASH_SHRINK_FLOOR, (
        f"1F1B stash shrink {shrink:.2f}x below the "
        f"{STASH_SHRINK_FLOOR}x floor at M={M}, n={n}")


#: acceptance bar: the interleaved bubble must be <= 0.75x the classic
#: 1F1B bubble at equal microbatch count (headline value is the inverse
#: ratio, so the floor is 1/0.75)
INTERLEAVE_BUBBLE_FLOOR = 1.0 / 0.75


def main_interleaved():
    """`--interleaved` (ISSUE 17): Megatron-style interleaved virtual
    stages through ParallelPlan. At pp=4, M=8, virtual=2 the schedule
    runs T = 2·M·v + 2(n-1) half-ticks, so the measured
    `pipeline_bubble_ratio` gauge drops from (n-1)/(M+n-1) to
    (T-2Mv)/T — the headline `value` is bubble(v=1)/bubble(v=2) with
    a 1/0.75 floor. The same leg pins compiled-step SGD parity between
    virtual=1 and virtual=2 and that each plan signature XLA-compiles
    its step function exactly once (the traced chunk index keeps every
    virtual chunk inside ONE executable)."""
    global _guard
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    _guard = guard = BudgetGuard(
        "pipeline_interleaved_bubble_speedup", "x").install()
    import logging

    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon.loss import L2Loss
    from mxnet_tpu.parallel.pipeline import (bubble_ratio,
                                             interleaved_bubble_ratio)
    from mxnet_tpu.parallel.plan import ParallelPlan

    n = int(os.environ.get("BENCH_PPI_STAGES", "4"))
    M = int(os.environ.get("BENCH_PPI_MICROBATCHES", "8"))
    v = int(os.environ.get("BENCH_PPI_VIRTUAL", "2"))
    mb = int(os.environ.get("BENCH_PP_MBSIZE", "8"))
    reps = int(os.environ.get("BENCH_PP_REPS", "5"))
    width = int(os.environ.get("BENCH_PP_WIDTH", "64"))
    batch = 2 * M * mb  # dp=2

    class _CompileLog(logging.Handler):
        def __init__(self):
            super().__init__(logging.WARNING)
            self.msgs = []

        def emit(self, record):
            m = record.getMessage()
            if "fn_step" in m and "compilation" in m.lower():
                self.msgs.append(m)

    def run(virtual):
        mx.random.seed(0)
        net = mx.gluon.nn.HybridSequential()
        for _ in range(2 * n):
            net.add(mx.gluon.nn.Dense(width, activation="tanh",
                                      in_units=width, flatten=False))
        net.initialize()
        plan = ParallelPlan(dp=2, pp=n, microbatches=M, virtual=virtual)
        step = plan.lower(net, L2Loss(),
                          mx.optimizer.SGD(learning_rate=0.1,
                                           momentum=0.9))
        rs = np.random.RandomState(1)
        x = mx.nd.NDArray(jnp.asarray(rs.rand(batch, width),
                                      jnp.float32))
        y = mx.nd.NDArray(jnp.asarray(rs.rand(batch, width),
                                      jnp.float32))
        log = _CompileLog()
        old_flag = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(log)
        try:
            losses = [float(step(x, y)) for _ in range(3)]
        finally:
            logging.getLogger("jax").removeHandler(log)
            jax.config.update("jax_log_compiles", old_flag)
        jax.block_until_ready(step._tr)
        t0 = time.perf_counter()
        with telemetry.phase("bench"):
            for _ in range(reps):
                step(x, y)
            jax.block_until_ready(step._tr)
        ms = (time.perf_counter() - t0) / reps * 1e3
        bubble = telemetry.snapshot()["gauges"].get(
            "pipeline_bubble_ratio")
        step.sync_to_params()
        weights = {k: np.asarray(p.data()._data)
                   for k, p in net.collect_params().items()}
        return losses, weights, bubble, ms, len(log.msgs)

    telemetry.enable()
    telemetry.reset()
    guard.best["phase"] = "virtual1"
    l1, w1, bub1, ms1, compiles1 = run(1)
    guard.best["phase"] = "virtual2"
    lv, wv, bubv, msv, compilesv = run(v)
    telemetry.disable()

    parity = float(max(abs(a - b) for a, b in zip(l1, lv)))
    w_parity = float(max(np.max(np.abs(w1[k] - wv[k])) for k in w1))
    cut = bub1 / bubv if bubv else float("inf")
    guard.best.update({
        "value": round(cut, 3),
        "vs_baseline": round(cut / INTERLEAVE_BUBBLE_FLOOR, 3),
        "phase": "done",
        "num_stages": n,
        "num_microbatches": M,
        "virtual_stages": v,
        "interleaved_bubble_ratio": round(bubv, 4),
        "baseline_bubble_ratio": round(bub1, 4),
        "bubble_ratio_analytic_v1": round(bubble_ratio(n, M), 4),
        "bubble_ratio_analytic_interleaved": round(
            interleaved_bubble_ratio(2 * M * v + 2 * (n - 1), M, v), 4),
        "interleaved_ms_per_step": round(msv, 3),
        "noninterleaved_ms_per_step": round(ms1, 3),
        "loss_parity_max_abs_diff": parity,
        "weight_parity_max_abs_diff": w_parity,
        "fn_step_compiles_v1": compiles1,
        "fn_step_compiles_interleaved": compilesv,
        "floor": round(INTERLEAVE_BUBBLE_FLOOR, 4),
    })
    telemetry.enable()
    _mirror_to_telemetry(guard, "pipeline_interleaved")
    assert compiles1 == 1 and compilesv == 1, (
        f"exactly one compiled executable per plan signature: "
        f"v1={compiles1}, v{v}={compilesv}")
    assert parity == 0.0 and w_parity == 0.0, (
        f"interleaved schedule must be bit-exact vs virtual=1 under "
        f"SGD: loss diff {parity}, weight diff {w_parity}")
    assert bubv <= 0.75 * bub1, (
        f"interleaved bubble {bubv:.4f} must be <= 0.75x the "
        f"non-interleaved {bub1:.4f} at pp={n}, M={M}, v={v}")


if __name__ == "__main__":
    try:
        if "--interleaved" in sys.argv:
            main_interleaved()
        else:
            main()
    except Exception as e:  # always emit a JSON line; rc stays 0
        import traceback

        traceback.print_exc()
        best = dict(_guard.best) if _guard is not None else {
            "metric": "pipeline_1f1b_stash_shrink_vs_gpipe_ad",
            "value": 0.0, "unit": "x", "vs_baseline": 0.0}
        best["error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps(best))
