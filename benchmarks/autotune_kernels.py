"""Kernel autotune harness (round-3 verdict item 2; reference
analogue: the fork's per-arch kernel tuning — cuDNN autotune,
MSHADOW_TUNING).

Sweeps every perf-sensitive Pallas constant on whatever backend is
available and emits a JSON table; with --write the winners land in
`mxnet_tpu/kernels/tuned.json`, which `kernels/tuning.py` serves to
the kernel modules at trace time. Sweep space:

- flash attention fwd + merged bwd: block_q x block_k in {128, 256,
  512, 1024} at the train cell's shape and at a prefill's
- fused RMSNorm: row_block_want in {128, 256, 512, 1024}
- fused softmax-CE: row_block_want in {64, 128, 256, 512}
- flash decode: Pallas-vs-reference speedup across cache sizes S;
  the VMEM gate budget is raised only to cover sizes where the
  Pallas kernel actually wins
- paged decode: in-kernel (scalar-prefetch block table) vs the
  gather fallback across pool block sizes; winners set the paged
  VMEM gate and the serving cache's preferred block size

On CPU the kernels run under the Pallas interpreter, so the timings
validate the harness (and the sweep plumbing) but are NOT advisory for
TPU constants — winners are still recorded, under the "cpu" platform
section, which TPU runs never read. Timing discipline:
chained/accumulated dispatch, host fetch of a chain-dependent scalar,
difference timing so the dispatch and fetch overheads cancel.

It takes the platform JAX gives its own process. Budget-guarded
(BENCH_BUDGET_S, default 540): when time runs out the BudgetGuard
prints the best-so-far table and ends the run with a non-zero exit
code.
"""
import argparse
import functools
import json
import os
import signal
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "540"))


class BudgetGuard:
    """Self-defended deadline of the sweep.

    Holds the best-measurement-so-far dict. When the budget expires it
    prints that dict as a JSON line and ends the process with exit code
    1 — a run that did not finish is a failed run — via a daemon
    THREAD, not signal.alarm: Python signal handlers only run between
    bytecodes on the main thread, so a main thread blocked in a C call
    (XLA compile, block_until_ready) never sees SIGALRM/SIGTERM. The
    timer thread's os._exit always fires."""

    def __init__(self, metric, unit, budget_s=None):
        self.budget_s = BUDGET_S if budget_s is None else budget_s
        self.t0 = time.monotonic()
        self.best = {"metric": metric, "value": 0.0, "unit": unit,
                     "vs_baseline": 0.0, "phase": "startup"}

    def remaining(self):
        return self.budget_s - (time.monotonic() - self.t0)

    def emit(self):
        sys.stdout.write(json.dumps(self.best) + "\n")
        sys.stdout.flush()

    def _deadline(self, signum=None, frame=None):
        # never let this thread die before os._exit: snapshot the dict
        # (the main thread may be mutating it) and exit even if
        # emission fails
        try:
            snap = dict(self.best)
            snap["error"] = "budget expired; best-so-far emitted"
            sys.stdout.write(json.dumps(snap) + "\n")
            sys.stdout.flush()
        finally:
            os._exit(1)

    def install(self):
        t = threading.Timer(max(5.0, self.budget_s), self._deadline)
        t.daemon = True
        t.start()
        # best-effort: if the main thread IS interruptible, end on the
        # driver's TERM the same way
        signal.signal(signal.SIGTERM, self._deadline)
        return self


_guard = None


def _remaining():
    return _guard.remaining()


def _diff_time(run_chain, lo, hi):
    """Seconds per iteration via difference timing (the module
    docstring says why)."""
    dt_lo = run_chain(lo)
    dt_hi = run_chain(hi)
    dd = dt_hi - dt_lo
    if dd > 1e-4:
        return dd / (hi - lo)
    return dt_hi / max(hi, 1)


def sweep_flash_attention(on_tpu, interpret):
    """block_q x block_k of the forward and of the merged backward at
    the two shapes the benchmark's cells run: the train step's (BERT:
    64 x 512, 12 heads of 64, not causal, lengths 256-512; forward and
    backward) and a prefill's (2,048 positions, 32 / 8 heads of 128,
    causal; forward only). The winner has the least sum of times, each
    over the best of its row, so neither shape outweighs the other.
    Heads a grid step are not swept: beyond the two that fill the
    lanes they changed nothing on a v5e (tuned.json's note, PR 31)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.kernels import flash_attention as fa

    if on_tpu:
        shapes = {"train": (64, 512, 12, 12, 64, False, True),
                  "prefill": (1, 2048, 32, 8, 128, True, False)}
        dtype, lo, hi = jnp.bfloat16, 3, 9
        cands = [128, 256, 512, 1024]
    else:
        shapes = {"train": (2, 256, 2, 2, 64, False, True),
                  "prefill": (1, 256, 4, 2, 128, True, False)}
        dtype, lo, hi = jnp.float32, 1, 2
        cands = [128, 256]

    def timed(fn, args):
        def chain(iters):
            t0 = time.perf_counter()
            acc = None
            for _ in range(iters):
                s = jnp.sum(fn(*args)[0].astype(jnp.float32))
                acc = s if acc is None else acc + s
            float(acc)
            return time.perf_counter() - t0

        chain(1)  # compile
        return round(_diff_time(chain, lo, hi) * 1e3, 3)

    res, score = {}, {}
    for label, (B, T, H, K, d, causal, bwd) in shapes.items():
        kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(0), 4)
        q = (jax.random.normal(kq, (B, T, H, d)) * 0.5).astype(dtype)
        k = (jax.random.normal(kk, (B, T, K, d)) * 0.5).astype(dtype)
        v = (jax.random.normal(kv, (B, T, K, d)) * 0.5).astype(dtype)
        g = (jax.random.normal(kg, (B, T, H, d)) * 0.5).astype(dtype)
        lengths = jnp.asarray(np.linspace(T // 2, T, B).astype(np.int32))
        scale = 1.0 / (d ** 0.5)
        out, lse = fa._pallas_forward(q, k, v, causal, scale,
                                      interpret=interpret,
                                      return_lse=True, lengths=lengths)
        delta = fa._rowsum_per_head(g, out)
        rows = {"fwd": [], "bwd": []} if bwd else {"fwd": []}
        # the committed blocks first, so a budget cutoff still records
        # a line for them
        combos = sorted(((bq, bk) for bq in cands for bk in cands
                         if bq <= T and bk <= T),
                        key=lambda c: (c != (512, 512), c))
        for bq, bk in combos:
            if _remaining() < 30.0:
                break
            kw = dict(causal=causal, scale=scale, block_q=bq, block_k=bk,
                      interpret=interpret, lengths=lengths)
            calls = {"fwd": (functools.partial(
                fa._pallas_forward, return_lse=True, **kw), (q, k, v)),
                "bwd": (functools.partial(fa._pallas_backward, **kw),
                        (q, k, v, lse, delta, g))}
            for kind in rows:
                row = {"block_q": bq, "block_k": bk}
                try:
                    row["ms"] = timed(*calls[kind])
                except Exception as e:
                    row["error"] = f"{type(e).__name__}"[:60]
                rows[kind].append(row)
        res[label] = {"shape": [B, T, H, K, d], "causal": causal, **rows}
        for kind, rs in rows.items():
            ok = [r for r in rs if "ms" in r]
            best = min((r["ms"] for r in ok), default=None)
            for r in ok:
                key = (r["block_q"], r["block_k"])
                score.setdefault(key, []).append(r["ms"] / best)
    full = max((len(v) for v in score.values()), default=0)
    done = {c: sum(v) for c, v in score.items() if len(v) == full}
    if not done:
        return res, None
    bq, bk = min(done, key=done.get)
    return res, {"block_q": bq, "block_k": bk}


def sweep_norm(on_tpu, interpret):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import fused_norm as fn
    from mxnet_tpu.kernels import tuning

    if on_tpu:
        n, d, dtype = 16384, 1024, jnp.bfloat16
        lo, hi = 4, 12
        cands = [128, 256, 512, 1024]
    else:
        n, d, dtype = 512, 128, jnp.float32
        lo, hi = 1, 2
        cands = [128, 256]
    x2 = (jax.random.normal(jax.random.PRNGKey(1), (n, d))
          .astype(dtype))
    g = jnp.ones((d,), dtype)

    rows_out = []
    try:
        for want in cands:
            if _remaining() < 20.0:
                break
            tuning.set_runtime("fused_norm", "row_block_want", want)
            f = jax.jit(functools.partial(fn._rms_pallas_fwd, eps=1e-6,
                                          interpret=interpret))

            def chain(iters):
                t0 = time.perf_counter()
                c = x2
                for _ in range(iters):
                    c, _rr = f(c, g)
                float(jnp.sum(c.astype(jnp.float32)))
                return time.perf_counter() - t0

            try:
                chain(1)
                s_it = _diff_time(chain, lo, hi)
                rows_out.append({"row_block_want": want,
                                 "ms": round(s_it * 1e3, 3)})
            except Exception as e:
                rows_out.append({"row_block_want": want,
                                 "error": f"{type(e).__name__}"[:60]})
    finally:
        tuning.clear_runtime()
    timed = [r for r in rows_out if "ms" in r]
    winner = min(timed, key=lambda r: r["ms"]) if timed else None
    win = ({"row_block_want": winner["row_block_want"]}
           if winner else None)
    return {"shape": [n, d], "rows": rows_out}, win


def sweep_ce(on_tpu, interpret):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import fused_ce as fc
    from mxnet_tpu.kernels import tuning

    if on_tpu:
        n, v, dtype = 2048, 30522, jnp.bfloat16
        lo, hi = 4, 12
        cands = [64, 128, 256, 512]
    else:
        n, v, dtype = 64, 1024, jnp.float32
        lo, hi = 1, 2
        cands = [64, 128]
    x2 = (jax.random.normal(jax.random.PRNGKey(2), (n, v)) * 0.1) \
        .astype(dtype)
    lbl = jax.random.randint(jax.random.PRNGKey(3), (n,), 0, v)

    rows_out = []
    try:
        for want in cands:
            if _remaining() < 20.0:
                break
            tuning.set_runtime("fused_ce", "row_block_want", want)
            f = jax.jit(functools.partial(fc._ce_pallas,
                                          interpret=interpret))

            def chain(iters):
                t0 = time.perf_counter()
                acc = None
                for _ in range(iters):
                    loss = f(x2, lbl)
                    s = jnp.sum(loss.astype(jnp.float32))
                    acc = s if acc is None else acc + s
                float(acc)
                return time.perf_counter() - t0

            try:
                chain(1)
                s_it = _diff_time(chain, lo, hi)
                rows_out.append({"row_block_want": want,
                                 "ms": round(s_it * 1e3, 3)})
            except Exception as e:
                rows_out.append({"row_block_want": want,
                                 "error": f"{type(e).__name__}"[:60]})
    finally:
        tuning.clear_runtime()
    timed = [r for r in rows_out if "ms" in r]
    winner = min(timed, key=lambda r: r["ms"]) if timed else None
    win = ({"row_block_want": winner["row_block_want"]}
           if winner else None)
    return {"shape": [n, v], "rows": rows_out}, win


def sweep_decode(on_tpu, interpret):
    """Pallas decode vs dequantize-reference across cache sizes; the
    VMEM gate is only worth raising over sizes where Pallas wins."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import flash_decode as fd

    if on_tpu:
        B, H, d, dtype = 8, 16, 64, jnp.bfloat16
        sizes = [1024, 2048, 4096, 8192]
        lo, hi = 4, 12
    else:
        B, H, d, dtype = 1, 2, 32, jnp.float32
        sizes = [256]
        lo, hi = 1, 2
    rows_out = []
    best_bytes = None   # largest cache the Pallas kernel WON at
    loss_bytes = None   # smallest cache it LOST at
    for S in sizes:
        if _remaining() < 25.0:
            break
        q = (jax.random.normal(jax.random.PRNGKey(4), (B, H, d)) * 0.1) \
            .astype(dtype)
        kc = (jax.random.normal(jax.random.PRNGKey(5), (B, H, S, d))
              * 0.1).astype(dtype)
        vc = (jax.random.normal(jax.random.PRNGKey(6), (B, H, S, d))
              * 0.1).astype(dtype)
        vl = jnp.full((B,), S, jnp.int32)
        scale = 1.0 / (d ** 0.5)
        row = {"S": S,
               "cache_bytes": 2 * S * d * jnp.dtype(dtype).itemsize}

        def timed_call(fun):
            f = jax.jit(fun)

            def chain(iters):
                t0 = time.perf_counter()
                acc = None
                for _ in range(iters):
                    o = f(q, kc, vc, vl)
                    s = jnp.sum(o.astype(jnp.float32))
                    acc = s if acc is None else acc + s
                float(acc)
                return time.perf_counter() - t0

            chain(1)
            return _diff_time(chain, lo, hi)

        try:
            row["pallas_ms"] = round(timed_call(
                lambda q_, k_, v_, l_: fd._flash_decode_pallas(
                    q_, k_, v_, l_, scale, interpret)) * 1e3, 3)
            row["reference_ms"] = round(timed_call(
                lambda q_, k_, v_, l_: fd.reference_decode_attention(
                    q_, k_, v_, l_, scale)) * 1e3, 3)
            if row["pallas_ms"] < row["reference_ms"]:
                best_bytes = max(best_bytes or 0, row["cache_bytes"])
            else:
                loss_bytes = min(loss_bytes or (1 << 62),
                                 row["cache_bytes"])
        except Exception as e:
            row["error"] = f"{type(e).__name__}"[:60]
        rows_out.append(row)
    win = None
    if on_tpu and best_bytes is not None:
        # cover the largest WINNING size; extend headroom (one power
        # of two, capped at 14 MiB for the working blocks) only when
        # no measured LOSS sits in that extension — "raise the gate
        # only where Pallas wins"
        budget = min(best_bytes * 2, 14 << 20)
        if loss_bytes is not None and loss_bytes <= budget:
            budget = best_bytes
        win = {"vmem_cache_budget_bytes": budget}
    return {"rows": rows_out}, win


def sweep_paged(on_tpu, interpret):
    """In-kernel paged decode vs the gather fallback across pool
    block sizes. The winner sets the block size serving caches should
    prefer (preferred_block_size). The kernel's VMEM budget
    (flash_decode_paged.vmem_budget_bytes) is how many pages one step
    of its sweep holds and is not this sweep's to shrink: on a v5e the
    kernel's time fell with every page up to it (PERF.md, PR 25)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import flash_decode as fd

    if on_tpu:
        # head_dim 128: the compiled sweep needs whole 128-lane rows
        B, K, H, d, dtype = 8, 8, 32, 128, jnp.bfloat16
        S = 2048
        cands = [16, 32, 64, 128]
        lo, hi = 4, 12
    else:
        B, K, H, d, dtype = 2, 2, 4, 32, jnp.float32
        S = 128
        cands = [8, 16]
        lo, hi = 1, 2
    scale = 1.0 / (d ** 0.5)
    rows_out = []
    best = None          # (ms, block_size) of the winner
    for bs in cands:
        if _remaining() < 25.0:
            break
        nb = S // bs
        N = B * nb + 1   # + scratch block 0
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
        q = (jax.random.normal(kq, (B, H, d)) * 0.1).astype(dtype)
        kp = (jax.random.normal(kk, (N, K, bs, d)) * 0.1).astype(dtype)
        vp = (jax.random.normal(kv, (N, K, bs, d)) * 0.1).astype(dtype)
        bt = jnp.arange(1, N, dtype=jnp.int32).reshape(B, nb)
        vl = jnp.full((B,), S, jnp.int32)
        itemsize = jnp.dtype(dtype).itemsize
        row = {"block_size": bs,
               "pages_per_step": fd._paged_sweep_pages(
                   kp.shape, itemsize, nb)}

        def timed_call(fun):
            f = jax.jit(fun)

            def chain(iters):
                t0 = time.perf_counter()
                acc = None
                for _ in range(iters):
                    o = f(q, kp, vp, bt, vl)
                    s = jnp.sum(o.astype(jnp.float32))
                    acc = s if acc is None else acc + s
                float(acc)
                return time.perf_counter() - t0

            chain(1)
            return _diff_time(chain, lo, hi)

        try:
            row["inkernel_ms"] = round(timed_call(
                lambda q_, k_, v_, b_, l_: fd._flash_decode_paged_pallas(
                    q_, k_, v_, b_, l_, scale, interpret)) * 1e3, 3)
            # the fallback it replaces: gather to contiguous + the
            # contiguous flash sweep
            row["gather_ms"] = round(timed_call(
                lambda q_, k_, v_, b_, l_: fd.flash_decode(
                    q_, fd.gather_kv_pages(k_, b_),
                    fd.gather_kv_pages(v_, b_), l_,
                    scale=scale)) * 1e3, 3)
            if row["inkernel_ms"] < row["gather_ms"] \
                    and (best is None or row["inkernel_ms"] < best[0]):
                best = (row["inkernel_ms"], bs)
        except Exception as e:
            row["error"] = f"{type(e).__name__}"[:60]
        rows_out.append(row)
    win = None
    if on_tpu and best is not None:
        win = {"preferred_block_size": best[1]}
    return {"shape": [B, K, H, d, S], "rows": rows_out}, win


def write_tuned(winners, backend, meta):
    from mxnet_tpu.kernels import tuning

    path = tuning.tuned_path()
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        table = {}
    sec = table.setdefault(backend, {})
    for family, win in winners.items():
        if win:
            sec.setdefault(family, {}).update(win)
    table.setdefault("meta", {})[backend] = meta
    with open(path, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
        f.write("\n")
    tuning.reload()
    return path


def main(argv=None):
    global _guard
    ap = argparse.ArgumentParser()
    ap.add_argument("--write", action="store_true",
                    help="commit winners to mxnet_tpu/kernels/tuned.json")
    ap.add_argument("--families", default="flash,norm,ce,decode,paged")
    args = ap.parse_args(argv)

    import jax

    from mxnet_tpu import tracing

    _guard = BudgetGuard("autotune_kernels", "families").install()
    backend = jax.default_backend()
    on_tpu = backend not in ("cpu",)
    tracing.enable_compile_cache()
    interpret = not on_tpu
    if interpret:
        # the interpreter path needs no Mosaic, runs anywhere
        os.environ.setdefault("MXNET_TPU_FLASH_INTERPRET", "1")
    best = _guard.best
    best.update({"backend": backend, "advisory": on_tpu,
                 "results": {}, "winners": {}})

    sweeps = {"flash": ("flash_attention", sweep_flash_attention),
              "norm": ("fused_norm", sweep_norm),
              "ce": ("fused_ce", sweep_ce),
              "decode": ("flash_decode", sweep_decode),
              "paged": ("flash_decode_paged", sweep_paged)}
    for name in args.families.split(","):
        if name not in sweeps or _remaining() < 25.0:
            continue
        family, fn = sweeps[name]
        try:
            res, win = fn(on_tpu, interpret)
            best["results"][family] = res
            if win:
                best["winners"][family] = win
            best["value"] = float(len(best["results"]))
            _guard.emit()
        except Exception as e:
            import traceback

            traceback.print_exc()
            best["results"][family] = {
                "error": f"{type(e).__name__}: {e}"[:200]}
    if args.write and best["winners"]:
        path = write_tuned(best["winners"], backend,
                           {"time": time.time(),
                            "advisory": on_tpu})
        best["written"] = path
    _guard.emit()


if __name__ == "__main__":
    main()
