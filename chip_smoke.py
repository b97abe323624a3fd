#!/usr/bin/env python3
"""First light on the chip: the main path, once, on the TPU.

    python chip_smoke.py            # no flags, one process, no children

Drives the registered ``llama_3_8b`` preset at its full widths (hidden
4096, feed-forward 14336, 32 query / 8 KV heads of 128, vocab 32000,
bf16) through the two engines a user calls — ``ParallelPlan.lower`` for
training and ``InferenceServer`` for serving — with every Pallas kernel
family compiled by Mosaic, and checks what comes out against the repo's
own references. Depth is the only cut (see ``FULL.layers``); weights are
random, from a seed.

Five phases, each a hard failure: ``device``, ``kernels``, ``train``,
``serve``, ``multichip`` (four or more devices, else one line saying it
was skipped — the only skip there is). The first failed check raises and
the process exits non-zero; nothing here catches an exception to carry
on. It prints facts only (platform, device kind and count, versions,
where the compile cache is and whether it was warm, compile seconds and
counts, peak device memory) and never a rate under a metric's name: it
is a smoke, not a benchmark.

Anything but a TPU is refused in the ``device`` phase. The last line of
standard output is one JSON object naming the device the run was on.

The phases are functions of a :class:`Size`, so ``tests/test_chip_smoke
.py`` runs the same code tiny on the CPU with Pallas in interpret mode;
this script itself takes no size.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.metadata
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Size:
    """Everything a phase is a function of."""
    # -- the decoder (LlamaConfig fields)
    vocab: int
    hidden: int
    ffn: int
    heads: int
    kv_heads: int
    layers: int
    dtype: str
    # -- train: one seeded batch, `steps` steps
    batch: int
    seq: int
    steps: int
    # -- kernels beside the decoder's own shapes: the encoder attention
    # (heads x head_dim at sequence bert_seq, ragged lengths), the
    # LayerNorm width and the second vocabulary; decode cache length,
    # paged block size and the chunk/verify window
    bert_heads: int
    bert_head_dim: int
    bert_seq: int
    ln_width: int
    vocab2: int
    decode_len: int
    block: int
    window: int
    #: the paged decode kernel once more at a table shape that serves:
    #: (rows, pages a row, pool blocks, shortest and longest context)
    serve_table: tuple
    #: a sliding-window layer's window, for the windowed variants of
    #: flash_attention_fwd and flash_decode_paged (the serving table is
    #: swept once more at `serve_window`), and an expert layer's
    #: (published experts, held here, expert width, experts a token)
    #: for the grouped matmul
    sliding_window: int
    serve_window: int
    experts: tuple
    # -- serve
    slots: int
    max_len: int
    max_prompt: int
    #: per wave, per request: (prompt tokens, new tokens, temperature)
    waves: tuple
    #: True on the chip: kernels are Mosaic-compiled and every check on
    #: the module text applies. False in the CPU rehearsal, where the
    #: same kernels run under the Pallas interpreter and leave no custom
    #: call to count.
    compiled: bool = True

    @property
    def head_dim(self):
        return self.hidden // self.heads


#: Llama-3-8B widths. Depth 2 of 32: one layer is 218M parameters and the
#: embedding plus head another 262M, so two layers are 698M. Compiled for
#: a v5e, the step holds 6.5 GiB of arguments (the bf16 weight and AdamW's
#: two fp32 moments, 10 bytes a parameter, donated) and 3.5 GiB of
#: temporaries at 4 x 2048 tokens without rematerialization; the
#: parameters' bf16 gradient buffers add 1.3 GiB: 11.3 GiB of the chip's
#: 15.75. A third layer is 2.4 GiB more state and ~0.8 GiB more
#: temporaries, which fits only on paper. Two layers already make the
#: step's module repeat every per-layer kernel, which is what depth is
#: for here.
FULL = Size(
    vocab=32000, hidden=4096, ffn=14336, heads=32, kv_heads=8, layers=2,
    dtype="bfloat16",
    batch=4, seq=2048, steps=5,
    bert_heads=12, bert_head_dim=64, bert_seq=512, ln_width=768,
    vocab2=30522, decode_len=4096, block=16, window=4,
    # mistral_7b.reason (BENCHMARK.json): 20 slots of max_len 8448
    serve_table=(20, 528, 5633, 1478, 6118),
    # trinity_large (BENCHMARK.json): window 4096; 8 of 256 experts of
    # width 3072 held here, 4 a token
    sliding_window=1024, serve_window=4096, experts=(256, 8, 3072, 4),
    slots=8, max_len=2048, max_prompt=1024,
    waves=(((700, 4, 0.0), (24, 24, 0.0), (1000, 8, 0.8), (57, 32, 0.0),
            (311, 16, 0.7), (990, 12, 0.0), (128, 20, 0.9)),
           ((33, 16, 0.0), (640, 8, 0.8), (1001, 6, 0.0), (90, 24, 0.6),
            (480, 12, 0.0), (16, 28, 0.0), (850, 10, 0.9))),
)

# -- tolerances, each with its reason ------------------------------------
#: kernel output vs the float32 reference at "highest", as max |a - b|
#: over max |b|. The attention and decode kernels take bf16 operands,
#: accumulate in fp32 and round the result to bf16 once (2^-9 relative),
#: and the MXU rounds the fp32 softmax weights to bf16 before the second
#: matmul (another 2^-9 per term, averaging down over the row): 1e-2
#: leaves both a factor of two. Gradients pass through two such matmuls.
#: (Measured on the v5e, worst output or gradient of each family:
#: attention 5.0e-3, norms 4.8e-3, cross-entropy 2.5e-3, decode 4.1e-3.)
TOL_ATTN = 1e-2
TOL_ATTN_GRAD = 2e-2
#: the int8 cache is compared with the reference ON THE DEQUANTIZED
#: cache, so the kernel's error is the same as the bf16 one
TOL_DECODE = 1e-2
TOL_SCAN = 1e-3      # float32 in and out; exp and the order of a 16-term sum differ
#: norm kernels compute in fp32 and round once to the activation dtype
TOL_NORM = 1e-2
#: the per-row loss is fp32 from the same upcast logits as the
#: reference — only the order of a 32000-term sum and the exp/log
#: approximations differ; its gradient is rounded to the logits' dtype
TOL_CE_LOSS = 1e-4
TOL_CE_GRAD = 1e-2
#: served logits vs the float32 reference forward on the same tokens,
#: as max |a - b| over the 2 x 32000 logits, in units of their spread
#: std(b). The served path rounds weights and every activation to bf16
#: (2^-9 relative each, ~20 roundings deep through two blocks and the
#: head), which puts the per-logit error near 1% of the spread, and the
#: maximum over 64000 of them sits about four deviations out. Measured
#: on the v5e: 0.052 (prefill) and 0.057 (first decode step).
TOL_LOGITS = 0.15
#: dp=4 / dp=2 x tp=2 first loss vs the one-chip first loss on the same
#: global batch: the same bf16 math summed in another order. The loss is
#: a mean over 8192 rows of values ~11, each row good to ~2^-8 relative.
TOL_LOSS_PARALLEL = 2e-2

#: N(0, 0.02) weights, the Llama default. The head then gives logits of
#: variance hidden x 0.02^2 over a unit-RMS input, and the expected
#: cross-entropy of Gaussian logits against a random label is
#: ln V + var / 2.
INIT_STD = 0.02


def say(phase, **facts):
    print(f"[{phase}] " + ", ".join(f"{k}: {v}" for k, v in facts.items()),
          flush=True)


def check(ok, what):
    """One hard check: raise on the first that fails."""
    if not ok:
        raise AssertionError(what)


def rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(np.isfinite(got).all(), "non-finite values in kernel output")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


class CompileMeter:
    """Counts what XLA builds, from jax.monitoring: every executable
    (`builds`, with the seconds spent), and how many of them the
    persistent cache served (`hits`). Eager per-op programs count too —
    "compiles nothing" means nothing."""

    def __init__(self):
        from jax import monitoring
        self.builds = 0
        self.hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.builds += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.builds, self.hits, self.seconds)

    def since(self, mark):
        return {"builds": self.builds - mark[0],
                "cache_hits": self.hits - mark[1],
                "compile_s": round(self.seconds - mark[2], 1)}


def kernel_names(lowered_text):
    """{kernel_name: count} of the Pallas custom calls in a module."""
    return collections.Counter(
        re.findall(r'kernel_name = "([^"]+)"', lowered_text))


def check_no_fallbacks(phase, before):
    """No kernel family fell back to its jnp path since `before` (a
    `fallback_counts()` snapshot taken when the phase began)."""
    from mxnet_tpu.kernels import dispatch

    counts = dispatch.fallback_counts()
    check(counts == before,
          f"kernel fallbacks during {phase}: {before} -> {counts}")
    say(phase, fallback_counts=counts)


def peak_bytes(devices):
    return {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices}


# -- phase: device --------------------------------------------------------

def phase_device(size):
    """A TPU, of a kind the peak table knows, whose clock syncs."""
    import jax
    import jax.numpy as jnp
    import jaxlib

    from mxnet_tpu import goodput

    dev = jax.devices()[0]
    if size.compiled and dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU — jax.devices()[0] is platform "
            f"{dev.platform!r} ({dev.device_kind!r}, "
            f"{len(jax.devices())} device(s)). This script only runs on "
            "the chip; the CPU rehearsal of its logic is "
            "tests/test_chip_smoke.py.")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("device", platform=dev.platform, device_kind=dev.device_kind,
        device_count=len(jax.devices()), jax=jax.__version__,
        jaxlib=jaxlib.__version__, libtpu=libtpu,
        python=sys.version.split()[0])
    peak = goodput.peak_flops(dev)      # raises for an unknown kind
    if peak is None:                    # CPU rehearsal: nothing to bound
        return dev

    # one large bf16 matmul against block_until_ready. A figure above
    # the table's peak means the clock is not waiting for the device
    # (a remote backend once reported 1363 TFLOP/s on this 197 TFLOP/s
    # part) and every later second would be fiction.
    n, reps = 8192, 16
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
    mm = jax.jit(lambda x, y: (x @ y) * jnp.bfloat16(1.0 / n))
    mm(a, a).block_until_ready()
    t0 = time.perf_counter()
    c = a
    for _ in range(reps):
        c = mm(c, a)
    c.block_until_ready()
    tflops = 2.0 * n ** 3 * reps / (time.perf_counter() - t0) / 1e12
    say("device", matmul_bf16_tflops_information_only=round(tflops, 1),
        table_peak_tflops=peak / 1e12)
    check(tflops <= peak / 1e12,
          f"matmul timed at {tflops:.0f} TFLOP/s, above the "
          f"{peak / 1e12:.0f} TFLOP/s peak of {dev.device_kind!r}: "
          "block_until_ready is not syncing")
    check(bool(np.isfinite(np.asarray(c[:8, :8], np.float32)).all()),
          "matmul result is not finite")
    return dev


# -- phase: kernels -------------------------------------------------------

def phase_kernels(size):
    """Every Pallas family, jitted and eagerly, against its reference."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import (dispatch, flash_attention as fa,
                                   flash_decode as fd, fused_ce as ce,
                                   fused_norm as fnorm,
                                   grouped_matmul as _gmm,  # noqa: F401
                                   power_retention as pret,
                                   selective_scan as ssm)
    from mxnet_tpu.parallel import moe

    fallbacks0 = dispatch.fallback_counts()
    dt = jnp.dtype(size.dtype)
    key = iter(jax.random.split(jax.random.PRNGKey(1), 96))

    def randn(shape, dtype=dt, scale=1.0):
        return (jax.random.normal(next(key), shape, jnp.float32)
                * scale).astype(dtype)

    def run(name, kernel, reference, args, kernels_in_module, tols):
        """Jit and eager runs of `kernel(*args)` vs `reference(*args)`
        (pytrees of arrays); `tols` per output leaf."""
        lowered = jax.jit(kernel).lower(*args)
        if size.compiled:
            names = kernel_names(lowered.as_text())
            for k in kernels_in_module:
                check(names[k] >= 1,
                      f"{name}: no {k!r} custom call in the module "
                      f"(found {dict(names)}) — the gate fell through "
                      "to the jnp path")
        with jax.default_matmul_precision("highest"):
            want = jax.tree_util.tree_leaves(jax.jit(reference)(*args))
        errs = []
        for how, fn in (("jit", lowered.compile()), ("eager", kernel)):
            got = jax.tree_util.tree_leaves(fn(*args))
            check(len(got) == len(want), f"{name}: output arity")
            for g, w, tol in zip(got, want, tols):
                e = rel_err(g, w)
                check(e <= tol, f"{name} ({how}): error {e:.2e} above "
                                f"tolerance {tol:.0e}")
                errs.append(e)
        say("kernels", kernel=name, max_rel_err=f"{max(errs):.1e}")

    f32 = jnp.float32

    def up(*xs):
        return [x.astype(f32) for x in xs]

    # flash attention, causal GQA, forward and backward
    H, K, d, T = size.heads, size.kv_heads, size.head_dim, size.seq
    q, k, v = randn((1, T, H, d)), randn((1, T, K, d)), randn((1, T, K, d))
    w = randn((1, T, H, d), f32)        # a cotangent that is not all-ones

    def attn(fn, w, **kw):
        """fwd+bwd of attention `fn`: (out, dq, dk, dv)."""
        def fwd_bwd(q, k, v, *lengths):
            def loss(q, k, v):
                out = fn(q, k, v, lengths=lengths[0] if lengths else None,
                         **kw)
                return jnp.sum(out.astype(f32) * w), out
            (_, out), grads = jax.value_and_grad(
                loss, (0, 1, 2), has_aux=True)(q, k, v)
            return (out,) + grads
        return fwd_bwd

    flash_kernels = ("flash_attention_fwd", "flash_attention_dkv")
    run("flash_attention causal GQA fwd+bwd",
        attn(fa.flash_attention_raw, w, causal=True),
        lambda q, k, v: attn(fa.reference_attention, w, causal=True)(
            *up(q, k, v)),
        (q, k, v), flash_kernels, (TOL_ATTN,) + (TOL_ATTN_GRAD,) * 3)

    # ... and with key-padding lengths at the encoder's shape
    Hb, db, Tb = size.bert_heads, size.bert_head_dim, size.bert_seq
    qb, kb, vb = (randn((4, Tb, Hb, db)) for _ in range(3))
    lens = jnp.asarray([Tb, Tb // 2 + 3, 17, Tb - 1], jnp.int32)

    wb = randn(qb.shape, f32)
    run("flash_attention lengths fwd+bwd",
        attn(fa.flash_attention_raw, wb, causal=False),
        lambda q, k, v, n: attn(fa.reference_attention, wb,
                                causal=False)(*up(q, k, v), n),
        (qb, kb, vb, lens), flash_kernels,
        (TOL_ATTN,) + (TOL_ATTN_GRAD,) * 3)

    # fused norms, forward and backward
    rows = 2 * size.seq
    x, g = randn((rows, size.hidden)), randn((size.hidden,), scale=0.5) + 1
    wn = randn((rows, size.hidden), f32)

    def rms_ref(x, g):
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-5) * g

    def norm_fwd_bwd(fn, wn):
        def f(*a):
            def loss(*a):
                out = fn(*a)
                return jnp.sum(out.astype(f32) * wn), out
            (_, out), grads = jax.value_and_grad(
                loss, tuple(range(len(a))), has_aux=True)(*a)
            return (out,) + grads
        return f

    run("fused_rmsnorm fwd+bwd",
        norm_fwd_bwd(lambda x, g: fnorm.fused_rmsnorm(x, g, 1e-5), wn),
        lambda x, g: norm_fwd_bwd(rms_ref, wn)(*up(x, g)), (x, g),
        ("rmsnorm_fwd", "rmsnorm_bwd"), (TOL_NORM,) * 3)

    xl = randn((4 * size.bert_seq, size.ln_width))
    gl = randn((size.ln_width,), scale=0.5) + 1
    bl = randn((size.ln_width,), scale=0.5)
    wl = randn(xl.shape, f32)

    def ln_ref(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b

    run("fused_layernorm fwd+bwd",
        norm_fwd_bwd(lambda x, g, b: fnorm.fused_layernorm(x, g, b), wl),
        lambda x, g, b: norm_fwd_bwd(ln_ref, wl)(*up(x, g, b)),
        (xl, gl, bl), ("layernorm_fwd", "layernorm_bwd"),
        (TOL_NORM,) * 4)

    # fused cross-entropy at both vocabularies (the second is not a
    # lane multiple: the padding path)
    for V in (size.vocab, size.vocab2):
        n = size.seq
        logits = randn((n, V), scale=2.0)
        labels = jax.random.randint(next(key), (n,), 0, V)
        wr = randn((n,), f32)

        def ce_fwd_bwd(fn):
            def f(x, lbl):
                def loss(x):
                    per = fn(x, lbl)
                    return jnp.sum(per * wr), per
                (_, per), dx = jax.value_and_grad(loss, has_aux=True)(x)
                return per, dx
            return f

        run(f"fused_softmax_ce fwd+bwd vocab {V}",
            ce_fwd_bwd(ce.fused_softmax_ce_raw),
            lambda x, lbl: ce_fwd_bwd(ce.reference_softmax_ce)(
                x.astype(f32), lbl),
            (logits, labels), ("softmax_ce_fwd", "softmax_ce_bwd"),
            (TOL_CE_LOSS, TOL_CE_GRAD))

    # decode kernels: one position against a cache, GQA without repeat
    B, S, bs = size.slots, size.decode_len, size.block
    qd = randn((B, H, d))
    kc, vc = randn((B, K, S, d)), randn((B, K, S, d))
    vl = jnp.asarray([(S * (i + 1)) // B - (i % 3) for i in range(B)],
                     jnp.int32)
    run("flash_decode contiguous", fd.flash_decode,
        lambda q, k, v, n: fd.reference_decode_attention(
            *up(q, k, v), n), (qd, kc, vc, vl),
        ("flash_decode",), (TOL_DECODE,))

    k8, ks, v8, vs = fd.quantize_kv(kc, vc)
    run("flash_decode contiguous int8", fd.flash_decode_quantized,
        lambda q, k8, ks, v8, vs, n: fd.reference_decode_attention(
            q.astype(f32), fd.dequantize_kv(k8, ks, f32),
            fd.dequantize_kv(v8, vs, f32), n),
        (qd, k8, ks, v8, vs, vl), ("flash_decode_q8",), (TOL_DECODE,))

    # paged: the same caches cut into blocks and scattered over a pool
    # through a shuffled block table (block 0 is the scratch sink)
    nb = S // bs
    perm = np.random.RandomState(2).permutation(B * nb) + 1
    bt = jnp.asarray(perm.reshape(B, nb), jnp.int32)

    def to_pool(c):
        blocks = c.reshape(B, K, nb, bs, c.shape[-1]) \
            .transpose(0, 2, 1, 3, 4).reshape(B * nb, K, bs, c.shape[-1])
        pool = jnp.zeros((B * nb + 1,) + blocks.shape[1:], c.dtype)
        return pool.at[bt.reshape(-1)].set(blocks)

    def paged_ref(q, kp, vp, bt, n):
        return fd.reference_decode_attention(
            q.astype(f32), fd.gather_kv_pages(kp, bt).astype(f32),
            fd.gather_kv_pages(vp, bt).astype(f32), n)

    kp, vp = to_pool(kc), to_pool(vc)
    run("flash_decode paged", fd.flash_decode_paged, paged_ref,
        (qd, kp, vp, bt, vl), ("flash_decode_paged",), (TOL_DECODE,))

    # ... and at the table shape that serves, so that a first-light
    # run compiles the kernel at that size: ragged rows, the pages
    # past each row's length left at the sink, scattered over the pool
    Bs, nbs, Ns, lo, hi = size.serve_table
    rs = np.random.RandomState(3)
    vls = rs.permutation(np.linspace(lo, hi, Bs).astype(np.int32))
    free = iter(rs.permutation(Ns - 1) + 1)
    bts = np.zeros((Bs, nbs), np.int32)
    for row, n in enumerate(-(-vls // bs)):
        bts[row, :n] = [next(free) for _ in range(n)]
    run("flash_decode paged, serving table", fd.flash_decode_paged,
        paged_ref, (randn((Bs, H, d)), randn((Ns, K, bs, d)),
                    randn((Ns, K, bs, d)), jnp.asarray(bts),
                    jnp.asarray(vls)),
        ("flash_decode_paged",), (TOL_DECODE,))

    # ... and with idle rows, the first, every third and the last, their
    # table all sink: of length 1 (what the server hands the kernel for
    # an idle slot: one token of the sink page, a successor that skips
    # the branch round the lead's shares) and of length 0 (fetches no
    # page, hands the scratch's halves on and writes a finite 0)
    idle = np.arange(Bs) % 3 == 0
    idle[-1] = True
    short = np.where(np.arange(Bs) % 2 == 0, 1, 0)
    run("flash_decode paged, serving table, empty rows",
        fd.flash_decode_paged,
        lambda q, kp, vp, bt, n: jnp.where(
            (n > 0)[:, None, None], paged_ref(q, kp, vp, bt, n), 0),
        (randn((Bs, H, d)), randn((Ns, K, bs, d)), randn((Ns, K, bs, d)),
         jnp.asarray(np.where(idle[:, None], 0, bts)),
         jnp.asarray(np.where(idle, short, vls))),
        ("flash_decode_paged",), (TOL_DECODE,))

    pools8 = [to_pool(c) for c in (k8, ks, v8, vs)]
    run("flash_decode paged int8", fd.flash_decode_paged_quantized,
        lambda q, k8, ks, v8, vs, bt, n: fd.reference_decode_attention(
            q.astype(f32),
            fd.dequantize_kv(fd.gather_kv_pages(k8, bt),
                             fd.gather_kv_pages(ks, bt), f32),
            fd.dequantize_kv(fd.gather_kv_pages(v8, bt),
                             fd.gather_kv_pages(vs, bt), f32), n),
        (qd, *pools8, bt, vl), ("flash_decode_paged_q8",), (TOL_DECODE,))

    W = size.window
    qw = randn((B, W, H, d))
    vlw = jnp.maximum(vl[:, None] - (W - 1) + jnp.arange(W)[None, :], 1)
    run("flash_decode paged window", fd.flash_decode_paged_window,
        lambda q, kp, vp, bt, n: fd.reference_paged_window_attention(
            q.astype(f32), fd.gather_kv_pages(kp, bt).astype(f32),
            fd.gather_kv_pages(vp, bt).astype(f32), n),
        (qw, kp, vp, bt, vlw), ("flash_decode_paged_window",),
        (TOL_DECODE,))

    # the windowed variants a sliding-window layer runs: the forward
    # kernel masks and skips the key blocks behind the window (prefill;
    # its backward kernels refuse a window), the paged sweep starts at
    # the first page inside it (decode)
    win = size.sliding_window
    run("flash_attention sliding window fwd",
        lambda q, k, v: fa.flash_attention_raw(q, k, v, window=win),
        lambda q, k, v: fa.reference_attention(*up(q, k, v), window=win),
        (q, k, v), ("flash_attention_fwd",), (TOL_ATTN,))

    def paged_window(w):
        return (lambda q, kp, vp, bt, n: fd.flash_decode_paged(
                    q, kp, vp, bt, n, window=w),
                lambda q, kp, vp, bt, n: fd.reference_decode_attention(
                    q.astype(f32), fd.gather_kv_pages(kp, bt).astype(f32),
                    fd.gather_kv_pages(vp, bt).astype(f32), n, window=w))

    run("flash_decode paged, sliding window", *paged_window(win),
        (qd, kp, vp, bt, vl), ("flash_decode_paged",), (TOL_DECODE,))
    run("flash_decode paged, sliding window, serving table",
        *paged_window(size.serve_window),
        (randn((Bs, H, d)), randn((Ns, K, bs, d)), randn((Ns, K, bs, d)),
         jnp.asarray(bts), jnp.asarray(vls)),
        ("flash_decode_paged",), (TOL_DECODE,))

    # the latent (MLA) sweep at the serving table: ONE pool of rows that
    # all 64 heads share, read whole as keys and by their first 512 as
    # values (sarvam_105b's widths: 512 latent + 64 rotated key + 64 zeros)
    run("flash_decode paged latent, serving table",
        lambda q, p, bt, n: fd.flash_decode_paged_latent(
            q, p, bt, n, latent=512, scale=0.1352),
        lambda q, p, bt, n: fd.reference_paged_latent_attention(
            q.astype(f32), p.astype(f32), bt, n, 512, 0.1352),
        (randn((Bs, 64, 640)), randn((Ns, 1, bs, 640), scale=0.5),
         jnp.asarray(bts), jnp.asarray(vls)),
        ("flash_decode_paged_latent",), (TOL_DECODE,))

    # the held experts' grouped matmul: a decode tick's few rows (most
    # held experts empty) and a prefill's many, routed a chunk at a time
    E, n, width, top_k = size.experts
    rw, rb = randn((E, size.hidden), scale=INIT_STD), \
        randn((E,), f32, scale=0.02)
    eg, eu = (randn((n, size.hidden, width), scale=INIT_STD)
              for _ in range(2))
    ed = randn((n, width, size.hidden), scale=INIT_STD)

    def experts_ref(x, rw, rb, eg, eu, ed):
        """Every held expert on every row, masked: the definition."""
        sel, wt = moe.route_top_k(x, rw, rb, top_k, 2.448)
        x, eg, eu, ed = up(x, eg, eu, ed)
        y = jnp.einsum("tni,nid->tnd",
                       jax.nn.silu(jnp.einsum("td,ndi->tni", x, eg))
                       * jnp.einsum("td,ndi->tni", x, eu), ed)
        on = jnp.sum(jnp.where(sel[:, :, None] == jnp.arange(n),
                               wt[:, :, None], 0.0), axis=1)   # (T, n)
        return jnp.einsum("tn,tnd->td", on, y)

    for rows_, label in ((size.slots, "decode rows"),
                         (2 * size.seq, "prefill rows")):
        run(f"moe_grouped_matmul held experts, {label}",
            lambda x, *w_: moe.held_expert_ffn(
                x, *w_, lo=0, top_k=top_k, route=moe.route_top_k,
                route_scale=2.448)[0],
            experts_ref, (randn((rows_, size.hidden)), rw, rb, eg, eu, ed),
            ("moe_grouped_matmul",), (TOL_ATTN,))

    # the state-space kernels: the scan over a prompt from a nonzero
    # state (three time chunks, dt = 0 on a padded tail) and one decode
    # step of every row, two rows idle
    Dn, Ns_, Ts = 2 * size.hidden, 16, 3 * 256 - 40
    a_log = jnp.log(jnp.broadcast_to(
        jnp.arange(1, Ns_ + 1, dtype=f32)[:, None], (Ns_, Dn)))
    xs, bs_, cs = randn((1, Ts, Dn), f32), randn((1, Ts, Ns_), f32), \
        randn((1, Ts, Ns_), f32)
    dts = jnp.where(jnp.arange(Ts)[None, :, None] < Ts - 9,
                    jax.nn.softplus(randn((1, Ts, Dn), f32) - 3.0), 0.0)
    h0 = randn((1,) + ssm.state_shape(Ns_, Dn), f32)
    run("selective_scan prompt", ssm.selective_scan,
        ssm.selective_scan_ref, (xs, dts, a_log, bs_, cs, h0),
        ("selective_scan_fwd",), (TOL_SCAN, TOL_SCAN))
    # one decode step of every row, a row in four idle: the whole of a
    # recurrent layer between in_proj and out_proj in one call (the
    # convolution, x_proj, Jamba's three norms, dt_proj, softplus, the
    # recurrence, the gate), both pools in place, against its jnp twin,
    # which rounds x_proj's and dt_proj's products, B and C to bf16
    # where the call keeps float32: g and h' to the matmuls' tolerance,
    # the tail to the bit
    Kc, Rk = 4, Dn // 32
    lw = {"conv_w": randn((Kc, Dn), f32, 0.5), "conv_b": randn((Dn,), f32, 0.1),
          "x_proj": randn((Rk + 2 * Ns_, Dn), scale=Dn ** -0.5),
          "dt_norm": randn((Rk,), scale=0.1) + 1,
          "b_norm": randn((Ns_,), scale=0.1) + 1,
          "c_norm": randn((Ns_,), scale=0.1) + 1,
          "dt_proj": randn((Dn, Rk), scale=Rk ** -0.5),
          "dt_bias": randn((Dn,), f32) - 3.0, "A_log": a_log,
          "D": randn((Dn,), f32)}
    hr = randn((B,) + ssm.state_shape(Ns_, Dn), f32)
    live = jnp.arange(B) % 4 != 1
    run("ssm_state_update decode rows",
        lambda h, t, xz: ssm.ssm_state_update(h, t, xz, live, lw, 1e-6),
        lambda h, t, xz: ssm.ssm_state_update_ref(h, t, xz, live, lw, 1e-6),
        (hr, randn((B,) + ssm.tail_shape(Kc, Dn)), randn((B, 2 * Dn))),
        ("ssm_state_update",), (TOL_ATTN, TOL_ATTN, 0.0))

    # the power-retention kernels (models/brumby.py's layer, 5 query
    # heads a kv head): the chunked form over a prompt of three chunks
    # with a ragged tail (zero keys and a gate of 1 on the padding),
    # q and k normed as the model's, gates of a 64-8,192 horizon; then
    # one decode step of every row from that state, a row in four idle
    def unit(x):
        return (x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True))
                ).astype(dt)

    Tr, Kr = 3 * 256 - 40, 2
    on = (jnp.arange(Tr) < Tr - 9)[None, :, None]
    qr, kr = unit(randn((1, Tr, 5 * Kr, d), f32)), \
        unit(randn((1, Tr, Kr, d), f32)) * on[..., None].astype(dt)
    vr = randn((1, Tr, Kr, d))
    horizon = jnp.exp(jax.random.uniform(next(key), (1, 1, Kr), f32,
                                         math.log(64), math.log(8192)))
    lgr = jnp.where(on, jnp.log1p(-1.0 / horizon), 0.0)
    run("power_retention chunked prompt", pret.power_retention_chunked,
        lambda *a: pret.power_retention_chunked_ref(*a),
        (qr, kr, vr, lgr), ("power_retention_chunked",),
        (TOL_ATTN, TOL_ATTN, TOL_ATTN))
    st = pret.power_retention_chunked_ref(qr, kr, vr, lgr)[1]
    Sr, zr = (jnp.broadcast_to(st[n_], (B,) + st[n_].shape[1:])
              for n_ in ("S", "z"))
    run("power_retention step decode rows",
        lambda S_, z_, *a: pret.power_retention_step(S_, z_, *a, live),
        lambda S_, z_, *a: pret.power_retention_step_ref(S_, z_, *a,
                                                         live),
        (Sr, zr, unit(randn((B, 5 * Kr, d), f32)),
         unit(randn((B, Kr, d), f32)), randn((B, Kr, d)),
         jnp.broadcast_to(lgr[0, 0], (B, Kr))),
        ("power_retention_step",), (TOL_SCAN, TOL_SCAN, TOL_ATTN))

    # decode and prefill attention at 20 query heads on ONE kv head: a
    # group that is no multiple of 8 sublanes, pages of 4 KB
    q20 = randn((Bs, 20, d))
    run("flash_decode paged, 20 heads on 1 kv head, serving table",
        fd.flash_decode_paged, paged_ref,
        (q20, randn((Ns, 1, bs, d)), randn((Ns, 1, bs, d)),
         jnp.asarray(bts), jnp.asarray(vls)),
        ("flash_decode_paged",), (TOL_DECODE,))
    run("flash_attention causal, 20 heads on 1 kv head, fwd",
        lambda q, k, v: fa.flash_attention_raw(q, k, v, causal=True),
        lambda q, k, v: fa.reference_attention(*up(q, k, v), causal=True),
        (randn((1, T, 20, d)), randn((1, T, 1, d)), randn((1, T, 1, d))),
        ("flash_attention_fwd",), (TOL_ATTN,))

    check_no_fallbacks("kernels", fallbacks0)


# -- phase: train ---------------------------------------------------------

def build_net(size):
    import mxnet_tpu as mx

    mx.random.seed(0)
    net = mx.models.get_model(
        "llama_3_8b", vocab_size=size.vocab, hidden_size=size.hidden,
        intermediate_size=size.ffn, num_layers=size.layers,
        num_heads=size.heads, num_kv_heads=size.kv_heads,
        max_seq_len=max(size.seq, size.max_len), dtype=size.dtype)
    net.initialize(init=mx.init.Normal(INIT_STD))
    return net


def train_batch(size):
    import mxnet_tpu as mx

    tok = np.random.RandomState(0).randint(
        0, size.vocab, (size.batch, size.seq + 1))
    return (mx.nd.array(tok[:, :-1], dtype="int32"),
            mx.nd.array(tok[:, 1:], dtype="int32"))


def lower_step(size, net, plan):
    """The examples/llama_train.py path: net + loss + AdamW through a
    ParallelPlan into one compiled step."""
    import mxnet_tpu as mx

    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    def lm_loss(logits, labels):
        return ce(logits.reshape(-1, size.vocab), labels.reshape(-1))

    return plan.lower(net, lm_loss,
                      mx.optimizer.AdamW(learning_rate=3e-4, wd=0.1))


def run_steps(size, step, meter, label):
    """`size.steps` steps on the one batch; returns the losses. Exactly
    one compile, in the first step."""
    from mxnet_tpu import tracing

    x, y = train_batch(size)
    tracing.reset_cache_stats()
    losses = []
    for i in range(size.steps):
        mark = meter.mark()
        losses.append(float(step(x, y).asscalar()))
        built = meter.since(mark)
        if i == 0:
            say(label, first_step=built)
        else:
            check(built["builds"] == 0,
                  f"step {i + 1} built {built['builds']} executable(s)")
    stats = tracing.cache_stats()["per_block"]["fused_step"]
    check(stats["compiles"] == 1 and stats["hits"] == size.steps - 1,
          f"fused_step compile accounting: {stats}")
    check(all(math.isfinite(v) for v in losses),
          f"non-finite loss: {losses}")
    say(label, losses=[round(v, 4) for v in losses],
        compiles=stats["compiles"], hits=stats["hits"])
    return losses


def phase_train(size, meter):
    """Five steps of the fused train step on one device; returns the
    trained net and the first loss (the multichip legs must reproduce
    it)."""
    import jax

    from mxnet_tpu.kernels import dispatch
    from mxnet_tpu.parallel.plan import ParallelPlan

    fallbacks0 = dispatch.fallback_counts()
    net = build_net(size)
    step = lower_step(size, net, ParallelPlan(dp=1))
    losses = run_steps(size, step, meter, "train")

    want = math.log(size.vocab) + size.hidden * INIT_STD ** 2 / 2
    check(abs(losses[0] - want) < 0.5,
          f"first loss {losses[0]:.3f}, expected about {want:.3f} "
          f"(ln {size.vocab} + logit variance / 2)")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")

    if size.compiled:
        # a gate that returns None is not an exception: count the
        # kernels in the module the step actually lowers
        names = kernel_names(step.lower(*train_batch(size)).as_text())
        L = size.layers
        # flash attention is a jit of its own: the layers share ONE
        # lowered function and call it L times
        want_names = {"flash_attention_fwd": 1,
                      "flash_attention_dkv": 1, "rmsnorm_fwd": 2 * L + 1,
                      "rmsnorm_bwd": 2 * L + 1, "softmax_ce_fwd": 1,
                      "softmax_ce_bwd": 1}
        check(dict(names) == want_names,
              f"Pallas calls in the train step: {dict(names)}, "
              f"expected {want_names}")
        say("train", pallas_calls=dict(names))
    check_no_fallbacks("train", fallbacks0)
    say("train", peak_bytes_in_use=peak_bytes(jax.devices()[:1]))

    step.sync_to_params()
    return net, losses[0]


# -- phase: serve ---------------------------------------------------------

def reference_logits(net, tokens, positions):
    """Float32 logits at `positions` of one sequence: the decoder's
    math spelled in plain jax.numpy from the repo's reference pieces
    (no kernel dispatch), on the host CPU at "highest"."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels.flash_attention import reference_attention
    from mxnet_tpu.models import llama_math
    from mxnet_tpu.models.llama_infer import _params_tree

    cfg = net.model.cfg
    cpu = jax.local_devices(backend="cpu")[0]
    params = jax.device_put(
        jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                               _params_tree(net)), cpu)

    def rms(x, g):
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + cfg.rms_eps) * g

    def forward(params, ids, positions):
        H, K, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        T = ids.shape[1]
        pos = jnp.arange(T)
        x = params["embed"][ids]
        for lp in params["layers"]:
            h = rms(x, lp["ln1"])
            q = llama_math.rope_at((h @ lp["wq"].T).reshape(1, T, H, d),
                                   pos, cfg.rope_base)
            k = llama_math.rope_at((h @ lp["wk"].T).reshape(1, T, K, d),
                                   pos, cfg.rope_base)
            v = (h @ lp["wv"].T).reshape(1, T, K, d)
            att = reference_attention(q, k, v, causal=True)
            x = x + att.reshape(1, T, H * d) @ lp["wo"].T
            x = x + llama_math.swiglu(rms(x, lp["ln2"]), lp["gate"],
                                      lp["up"], lp["down"])
        return rms(x[0, positions], params["norm"]) @ params["head"].T

    with jax.default_matmul_precision("highest"):
        ids = jax.device_put(np.asarray(tokens, np.int32)[None, :], cpu)
        out = jax.jit(forward)(
            params, ids, jax.device_put(np.asarray(positions), cpu))
    return np.asarray(out)


def phase_serve(size, net, meter):
    """Two waves through InferenceServer (the examples/llama_serve.py
    path) on the net the train phase left behind."""
    import jax

    from mxnet_tpu.kernels import dispatch
    from mxnet_tpu.serving import InferenceServer

    fallbacks0 = dispatch.fallback_counts()
    server = InferenceServer(net, batch_slots=size.slots,
                             max_len=size.max_len, block_size=size.block,
                             max_prompt_len=size.max_prompt)
    check(server._kernel_paged,
          "decode would gather the page pool: the in-kernel paged gate "
          "refused this pool shape")
    rs = np.random.RandomState(3)

    def submit(wave):
        return [(new, server.submit(rs.randint(0, size.vocab, n), new,
                                    temperature=temp,
                                    top_k=50 if temp else 0,
                                    top_p=0.9 if temp else 0.0,
                                    seed=i))
                for i, (n, new, temp) in enumerate(wave)]

    # the probe for the logits check, ahead of wave 1: a request of ONE
    # token, so that the server launches one decode tick for it and no
    # second behind it. Admit it alone (the prefill), read the row, run
    # the tick (the first decode step), read the row again. Logits, not
    # tokens: with random weights the argmax is a coin toss.
    mark = meter.mark()
    n0 = size.waves[0][0][0]
    probe = server.submit(rs.randint(0, size.vocab, n0), 1)
    pool0 = server.cache.pages[0]["k"]
    server._admit()
    slot = server._slot_req.index(probe)
    prefill_row = np.asarray(server._last_logits[slot], np.float32)
    check(pool0.is_deleted(),
          "the page pool was not donated to the prefill executable")
    server.step()
    decode_row = np.asarray(server._last_logits[slot], np.float32)
    t0 = probe.output_tokens[0]

    wave1 = [(1, probe)] + submit(size.waves[0])
    server.run()
    built1 = meter.since(mark)
    cs1 = server.compile_stats()
    check(cs1["prefill_compiles"] == 1 and cs1["decode_compiles"] == 1,
          f"wave 1 compiles: {cs1}")

    mark = meter.mark()
    wave2 = submit(size.waves[1])
    server.run()
    built2 = meter.since(mark)
    cs2 = server.compile_stats()
    check(cs2["prefill_compiles"] == 1 and cs2["decode_compiles"] == 1
          and built2["builds"] == 0,
          f"wave 2 compiled something: {cs2}, {built2}")
    for new, r in wave1 + wave2:
        check(r.status == "ok" and len(r.output_tokens) == new,
              f"{r!r}: status {r.status}, {len(r.output_tokens)} of "
              f"{new} tokens")
    check(server.cache.num_used_blocks == 0, "KV blocks leaked")
    say("serve", requests=len(wave1) + len(wave2),
        tokens=server.tokens_generated, wave1=built1, wave2=built2,
        prefill_compiles=cs2["prefill_compiles"],
        decode_compiles=cs2["decode_compiles"],
        kernel_paged=server._kernel_paged, pools_donated=True)

    want = reference_logits(net, list(probe.prompt) + [t0], [n0 - 1, n0])
    spread = float(np.std(want))
    for name, row, ref in (("prefill", prefill_row, want[0]),
                           ("first decode", decode_row, want[1])):
        check(np.isfinite(row).all() and row.shape == ref.shape,
              f"{name} logits: not finite or wrong shape")
        err = float(np.max(np.abs(row - ref))) / spread
        check(err <= TOL_LOGITS,
              f"{name} logits off the float32 reference by "
              f"{err:.3f} x std, tolerance {TOL_LOGITS}")
        say("serve", logits=name, max_err_over_std=f"{err:.3f}")

    check_no_fallbacks("serve", fallbacks0)
    say("serve", peak_bytes_in_use=peak_bytes(jax.devices()[:1]))


# -- phase: multichip -----------------------------------------------------

def phase_multichip(size, first_loss, meter):
    """The train phase again on four chips: GSPMD data parallelism, the
    ZeRO-1 shard_map step, and dp x tp. Same global batch, so the same
    first loss; every device holds bytes; the Pallas calls work on the
    per-device batch."""
    import jax

    from mxnet_tpu.parallel.plan import ParallelPlan

    n = jax.device_count()
    if n < 4:
        print(f"[multichip] skipped: {n} device(s)", flush=True)
        return

    for plan in (ParallelPlan(dp=4), ParallelPlan(dp=4, zero=1),
                 ParallelPlan(dp=2, tp=2)):
        label = f"multichip dp={plan.dp} tp={plan.tp} zero={plan.zero}"
        net = build_net(size)
        step = lower_step(size, net, plan)
        losses = run_steps(size, step, meter, label)
        check(abs(losses[0] - first_loss) <= TOL_LOSS_PARALLEL,
              f"{label}: first loss {losses[0]:.4f} vs one chip "
              f"{first_loss:.4f}")
        check(losses[-1] < losses[0], f"{label}: loss did not fall")

        devs = list(step.mesh.devices.flat)
        held = {d.id: sum(s.data.nbytes
                          for a in jax.tree_util.tree_leaves(
                              (step._tr, step._states))
                          for s in a.addressable_shards
                          if s.device == d) for d in devs}
        check(len(devs) == 4 and all(held.values()),
              f"{label}: bytes held per device {held}")

        if size.compiled:
            # after partitioning, each kernel's custom call must see
            # the per-device batch (heads too, under tp) — an
            # all-gathered operand would show the global one
            hlo = step.lower(*train_batch(size)).compile().as_text()
            shapes = re.findall(
                r"%\w*flash_attention_fwd[\w.]* = \(\w+\[([\d,]+)\]",
                hlo)
            # the kernels work on the model's (B, T, H * d) layout
            want = f"{size.batch // plan.dp},{size.seq}," \
                   f"{size.heads // plan.tp * size.head_dim}"
            check(shapes and all(s == want for s in shapes),
                  f"{label}: flash_attention_fwd works on {shapes}, "
                  f"expected [{want}] per device")
            rows = re.findall(
                r"%\w*softmax_ce_fwd[\w.]* = \(\w+\[(\d+),1\]", hlo)
            want_rows = str(size.batch // plan.dp * size.seq)
            check(rows and all(r == want_rows for r in rows),
                  f"{label}: softmax_ce_fwd works on {rows} rows, "
                  f"expected {want_rows} per device")
            say(label, flash_attention_fwd_block=shapes[0],
                softmax_ce_rows=rows[0])
        say(label, bytes_held=held, peak_bytes_in_use=peak_bytes(devs))
        del step, net
        gc.collect()


# -- driver ---------------------------------------------------------------

def run(size, phases=("device", "kernels", "train", "serve",
                      "multichip")):
    """Run `phases` in order at `size`; returns the device description
    the final JSON line carries."""
    import jax

    from mxnet_tpu import tracing
    from mxnet_tpu.runtime import build as runtime_build

    cache_dir = tracing.enable_compile_cache()
    warm = os.path.isdir(cache_dir) and any(os.scandir(cache_dir))
    say("setup", compile_cache=cache_dir, cache_was_warm=warm,
        JAX_COMPILATION_CACHE_DIR=os.environ.get(
            "JAX_COMPILATION_CACHE_DIR", "unset"),
        # looked up, never built here (that would start a compiler
        # child); nothing on this path depends on which it is
        native_runtime="prebuilt .so" if runtime_build.build(
            build_if_missing=False) else "python fallback")
    meter = CompileMeter()
    t_all = time.perf_counter()
    net = first_loss = None
    for phase in phases:
        t0 = time.perf_counter()
        mark = meter.mark()
        if phase == "device":
            phase_device(size)
        elif phase == "kernels":
            phase_kernels(size)
        elif phase == "train":
            net, first_loss = phase_train(size, meter)
        elif phase == "serve":
            phase_serve(size, net, meter)
        elif phase == "multichip":
            net = None          # device 0 needs the room
            gc.collect()
            phase_multichip(size, first_loss, meter)
        else:
            raise ValueError(f"unknown phase {phase!r}")
        say(phase, ok=True, wall_s=round(time.perf_counter() - t0, 1),
            **meter.since(mark))
    dev = jax.devices()[0]
    say("summary", phases=list(phases), cache_was_warm=warm,
        wall_s=round(time.perf_counter() - t_all, 1),
        **meter.since((0, 0, 0.0)),
        peak_bytes_in_use=peak_bytes(jax.devices()))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main():
    if len(sys.argv) > 1:
        raise SystemExit("chip_smoke.py takes no arguments")
    device = run(FULL)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
