"""Fused normalization Pallas kernels (RMSNorm / LayerNorm).

Reference analogue: the fork's fused layer-norm CUDA kernels
(src/operator/nn/layer_norm.cu vectorized/fused paths). TPU-first: one
VMEM pass per row block computes the moments and applies scale/shift —
no separate mean/var/normalize kernels, no fp32 round trips to HBM.
Forward saves only the per-row statistics; the backward recomputes
x_hat from the saved stats in a second fused kernel (dgamma/dbeta are
cross-row sums XLA handles well in jnp).

Layout: (..., d) — normalization over the trailing axis. Kernels grid
over row blocks with the full feature dim resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from .dispatch import KernelFallback, kernel_mode, per_shard

__all__ = ["fused_rmsnorm", "fused_layernorm"]

#: under a mesh the leading (batch) dim splits over dp; the feature dim
#: stays whole — the kernels reduce over it
_ROWS = P("dp")

_fallback = KernelFallback("fused-norm", "NORM")


# block sizing/padding shared across kernel families (dispatch.py):
# tuned row target + VMEM byte budget (kernels/tuning.py; autotuned by
# benchmarks/autotune_kernels.py), power-of-two rows, 8-sublane minimum
from . import tuning as _tuning  # noqa: E402
from .dispatch import pad_rows as _pad_rows  # noqa: E402
from .dispatch import pick_rows as _pick_rows_raw  # noqa: E402


def _pick_rows(n, d, itemsize=4):
    """Rows per block for (n, d) activations of `itemsize` bytes. The
    budget (tuning: fused_norm.vmem_budget_bytes) covers the backward's
    whole working set — x, dy and dx blocks double-buffered in their
    own dtype plus about three fp32 temporaries — and is HALF of
    Mosaic's 16 MiB scoped limit on purpose: inside a large step XLA
    parks the kernel's (N, 1) statistics operand in VMEM too,
    lane-padded 128-fold (4 MiB at 8192 rows), against the same limit.
    (Compiled for v5e inside the Llama step, 256-row bf16 blocks of
    width 4096 asked for 18.95 MiB.)"""
    return _pick_rows_raw(
        n, d * (6 * itemsize + 12),
        want=_tuning.get("fused_norm", "row_block_want"),
        budget_bytes=_tuning.get("fused_norm", "vmem_budget_bytes"))


# ---------------------------------------------------------------- RMSNorm

def _rms_fwd_kernel(eps, x_ref, g_ref, o_ref, rrms_ref):
    x = x_ref[...].astype(jnp.float32)            # (rows, d)
    ms = jnp.mean(x * x, axis=-1)
    rrms = jax.lax.rsqrt(ms + eps)                # (rows,)
    o_ref[...] = (x * rrms[:, None] *
                  g_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
    # stats live as (rows, 1): Mosaic rejects rank-1 blocks that do not
    # span the whole array
    rrms_ref[...] = rrms[:, None]


def _rms_bwd_kernel(eps, x_ref, g_ref, rrms_ref, dy_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    rrms = rrms_ref[...].astype(jnp.float32)      # (rows, 1)
    dy = dy_ref[...].astype(jnp.float32)
    d = x.shape[-1]
    wdy = dy * g
    # dx = rrms * (wdy - x * mean(wdy * x) * rrms^2)
    corr = jnp.mean(wdy * x, axis=-1, keepdims=True) * rrms * rrms
    dx_ref[...] = (rrms * (wdy - x * corr)).astype(dx_ref.dtype)


def _rms_pallas_fwd(x2, g, eps, interpret):
    from jax.experimental import pallas as pl
    n, d = x2.shape
    rows = _pick_rows(n, d, x2.dtype.itemsize)
    x2p = _pad_rows(x2, rows)
    np_ = x2p.shape[0]
    grid = (np_ // rows,)
    out, rrms = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((np_, d), x2.dtype),
                   jax.ShapeDtypeStruct((np_, 1), jnp.float32)],
        interpret=interpret,
        name="rmsnorm_fwd",
    )(x2p, g)
    return out[:n], rrms[:n, 0]


def _rms_pallas_dx(x2, g, rrms, dy2, eps, interpret):
    from jax.experimental import pallas as pl
    n, d = x2.shape
    rows = _pick_rows(n, d, x2.dtype.itemsize)
    x2p = _pad_rows(x2, rows)
    rrmsp = _pad_rows(rrms[:, None], rows)
    dy2p = _pad_rows(dy2, rows)
    np_ = x2p.shape[0]
    grid = (np_ // rows,)
    dx = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, eps),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((rows, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, d), x2.dtype),
        interpret=interpret,
        name="rmsnorm_bwd",
    )(x2p, g, rrmsp, dy2p)
    return dx[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms(x2, g, eps, interpret):
    out, _ = _rms_fwd(x2, g, eps, interpret)
    return out


def _rms_fwd(x2, g, eps, interpret):
    out, rrms = _rms_pallas_fwd(x2, g, eps, interpret)
    return out, (x2, g, rrms)


def _rms_bwd(eps, interpret, res, dy2):
    x2, g, rrms = res
    dx = _rms_pallas_dx(x2, g, rrms, dy2.astype(x2.dtype), eps,
                        interpret)
    xhat = x2.astype(jnp.float32) * rrms[:, None]
    dg = jnp.sum(dy2.astype(jnp.float32) * xhat, axis=0).astype(g.dtype)
    return dx, dg


_rms.defvjp(_rms_fwd, _rms_bwd)


def fused_rmsnorm(x, gamma, eps=1e-6):
    """RMSNorm over the trailing axis; Pallas on TPU, jnp elsewhere."""
    def twin():
        xs = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xs), axis=-1, keepdims=True)
        return (xs * jax.lax.rsqrt(ms + eps) *
                gamma.astype(jnp.float32)).astype(x.dtype)

    return _fallback.run(
        kernel_mode("NORM", x),
        lambda interpret: per_shard(
            lambda x_, g_: _rms(x_.reshape(-1, x_.shape[-1]), g_, eps,
                                interpret).reshape(x_.shape),
            (x, gamma), (_ROWS, P())),
        twin)


# -------------------------------------------------------------- LayerNorm

def _ln_fwd_kernel(eps, x_ref, g_ref, b_ref, o_ref, mu_ref, rstd_ref):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1)
    xc = x - mu[:, None]
    var = jnp.mean(xc * xc, axis=-1)
    rstd = jax.lax.rsqrt(var + eps)
    o_ref[...] = (xc * rstd[:, None] * g_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
    mu_ref[...] = mu[:, None]
    rstd_ref[...] = rstd[:, None]


def _ln_bwd_kernel(eps, x_ref, g_ref, mu_ref, rstd_ref, dy_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)       # (rows, 1)
    rstd = rstd_ref[...].astype(jnp.float32)   # (rows, 1)
    dy = dy_ref[...].astype(jnp.float32)
    xhat = (x - mu) * rstd
    wdy = dy * g
    # dx = rstd * (wdy - mean(wdy) - xhat * mean(wdy * xhat))
    m1 = jnp.mean(wdy, axis=-1, keepdims=True)
    m2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (wdy - m1 - xhat * m2)).astype(dx_ref.dtype)


def _ln_pallas_fwd(x2, g, b, eps, interpret):
    from jax.experimental import pallas as pl
    n, d = x2.shape
    rows = _pick_rows(n, d, x2.dtype.itemsize)
    x2p = _pad_rows(x2, rows)
    np_ = x2p.shape[0]
    grid = (np_ // rows,)
    out, mu, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((np_, d), x2.dtype),
                   jax.ShapeDtypeStruct((np_, 1), jnp.float32),
                   jax.ShapeDtypeStruct((np_, 1), jnp.float32)],
        interpret=interpret,
        name="layernorm_fwd",
    )(x2p, g, b)
    return out[:n], mu[:n, 0], rstd[:n, 0]


def _ln_pallas_dx(x2, g, mu, rstd, dy2, eps, interpret):
    from jax.experimental import pallas as pl
    n, d = x2.shape
    rows = _pick_rows(n, d, x2.dtype.itemsize)
    x2p = _pad_rows(x2, rows)
    mup = _pad_rows(mu[:, None], rows)
    rstdp = _pad_rows(rstd[:, None], rows)
    dy2p = _pad_rows(dy2, rows)
    np_ = x2p.shape[0]
    grid = (np_ // rows,)
    dx = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((rows, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, d), x2.dtype),
        interpret=interpret,
        name="layernorm_bwd",
    )(x2p, g, mup, rstdp, dy2p)
    return dx[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln(x2, g, b, eps, interpret):
    out, _ = _ln_fwd(x2, g, b, eps, interpret)
    return out


def _ln_fwd(x2, g, b, eps, interpret):
    out, mu, rstd = _ln_pallas_fwd(x2, g, b, eps, interpret)
    return out, (x2, g, mu, rstd)


def _ln_bwd(eps, interpret, res, dy2):
    x2, g, mu, rstd = res
    dx = _ln_pallas_dx(x2, g, mu, rstd, dy2.astype(x2.dtype), eps,
                       interpret)
    xhat = (x2.astype(jnp.float32) - mu[:, None]) * rstd[:, None]
    dyf = dy2.astype(jnp.float32)
    dg = jnp.sum(dyf * xhat, axis=0).astype(g.dtype)
    db = jnp.sum(dyf, axis=0).astype(g.dtype)
    return dx, dg, db


_ln.defvjp(_ln_fwd, _ln_bwd)


def fused_layernorm(x, gamma, beta, eps=1e-5):
    """LayerNorm over the trailing axis; Pallas on TPU, jnp elsewhere."""
    def twin():
        xs = x.astype(jnp.float32)
        mean = jnp.mean(xs, axis=-1, keepdims=True)
        var = jnp.var(xs, axis=-1, keepdims=True)
        return ((xs - mean) * jax.lax.rsqrt(var + eps)
                * gamma.astype(jnp.float32)
                + beta.astype(jnp.float32)).astype(x.dtype)

    return _fallback.run(
        kernel_mode("NORM", x),
        lambda interpret: per_shard(
            lambda x_, g_, b_: _ln(x_.reshape(-1, x_.shape[-1]), g_, b_,
                                   eps, interpret).reshape(x_.shape),
            (x, gamma, beta), (_ROWS, P(), P())),
        twin)
