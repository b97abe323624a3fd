"""Fused softmax cross-entropy (sparse labels) over a large vocab.

Reference analogue: the fork's fused softmax work — softmax_cross_entropy
(src/operator/loss/softmax_cross_entropy.cc) and the NVIDIA fork's
vectorized softmax CUDA kernels (src/operator/nn/softmax*) — the LM hot
path where the (N, V) logits dominate HBM traffic. TPU-first: a Pallas
kernel keeps one (rows, V) block resident in VMEM and produces per-row
loss + logsumexp in a single pass WITHOUT materializing the (N, V)
log-probabilities; the backward writes (softmax(x) - onehot) * dloss
straight from the saved stats — one read of the logits and one write of
the gradient, where the jnp path (log_softmax then pick then vjp)
round-trips the full matrix several times.

Layout: logits (N, V), labels (N,) int32. The vocab axis is padded to a
lane multiple (128) with the dtype's most-negative finite value (exp
underflows to exactly 0, so padding never contributes to the softmax);
rows are padded to the 8-sublane multiple and sliced off the outputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from . import tuning
from .dispatch import (KernelFallback, kernel_mode, pad_rows, per_shard,
                       pick_rows)

__all__ = ["fused_softmax_ce_raw", "reference_softmax_ce", "eligible"]

_fallback = KernelFallback("fused-ce", "CE")


#: Mosaic's default scoped-VMEM limit is 16 MiB per kernel. The backward
#: holds the logits block and the gradient block, each double-buffered
#: in the input dtype, plus about two fp32 temporaries of the block's
#: shape (compiled for v5e, a (32, 32000) fp32 block asked for 20.5 MiB),
#: so rows are sized for that whole working set against 12 MiB.
_VMEM_WORKING_SET_BYTES = 12 << 20


def _row_bytes(vocab, itemsize):
    return vocab * (4 * itemsize + 8)


def _worth_it(vocab, itemsize):
    """The kernel only pays off once the vocab is large enough that
    the jnp path's extra HBM round trips dominate (kernels/tuning.py:
    fused_ce.min_vocab), and it needs the 8-row minimum block of
    `itemsize`-byte logits to fit the working set (fp32: 64k columns,
    bf16: 96k)."""
    return (vocab >= tuning.get("fused_ce", "min_vocab")
            and 8 * _row_bytes(vocab, itemsize) <= _VMEM_WORKING_SET_BYTES)


def eligible(vocab: int, itemsize: int = 4) -> bool:
    """Whether a loss over `vocab` columns should take this module's
    entry at all (gluon.loss asks before it reshapes)."""
    return kernel_mode("CE", ok=_worth_it(vocab, itemsize)) is not None


def reference_softmax_ce(x2, lbl):
    """jnp path: -log_softmax(x)[label] per row; fp32 accumulation."""
    lp = jax.nn.log_softmax(x2.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(lp, lbl[:, None], axis=-1)[:, 0]


def _pick_rows(n, v, itemsize):
    return pick_rows(n, _row_bytes(v, itemsize),
                     want=tuning.get("fused_ce", "row_block_want"),
                     budget_bytes=_VMEM_WORKING_SET_BYTES)


def _pad_cols_neg(x2, mult=128):
    """Pad the vocab axis with the most-negative finite value: exp of
    (pad - lse) underflows to exactly 0, so the padding is invisible to
    both the softmax normalizer and the max."""
    pad = (-x2.shape[1]) % mult
    if pad:
        neg = jnp.finfo(x2.dtype).min
        x2 = jnp.concatenate(
            [x2, jnp.full((x2.shape[0], pad), neg, x2.dtype)], axis=1)
    return x2


def _ce_fwd_kernel(x_ref, lbl_ref, loss_ref, lse_ref):
    x = x_ref[...].astype(jnp.float32)            # (rows, Vp)
    lbl = lbl_ref[...]                            # (rows, 1) int32
    m = jnp.max(x, axis=-1)
    l = jnp.sum(jnp.exp(x - m[:, None]), axis=-1)
    lse = m + jnp.log(l)                          # (rows,)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    xl = jnp.sum(jnp.where(cols == lbl, x, 0.0), axis=-1)
    loss_ref[...] = (lse - xl)[:, None]
    lse_ref[...] = lse[:, None]


def _ce_bwd_kernel(x_ref, lbl_ref, lse_ref, dl_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)            # (rows, Vp)
    lse = lse_ref[...]                            # (rows, 1) f32
    dl = dl_ref[...].astype(jnp.float32)          # (rows, 1)
    lbl = lbl_ref[...]                            # (rows, 1) int32
    p = jnp.exp(x - lse)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = jnp.where(cols == lbl, 1.0, 0.0)
    dx_ref[...] = ((p - onehot) * dl).astype(dx_ref.dtype)


def _run_fwd(x2p, lbl2p, rows, interpret):
    from jax.experimental import pallas as pl

    np_, vp = x2p.shape
    grid = (np_ // rows,)
    return pl.pallas_call(
        _ce_fwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, vp), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        interpret=interpret,
        name="softmax_ce_fwd",
    )(x2p, lbl2p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ce_pallas(x2, lbl, interpret):
    loss, _ = _ce_pallas_fwd(x2, lbl, interpret)
    return loss


def _ce_pallas_fwd(x2, lbl, interpret):
    n, v = x2.shape
    rows = _pick_rows(n, v, x2.dtype.itemsize)
    x2p = _pad_cols_neg(pad_rows(x2, rows))
    lbl2p = pad_rows(lbl.astype(jnp.int32)[:, None], rows)
    loss, lse = _run_fwd(x2p, lbl2p, rows, interpret)
    return loss[:n, 0], (x2p, lbl2p, lse, n, v)


def _ce_pallas_bwd(interpret, res, g):
    from jax.experimental import pallas as pl

    x2p, lbl2p, lse, n, v = res
    np_, vp = x2p.shape
    rows = _pick_rows(np_, vp, x2p.dtype.itemsize)
    g2p = pad_rows(g.astype(jnp.float32)[:, None], rows)
    grid = (np_ // rows,)
    dx = pl.pallas_call(
        _ce_bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, vp), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, vp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, vp), x2p.dtype),
        interpret=interpret,
        name="softmax_ce_bwd",
    )(x2p, lbl2p, lse, g2p)
    import numpy as _np

    # integer labels: float0 cotangent (jax's convention)
    return dx[:n, :v], _np.zeros((n,), jax.dtypes.float0)


_ce_pallas.defvjp(_ce_pallas_fwd, _ce_pallas_bwd)


def fused_softmax_ce_raw(x2, lbl, use_fused=True):
    """Per-row sparse softmax cross-entropy: x2 (N, V) logits, lbl (N,)
    int labels -> (N,) fp32 loss. Pallas on TPU (vocab padded to lane
    multiples), jnp reference elsewhere; falls back loudly, never
    silently (dispatch.KernelFallback)."""
    lbl = lbl.astype(jnp.int32)
    # rows over dp; the vocab axis stays whole (the softmax reduces
    # over it), so tp-sharded logits gather first
    return _fallback.run(
        kernel_mode("CE", x2, ok=use_fused and _worth_it(
            x2.shape[1], x2.dtype.itemsize)),
        lambda interpret: per_shard(
            lambda x_, l_: _ce_pallas(x_, l_, interpret),
            (x2, lbl), (P("dp"), P("dp")), out_like=1),
        lambda: reference_softmax_ce(x2, lbl))
