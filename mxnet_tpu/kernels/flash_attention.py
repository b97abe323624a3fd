"""Causal (flash) attention.

Reference analogue: the fork's fused multi-head attention CUDA kernels
(interleaved_matmul_selfatt*, fmha). TPU-first: a Pallas kernel tiles
Q/K/V blocks through VMEM with an online-softmax accumulator; the jnp
reference path is used for backward (recompute) and on CPU.

Layout convention: (B, T, H, d) for q, (B, T, K, d) for k/v with GQA
(H % K == 0). Output (B, T, H, d).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from . import tuning
from .dispatch import KernelFallback

__all__ = ["flash_attention_raw", "reference_attention"]

#: fallback bookkeeping (FALLBACK_COUNT exposed via __getattr__ below)
_fallback = KernelFallback("flash-attention",
                           strict_envs=("MXNET_TPU_STRICT_FLASH",))


def __getattr__(name):
    if name == "FALLBACK_COUNT":
        return _fallback.count
    raise AttributeError(name)


def reference_attention(q, k, v, causal=True, scale=None,
                        lengths=None, window=None):
    """jnp reference: XLA fuses this into a few kernels; exact softmax.
    lengths (B,) masks key positions >= lengths[b] (BERT-style key
    padding). `window` (with causal) keeps keys j > i - window."""
    B, T, H, d = q.shape
    K = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    rep = H // K
    kf = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vf = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    # (B, H, T, T) scores in fp32 for stability
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((T, T), bool), -int(window))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    if lengths is not None:
        keep = jnp.arange(T)[None, :] < lengths[:, None]   # (B, S)
        s = jnp.where(keep[:, None, None, :], s, -jnp.inf)
    # rows with no valid keys (query beyond lengths) -> zero output
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isfinite(jnp.max(s, axis=-1, keepdims=True)),
                  p, 0.0)
    out = jnp.einsum("bhts,bshd->bthd", p.astype(vf.dtype), vf)
    return out.astype(q.dtype)


def _pick_block(T, want):
    """Largest block <= want that divides T (the grid uses exact
    tiling; a non-divisor block would leave tail rows unwritten)."""
    b = max(1, min(want, T))
    while T % b:
        b //= 2
    return b


def _mask_causal(s, qi, ki, block_q, block_k):
    """-inf upper-triangle mask for score block (qi, ki)."""
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(qpos >= kpos, s, -jnp.inf)


def _mask_lengths(s, ki, block_k, len_b):
    """-inf for key positions >= len_b in score block column ki."""
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    return jnp.where(kpos < len_b, s, -jnp.inf)


def _mask_window(s, qi, ki, block_q, block_k, window):
    """-inf for keys at or beyond `window` positions behind the query
    in score block (qi, ki)."""
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(qpos - kpos < window, s, -jnp.inf)


def _pallas_forward(q, k, v, causal, scale, block_q=None, block_k=None,
                    interpret=False, return_lse=False, lengths=None,
                    window=None):
    has_len = lengths is not None
    plat = "cpu" if interpret else "tpu"
    if block_q is None:
        block_q = tuning.get("flash_attention", "block_q", plat)
    if block_k is None:
        block_k = tuning.get("flash_attention", "block_k", plat)
    """Online-softmax flash forward in Pallas (TPU; interpret=True runs
    the same kernel under the Pallas interpreter for CPU testing).

    Internally the kernel works on (B, H, T, d) — Mosaic requires the
    LAST TWO block dims be (8k, 128k) or span the array, which the
    public (B, T, H, d) layout cannot satisfy when blocking one head.
    Per-row log-sum-exp travels as (B, H, T, 1) for the same reason and
    is returned squeezed to (B, H, T) when return_lse=True."""
    from jax.experimental import pallas as pl

    B, T, H, d = q.shape
    Kh = k.shape[2]
    rep = H // Kh
    block_q = _pick_block(T, block_q)
    block_k = _pick_block(T, block_k)
    n_q = T // block_q

    def kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, lse_ref):
        # grid: (B, H, n_q). Block of Q rows vs full K/V sweep.
        qi = pl.program_id(2)
        len_b = lens_ref[pl.program_id(0)]
        qblk = q_ref[...].astype(jnp.float32) * scale  # (block_q, d)
        m = jnp.full((block_q,), -jnp.inf, jnp.float32)
        l = jnp.zeros((block_q,), jnp.float32)
        acc = jnp.zeros((block_q, d), jnp.float32)
        n_k = T // block_k

        def body(ki, carry):
            m_, l_, acc_ = carry
            kblk = k_ref[pl.dslice(ki * block_k, block_k), :] \
                .astype(jnp.float32)
            vblk = v_ref[pl.dslice(ki * block_k, block_k), :] \
                .astype(jnp.float32)
            s = qblk @ kblk.T  # (block_q, block_k)
            if causal:
                s = _mask_causal(s, qi, ki, block_q, block_k)
            if window is not None:
                s = _mask_window(s, qi, ki, block_q, block_k, window)
            if has_len:
                s = _mask_lengths(s, ki, block_k, len_b)
            m_new = jnp.maximum(m_, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            # "row has seen a valid key" == running max left -inf; spelled
            # as a comparison because Mosaic has no is_finite lowering
            p = jnp.where((m_new > -jnp.inf)[:, None], p, 0.0)
            corr = jnp.where(m_ > -jnp.inf, jnp.exp(m_ - m_new), 0.0)
            l_new = corr * l_ + jnp.sum(p, axis=-1)
            acc_new = corr[:, None] * acc_ + p @ vblk
            return m_new, l_new, acc_new

        if causal:
            upper = jnp.minimum(
                n_k, ((qi + 1) * block_q + block_k - 1) // block_k)
        else:
            upper = n_k
        # key blocks wholly behind the window of the block's first
        # query row are skipped, like those above the diagonal
        lower = 0 if window is None else jnp.maximum(
            0, (qi * block_q - window + 1) // block_k)
        if has_len:
            # key blocks past lengths[b] are fully masked: skip them
            upper = jnp.minimum(upper, (len_b + block_k - 1) // block_k)
        m, l, acc = jax.lax.fori_loop(lower, upper, body, (m, l, acc))
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[...] = (acc / safe_l[:, None]).astype(o_ref.dtype)
        # rows with no unmasked keys get lse=+inf so exp(s - lse) == 0
        # in the backward (cannot happen for full causal blocks, but
        # keeps the kernel total for arbitrary masks)
        lse_ref[...] = jnp.where(l > 0, m + jnp.log(safe_l),
                                 jnp.inf)[:, None]

    from jax.experimental.pallas import tpu as pltpu

    qt = q.transpose(0, 2, 1, 3)          # (B, H, T, d)
    kt = k.transpose(0, 2, 1, 3)          # (B, Kh, T, d)
    vt = v.transpose(0, 2, 1, 3)
    if lengths is None:  # static no-padding case: kernels skip the
        lengths = jnp.full((B,), T, jnp.int32)  # mask entirely
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, n_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda b, h, i, lens: (b, h, i, 0)),
            pl.BlockSpec((None, None, T, d),
                         lambda b, h, i, lens: (b, h // rep, 0, 0)),
            pl.BlockSpec((None, None, T, d),
                         lambda b, h, i, lens: (b, h // rep, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda b, h, i, lens: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda b, h, i, lens: (b, h, i, 0)),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, d), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(lengths.astype(jnp.int32), qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)       # back to (B, T, H, d)
    return (out, lse[..., 0]) if return_lse else out


def _pallas_backward(q, k, v, lse, delta, dout, causal, scale,
                     block_q=None, block_k=None, interpret=False,
                     lengths=None):
    has_len = lengths is not None
    plat = "cpu" if interpret else "tpu"
    if block_q is None:
        block_q = tuning.get("flash_attention", "block_q", plat)
    if block_k is None:
        block_k = tuning.get("flash_attention", "block_k", plat)
    """O(T)-memory flash backward: dQ/dK/dV via block recomputation
    against the saved log-sum-exp — no (T, T) score matrix is ever
    materialized. delta is rowsum(dO * O), shape (B, H, T).

    dq kernel: one Q block vs a K/V sweep (same walk as forward).
    dkv kernel: one K block vs a Q sweep, per *query* head; the GQA
    group-sum over the rep query heads per kv head happens outside."""
    from jax.experimental import pallas as pl

    B, T, H, d = q.shape
    Kh = k.shape[2]
    rep = H // Kh
    block_q = _pick_block(T, block_q)
    block_k = _pick_block(T, block_k)
    n_q = T // block_q
    n_k = T // block_k

    def dq_kernel(lens_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref,
                  do_ref, dq_ref):
        qi = pl.program_id(2)
        len_b = lens_ref[pl.program_id(0)]
        qblk = q_ref[...].astype(jnp.float32)          # (block_q, d)
        doblk = do_ref[...].astype(jnp.float32)
        lseb = lse_ref[...].astype(jnp.float32)        # (block_q, 1)
        deltb = delta_ref[...].astype(jnp.float32)

        def body(ki, acc_):
            kblk = k_ref[pl.dslice(ki * block_k, block_k), :] \
                .astype(jnp.float32)
            vblk = v_ref[pl.dslice(ki * block_k, block_k), :] \
                .astype(jnp.float32)
            s = (qblk @ kblk.T) * scale
            if causal:
                s = _mask_causal(s, qi, ki, block_q, block_k)
            if has_len:
                s = _mask_lengths(s, ki, block_k, len_b)
            p = jnp.exp(s - lseb)                      # 0 where masked
            dp = doblk @ vblk.T
            ds = p * (dp - deltb)
            return acc_ + ds @ kblk

        if causal:
            upper = jnp.minimum(
                n_k, ((qi + 1) * block_q + block_k - 1) // block_k)
        else:
            upper = n_k
        if has_len:
            upper = jnp.minimum(upper, (len_b + block_k - 1) // block_k)
        acc = jax.lax.fori_loop(
            0, upper, body, jnp.zeros((block_q, d), jnp.float32))
        dq_ref[...] = (acc * scale).astype(dq_ref.dtype)

    def dkv_kernel(lens_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref,
                   do_ref, dk_ref, dv_ref):
        ki = pl.program_id(2)
        len_b = lens_ref[pl.program_id(0)]
        kblk = k_ref[...].astype(jnp.float32)          # (block_k, d)
        vblk = v_ref[...].astype(jnp.float32)

        def body(qi, carry):
            dk_, dv_ = carry
            qblk = q_ref[pl.dslice(qi * block_q, block_q), :] \
                .astype(jnp.float32)
            doblk = do_ref[pl.dslice(qi * block_q, block_q), :] \
                .astype(jnp.float32)
            lseb = lse_ref[pl.dslice(qi * block_q, block_q), :] \
                .astype(jnp.float32)                   # (block_q, 1)
            deltb = delta_ref[pl.dslice(qi * block_q, block_q), :] \
                .astype(jnp.float32)
            s = (qblk @ kblk.T) * scale                # (block_q, block_k)
            if causal:
                s = _mask_causal(s, qi, ki, block_q, block_k)
            if has_len:
                # NOTE: the q-block sweep is NOT truncated — query rows
                # beyond lengths still attend valid keys (only KEYS are
                # padded), so their cotangents legitimately reach dk/dv
                s = _mask_lengths(s, ki, block_k, len_b)
            p = jnp.exp(s - lseb)
            dv_ = dv_ + p.T @ doblk
            dp = doblk @ vblk.T
            ds = p * (dp - deltb)
            dk_ = dk_ + ds.T @ qblk
            return dk_, dv_

        lower = (ki * block_k) // block_q if causal else 0
        zeros = jnp.zeros((block_k, d), jnp.float32)
        dk, dv = jax.lax.fori_loop(lower, n_q, body, (zeros, zeros))
        dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)

    # (B, H, T, d) internal layout (see _pallas_forward); lse/delta as
    # (B, H, T, 1)
    from jax.experimental.pallas import tpu as pltpu

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = dout.transpose(0, 2, 1, 3)
    lse4 = lse[..., None]
    delta4 = delta[..., None]
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    lens = lengths.astype(jnp.int32)

    qspec = pl.BlockSpec((None, None, block_q, d),
                         lambda b, h, i, lens: (b, h, i, 0))
    full_q = pl.BlockSpec((None, None, T, d),
                          lambda b, h, i, lens: (b, h, 0, 0))
    full_kv = pl.BlockSpec((None, None, T, d),
                           lambda b, h, i, lens: (b, h // rep, 0, 0))
    row_blk = pl.BlockSpec((None, None, block_q, 1),
                           lambda b, h, i, lens: (b, h, i, 0))
    row_full = pl.BlockSpec((None, None, T, 1),
                            lambda b, h, i, lens: (b, h, 0, 0))

    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, n_q),
            in_specs=[qspec, full_kv, full_kv, row_blk, row_blk,
                      qspec],
            out_specs=qspec),
        out_shape=jax.ShapeDtypeStruct((B, H, T, d), q.dtype),
        interpret=interpret,
        name="flash_attention_dq",
    )(lens, qt, kt, vt, lse4, delta4, dot)

    kspec = pl.BlockSpec((None, None, block_k, d),
                         lambda b, h, i, lens: (b, h // rep, i, 0))
    dkv_out = pl.BlockSpec((None, None, block_k, d),
                           lambda b, h, i, lens: (b, h, i, 0))
    dk_h, dv_h = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, n_k),
            in_specs=[full_q, kspec, kspec, row_full, row_full,
                      full_q],
            out_specs=[dkv_out, dkv_out]),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, d), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, d), q.dtype),
        ],
        interpret=interpret,
        name="flash_attention_dkv",
    )(lens, qt, kt, vt, lse4, delta4, dot)
    dq = dq.transpose(0, 2, 1, 3)                  # (B, T, H, d)
    # GQA: query head h reads kv head h//rep, so sum each group of rep
    # consecutive query heads back into its kv head
    if rep > 1:
        dk = dk_h.reshape(B, Kh, rep, T, d).sum(axis=2) \
            .transpose(0, 2, 1, 3).astype(k.dtype)
        dv = dv_h.reshape(B, Kh, rep, T, d).sum(axis=2) \
            .transpose(0, 2, 1, 3).astype(v.dtype)
    else:
        dk = dk_h.transpose(0, 2, 1, 3)
        dv = dv_h.transpose(0, 2, 1, 3)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_pallas(q, k, v, lengths, causal, scale, interpret,
                  window=None):
    out, _ = _flash_pallas_fwd(q, k, v, lengths, causal, scale,
                               interpret, window)
    return out


def _flash_pallas_fwd(q, k, v, lengths, causal, scale, interpret,
                      window=None):
    out, lse = _pallas_forward(q, k, v, causal, scale,
                               interpret=interpret, return_lse=True,
                               lengths=lengths, window=window)
    return out, (q, k, v, lengths, out, lse)


def _len_cotangent(lengths):
    # integer primal -> float0 cotangent (jax's convention); None stays
    # None (the static no-padding case)
    if lengths is None:
        return None
    import numpy as _np
    return _np.zeros(lengths.shape, jax.dtypes.float0)


def _flash_pallas_bwd(causal, scale, interpret, window, res, g):
    if window is not None:
        raise NotImplementedError(
            "flash attention with a sliding window has no backward "
            "kernels (dq / dkv know no window mask): the windowed "
            "forward serves prefill only")
    q, k, v, lengths, out, lse = res
    # delta_i = rowsum(dO_i * O_i): the softmax-jacobian correction term
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)  # (B, H, T)
    try:
        dq, dk, dv = _pallas_backward(q, k, v, lse, delta,
                                      g.astype(q.dtype), causal, scale,
                                      interpret=interpret,
                                      lengths=lengths)
        return dq, dk, dv, _len_cotangent(lengths)
    except Exception as e:
        # same contract as the forward: never let a kernel regression
        # crash training unless the user opted into strict mode
        _fallback.note(e)
        _, vjp = jax.vjp(lambda q_, k_, v_:
                         reference_attention(q_, k_, v_, causal, scale,
                                             lengths),
                         q, k, v)
        return vjp(g) + (_len_cotangent(lengths),)


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_ref(q, k, v, lengths, causal, scale, window=None):
    return reference_attention(q, k, v, causal, scale, lengths, window)


def _flash_ref_fwd(q, k, v, lengths, causal, scale, window=None):
    # save only q/k/v; recompute the softmax in the backward instead of
    # storing the (B, H, T, T) probability matrix
    return (reference_attention(q, k, v, causal, scale, lengths,
                                window),
            (q, k, v, lengths))


def _flash_ref_bwd(causal, scale, window, res, g):
    q, k, v, lengths = res
    _, vjp = jax.vjp(lambda q_, k_, v_:
                     reference_attention(q_, k_, v_, causal, scale,
                                         lengths, window),
                     q, k, v)
    return vjp(g) + (_len_cotangent(lengths),)


_flash_ref.defvjp(_flash_ref_fwd, _flash_ref_bwd)


def _pallas_mode(T):
    """None (use reference), 'compiled', or 'interpret' (CPU testing of
    the real kernels, enabled via MXNET_TPU_FLASH_INTERPRET=1)."""
    if T % 128 != 0:
        return None
    if os.environ.get("MXNET_TPU_FLASH_INTERPRET", "0") == "1":
        return "interpret"
    if jax.default_backend() not in ("cpu",):
        return "compiled"
    return None


def flash_attention_raw(q, k, v, causal=True, scale=None,
                        use_flash=True, lengths=None, window=None):
    """lengths (B,) optionally masks key positions >= lengths[b]
    (BERT-style key padding); composes with causal. `window` (causal
    only) is a sliding window: query i sees keys i - window < j <= i;
    the forward kernel masks and skips the key blocks behind it, the
    backward kernels refuse it (the jnp path differentiates)."""
    if window is not None:
        if not causal:
            raise ValueError("a sliding window needs causal=True")
        window = int(window)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    mode = _pallas_mode(q.shape[1]) if use_flash else None
    if mode == "compiled":
        from .dispatch import operand_on_cpu

        if operand_on_cpu(q):
            mode = None  # eager call on CPU-committed data: no Mosaic
    if mode is not None:
        from jax.sharding import PartitionSpec as P

        from .dispatch import per_shard

        interp = mode == "interpret"
        qkv = P("dp", None, "tp", None)     # batch over dp, heads over tp
        has_len = lengths is not None
        try:
            return per_shard(
                lambda q_, k_, v_, *l_: _flash_pallas(
                    q_, k_, v_, l_[0] if l_ else None, causal, scale,
                    interp, window),
                (q, k, v) + ((lengths,) if has_len else ()),
                (qkv, qkv, qkv) + ((P("dp"),) if has_len else ()))
        except Exception as e:
            # fail loudly: a silently-degraded flash path hides O(T^2)
            # perf regressions. MXNET_TPU_STRICT_FLASH=1 (or
            # MXNET_TPU_STRICT_KERNELS=1) turns the fallback into an
            # error; otherwise warn once and count.
            _fallback.note(e)
    return _flash_ref(q, k, v, lengths, causal, scale, window)
