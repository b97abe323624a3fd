"""Causal (flash) attention.

Reference analogue: the fork's fused multi-head attention CUDA kernels
(interleaved_matmul_selfatt*, fmha). TPU-first: Pallas kernels tile
Q/K/V blocks through VMEM with an online-softmax accumulator, forward
(`flash_attention_fwd`) and backward (`flash_attention_dkv`: dQ, dK and
dV of one recomputation against the saved log-sum-exp). Both take a
sliding `window` and skip the blocks outside it, so a windowed layer
trains at the window's cost. The jnp reference is the CPU path and the
tests' yardstick; it differentiates too (`_flash_ref`).

Layout convention: (B, T, H, d) for q, (B, T, K, d) for k/v with GQA
(H % K == 0). Output (B, T, H, d). The kernels read and write the
free reshape (B, T, H * d) of it — where the projections leave the
activations — in column blocks of whole lane tiles (two heads of 64,
one of 128), so nothing is transposed or lane-padded round a call.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import tuning
from .dispatch import (KernelFallback, float0_like, kernel_mode,
                       per_shard)

__all__ = ["flash_attention_raw", "reference_attention"]

_fallback = KernelFallback("flash-attention", "FLASH")


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


def _tuned_block(T, key, want, interpret):
    """`want` rows (the tuning table's for the platform when None) cut
    to a divisor of T."""
    if want is None:
        want = tuning.get("flash_attention", key,
                          "cpu" if interpret else "tpu")
    return _pick_block(T, want)


def _heads_per_step(H, K, d):
    """(G, Gk): how many query heads and kv heads one grid step holds.

    The kernels block the activations as the model keeps them,
    (B, T, H * d), in column blocks of G heads. Mosaic wants a block's
    lane width a multiple of 128 or the whole array's: G is the fewest
    heads that fill whole lane tiles (2 heads of 64, 1 of 128) and
    whose kv heads do too, else every head (a width the array spans).
    Query head j of a step reads kv head j // rep of the step's kv
    block when the step holds whole GQA groups, else the block's one
    head."""
    rep = H // K
    for G in range(1, H):
        if H % G or (G * d) % 128:
            continue
        if G % rep == 0:
            Gk = G // rep
        elif rep % G == 0:
            Gk = 1
        else:
            continue
        if (Gk * d) % 128 == 0:
            return G, Gk
    return H, K


def _head_cols(j, d, G, rep):
    """Lane slices of query head j of a step in its q / o block and of
    its kv head in the step's k / v block (`_heads_per_step`)."""
    kv = j // rep if G % rep == 0 else 0
    return slice(j * d, (j + 1) * d), slice(kv * d, (kv + 1) * d)


# dot_general dimension numbers on 2-D operands
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims=_NN):
    """MXU product of the operands AS STORED (bf16 in, no upcast: the
    MXU rounds float32 operands to one bf16 pass anyway, PERF.md PR 25)
    accumulated in float32."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _keep_mask(qi, ki, block_q, block_k, causal, window, len_b):
    """Which scores of block (key block ki, query block qi) stay, in
    the kernels' layout: keys down the sublanes, queries along the
    lanes. None when nothing is masked."""
    if not causal and len_b is None:
        return None
    shape = (block_k, block_q)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    keep = None
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        keep = qpos >= kpos
        if window is not None:
            keep = jnp.logical_and(keep, qpos - kpos < window)
    if len_b is not None:
        valid = kpos < len_b
        keep = valid if keep is None else jnp.logical_and(keep, valid)
    return keep


def _rows(i, block):
    """Slice of block i; tells Mosaic a traced start is aligned."""
    from jax.experimental import pallas as pl

    start = i * block
    if not isinstance(start, int):
        start = pl.multiple_of(start, block)
    return pl.ds(start, block)


def _compiler_params(interpret, resident_bytes):
    """The grid's last axis carries state (resident blocks, the dq
    accumulator); VMEM is asked for by what a step holds — its blocks
    double-buffered plus the float32 score tiles — not left at the
    16 MiB default a whole-sequence K/V block already fills."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    limit = min(max(32 << 20, resident_bytes * 5 // 4), 100 << 20)
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=limit)}


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "return_lse",
    "window"))
def _pallas_forward(q, k, v, causal, scale, block_q=None, block_k=None,
                    interpret=False, return_lse=False, lengths=None,
                    window=None):
    """Online-softmax flash forward in Pallas (TPU; interpret=True runs
    the same kernel under the Pallas interpreter for CPU testing).

    The call reads q / k / v and writes o where the model keeps them:
    (B, T, H * d), a free reshape of (B, T, H, d), in column blocks of
    `_heads_per_step` heads — no transpose round the call and no lane
    padding at heads of 64. Inside, scores are held TRANSPOSED (keys
    down the sublanes, queries along the lanes), so the running max,
    the sum and the log-sum-exp are lane-dense rows: lse leaves as
    (B, H // G, G, T) and is returned as (B, H, T) when
    return_lse=True. A jit of its own: a program's layers share one
    trace of the unrolled head loop."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    has_len = lengths is not None
    B, T, H, d = q.shape
    Kh = k.shape[2]
    rep = H // Kh
    G, Gk = _heads_per_step(H, Kh, d)
    block_q = _tuned_block(T, "block_q", block_q, interpret)
    block_k = _tuned_block(T, "block_k", block_k, interpret)
    n_q, n_k = T // block_q, T // block_k

    def kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, lse_ref):
        # grid: (B, H // G, n_q). G heads of a block of Q rows, each
        # against a sweep of its kv head's resident K/V.
        qi = pl.program_id(2)
        len_b = lens_ref[pl.program_id(0)] if has_len else None
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

        for j in range(G):
            cols, kcols = _head_cols(j, d, G, rep)
            # the scale folds into q once (float32 product, one
            # rounding to the stored type: what the MXU sees)
            qh = (q_ref[:, cols].astype(jnp.float32) * scale) \
                .astype(q_ref.dtype)                     # (block_q, d)

            def body(ki, carry, qh=qh, kcols=kcols):
                m_, l_, acc_ = carry       # (1, bq), (1, bq), (d, bq)
                kblk = k_ref[_rows(ki, block_k), kcols]
                vblk = v_ref[_rows(ki, block_k), kcols]
                s = _dot(kblk, qh, _NT)                  # (block_k, bq)
                keep = _keep_mask(qi, ki, block_q, block_k, causal,
                                  window, len_b)
                if keep is not None:
                    s = jnp.where(keep, s, -jnp.inf)
                m_new = jnp.maximum(m_, jnp.max(s, axis=0, keepdims=True))
                # a query that has seen no valid key keeps max -inf:
                # subtracting 0 there leaves exp(-inf) = 0, no NaN
                # (a comparison: Mosaic has no is_finite lowering)
                m_safe = jnp.where(m_new > -jnp.inf, m_new, 0.0)
                p = jnp.exp(s - m_safe)
                corr = jnp.exp(m_ - m_safe)
                l_new = corr * l_ + jnp.sum(p, axis=0, keepdims=True)
                acc_new = corr * acc_ + _dot(vblk, p.astype(vblk.dtype),
                                             _TN)       # (d, bq)
                return m_new, l_new, acc_new

            init = (jnp.full((1, block_q), -jnp.inf, jnp.float32),
                    jnp.zeros((1, block_q), jnp.float32),
                    jnp.zeros((d, block_q), jnp.float32))
            if n_k == 1:    # a whole-sequence key block: no loop
                m, l, acc = body(0, init)
            else:
                m, l, acc = jax.lax.fori_loop(lower, upper, body, init)
            safe_l = jnp.where(l > 0, l, 1.0)
            o_ref[:, cols] = (acc * (1.0 / safe_l)).T.astype(o_ref.dtype)
            # rows with no unmasked keys get lse=+inf so exp(s - lse)
            # == 0 in the backward (cannot happen for full causal
            # blocks, but keeps the kernel total for arbitrary masks)
            lse_ref[j:j + 1, :] = jnp.where(l > 0, m + jnp.log(safe_l),
                                            jnp.inf)

    if lengths is None:  # static no-padding case: kernels skip the
        lengths = jnp.full((B,), T, jnp.int32)  # mask entirely
    qspec = pl.BlockSpec((None, block_q, G * d),
                         lambda b, g, i, lens: (b, i, g))
    kvspec = pl.BlockSpec((None, T, Gk * d),
                          lambda b, g, i, lens: (b, 0, g * G // rep // Gk))
    item = q.dtype.itemsize
    resident = 2 * item * (2 * block_q * G * d + 2 * T * Gk * d) \
        + 6 * 4 * block_q * block_k
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // G, n_q),
            in_specs=[qspec, kvspec, kvspec],
            out_specs=[qspec,
                       pl.BlockSpec((None, None, G, block_q),
                                    lambda b, g, i, lens: (b, g, 0, i))]),
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H * d), q.dtype),
            jax.ShapeDtypeStruct((B, H // G, G, T), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
        **_compiler_params(interpret, resident),
    )(lengths.astype(jnp.int32), q.reshape(B, T, H * d),
      k.reshape(B, T, Kh * d), v.reshape(B, T, Kh * d))
    out = out.reshape(B, T, H, d)
    return (out, lse.reshape(B, H, T)) if return_lse else out


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "window"))
def _pallas_backward(q, k, v, lse, delta, dout, causal, scale,
                     block_q=None, block_k=None, interpret=False,
                     lengths=None, window=None):
    """O(T)-memory flash backward: dQ/dK/dV via block recomputation
    against the saved log-sum-exp — no (T, T) score matrix is ever
    materialized. lse and delta = rowsum(dO * O) are (B, H, T).

    ONE call (`flash_attention_dkv`) on the forward's layout: a grid
    step holds a K/V block of G heads and sweeps the Q blocks, deriving
    the transposed scores and dP once for dV, dK and dQ (5 matmuls
    where a dq and a dkv call made 7). dK/dV come per *query* head; the
    GQA group-sum over the rep query heads per kv head happens
    outside. dQ of a query block gathers over the key blocks (the
    grid's last axis) in a float32 scratch. With a `window` (static,
    causal) a key block's sweep ends at the last query block that still
    sees one of its keys, and the edge blocks are masked by
    `_keep_mask` as the forward's are: the blocks behind the window
    cost nothing. `window=None` traces what it always did."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    has_len = lengths is not None
    B, T, H, d = q.shape
    Kh = k.shape[2]
    rep = H // Kh
    G, Gk = _heads_per_step(H, Kh, d)
    block_q = _tuned_block(T, "block_q", block_q, interpret)
    block_k = _tuned_block(T, "block_k", block_k, interpret)
    n_q, n_k = T // block_q, T // block_k

    def kernel(lens_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
               dq_ref, dk_ref, dv_ref, *scratch):
        # grid: (B, H // G, n_k). G heads of a block of K/V rows, each
        # against a sweep of its resident Q / dO; dq_acc gathers dQ
        # over the key blocks when there is more than one.
        dq_acc = scratch[0] if scratch else None
        ki = pl.program_id(2)
        len_b = lens_ref[pl.program_id(0)] if has_len else None
        lower = (ki * block_k) // block_q if causal else 0
        # NOTE: the q-block sweep is NOT truncated by lengths — query
        # rows beyond lengths still attend valid keys (only KEYS are
        # padded), so their cotangents legitimately reach dk/dv; a key
        # block wholly past lengths[b] is all zeros and sweeps nothing
        upper = n_q
        if window is not None:
            # the block's last key, (ki + 1) * block_k - 1, is seen by
            # the queries up to window - 1 past it: later query blocks
            # lie wholly outside the window
            upper = jnp.minimum(
                n_q, ((ki + 1) * block_k + window - 2) // block_q + 1)
        if has_len and n_k > 1:
            upper = jnp.where(ki * block_k < len_b, upper, 0)

        if n_k > 1:
            @pl.when(ki == 0)
            def _():
                dq_acc[...] = jnp.zeros_like(dq_acc)

        for j in range(G):
            cols, kcols = _head_cols(j, d, G, rep)
            kblk = k_ref[:, kcols]                       # (block_k, d)
            vblk = v_ref[:, kcols]

            def body(qi, carry, j=j, cols=cols, kblk=kblk, vblk=vblk):
                dk_, dv_ = carry
                r = _rows(qi, block_q)
                qh = (q_ref[r, cols].astype(jnp.float32) * scale) \
                    .astype(q_ref.dtype)                 # (block_q, d)
                doblk = do_ref[r, cols]
                s = _dot(kblk, qh, _NT)                  # (block_k, bq)
                keep = _keep_mask(qi, ki, block_q, block_k, causal,
                                  window, len_b)
                if keep is not None:
                    s = jnp.where(keep, s, -jnp.inf)
                p = jnp.exp(s - lse_ref[j:j + 1, r])     # 0 where masked
                dv_ = dv_ + _dot(p.astype(doblk.dtype), doblk)
                dp = _dot(vblk, doblk, _NT)
                ds = (p * (dp - delta_ref[j:j + 1, r])).astype(qh.dtype)
                dk_ = dk_ + _dot(ds, qh)                 # scale is in qh
                dq = _dot(ds, kblk, _TN) * scale         # (block_q, d)
                if n_k == 1:
                    dq_ref[r, cols] = dq.astype(dq_ref.dtype)
                else:
                    dq_acc[r, cols] += dq
                return dk_, dv_

            zeros = jnp.zeros((block_k, d), jnp.float32)
            if n_q == 1:    # a whole-sequence query block: no loop
                dk, dv = body(0, (zeros, zeros))
            else:
                dk, dv = jax.lax.fori_loop(lower, upper, body,
                                           (zeros, zeros))
            dk_ref[:, cols] = dk.astype(dk_ref.dtype)
            dv_ref[:, cols] = dv.astype(dv_ref.dtype)

        if n_k > 1:
            @pl.when(ki == n_k - 1)
            def _():
                dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)

    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    full_q = pl.BlockSpec((None, T, G * d),
                          lambda b, g, i, lens: (b, 0, g))
    kspec = pl.BlockSpec((None, block_k, Gk * d),
                         lambda b, g, i, lens: (b, i, g * G // rep // Gk))
    dkv_out = pl.BlockSpec((None, block_k, G * d),
                           lambda b, g, i, lens: (b, i, g))
    row_full = pl.BlockSpec((None, None, G, T),
                            lambda b, g, i, lens: (b, g, 0, 0))
    item = q.dtype.itemsize
    resident = 2 * item * (3 * T * G * d + 2 * block_k * (Gk + G) * d) \
        + (n_k > 1) * 4 * T * G * d + 8 * 4 * block_q * block_k
    dq, dk_h, dv_h = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // G, n_k),
            in_specs=[full_q, kspec, kspec, row_full, row_full, full_q],
            out_specs=[full_q, dkv_out, dkv_out],
            scratch_shapes=[pltpu.VMEM((T, G * d), jnp.float32)]
            if n_k > 1 else []),
        out_shape=[jax.ShapeDtypeStruct((B, T, H * d), q.dtype)] * 3,
        interpret=interpret,
        name="flash_attention_dkv",
        **_compiler_params(interpret, resident),
    )(lengths.astype(jnp.int32), q.reshape(B, T, H * d),
      k.reshape(B, T, Kh * d), v.reshape(B, T, Kh * d),
      lse.reshape(B, H // G, G, T), delta.reshape(B, H // G, G, T),
      dout.reshape(B, T, H * d))
    dq = dq.reshape(B, T, H, d)
    # GQA: query head h reads kv head h//rep, so sum each group of rep
    # consecutive query heads back into its kv head
    if rep > 1:
        dk = dk_h.reshape(B, T, Kh, rep, d).sum(axis=3).astype(k.dtype)
        dv = dv_h.reshape(B, T, Kh, rep, d).sum(axis=3).astype(v.dtype)
    else:
        dk = dk_h.reshape(B, T, Kh, d)
        dv = dv_h.reshape(B, T, Kh, d)
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


def _rowsum_per_head(g, out):
    """delta_i = rowsum(dO_i * O_i), the softmax-jacobian correction
    term, as (B, H, T) float32. Summed on the (B, T, H * d) layout by a
    product with the heads' 0/1 selector: a reduce over the d of
    (B, T, H, d) would first re-lay the float32 product out with
    (H, d) minor — a copy of twice the activations' bytes."""
    B, T, H, d = g.shape
    prod = (g.astype(jnp.float32) * out.astype(jnp.float32)) \
        .reshape(B, T, H * d)
    sel = (jnp.arange(H * d)[:, None] // d
           == jnp.arange(H)[None, :]).astype(jnp.float32)
    return jnp.einsum("btc,ch->bht", prod, sel,
                      precision=jax.lax.Precision.HIGHEST)


def _len_cotangent(lengths):
    # integer primal -> float0 cotangent (jax's convention); None stays
    # None (the static no-padding case)
    return None if lengths is None else float0_like(lengths)


def _flash_pallas_bwd(causal, scale, interpret, window, res, g):
    q, k, v, lengths, out, lse = res
    delta = _rowsum_per_head(g, out)                 # (B, H, T)

    def ref():
        _, vjp = jax.vjp(lambda q_, k_, v_:
                         reference_attention(q_, k_, v_, causal, scale,
                                             lengths, window),
                         q, k, v)
        return vjp(g)

    # same contract as the forward: never let a kernel regression
    # crash training unless the user opted into strict mode
    return _fallback.run(
        "interpret" if interpret else "compiled",
        lambda interp: _pallas_backward(
            q, k, v, lse, delta, g.astype(q.dtype), causal, scale,
            interpret=interp, lengths=lengths, window=window),
        ref) + (_len_cotangent(lengths),)


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


def flash_attention_raw(q, k, v, causal=True, scale=None, lengths=None,
                        window=None):
    """lengths (B,) optionally masks key positions >= lengths[b]
    (BERT-style key padding); composes with causal. `window` (causal
    only) is a sliding window: query i sees keys i - window < j <= i;
    the forward kernel masks and skips the key blocks behind it and
    the backward kernel the query blocks past it, so both
    differentiate at the window's cost (as the jnp path does at the
    whole square's)."""
    if window is not None:
        if not causal:
            raise ValueError("a sliding window needs causal=True")
        window = int(window)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    qkv = P("dp", None, "tp", None)     # batch over dp, heads over tp
    has_len = lengths is not None
    # fail loudly: a silently-degraded flash path hides O(T^2) perf
    # regressions (the Mosaic blocks need T in whole 128-row tiles)
    return _fallback.run(
        kernel_mode("FLASH", q, ok=q.shape[1] % 128 == 0),
        lambda interp: per_shard(
            lambda q_, k_, v_, *l_: _flash_pallas(
                q_, k_, v_, l_[0] if l_ else None, causal, scale,
                interp, window),
            (q, k, v) + ((lengths,) if has_len else ()),
            (qkv, qkv, qkv) + ((P("dp"),) if has_len else ())),
        lambda: _flash_ref(q, k, v, lengths, causal, scale, window))
