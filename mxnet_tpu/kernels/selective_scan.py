"""Selective-scan (Mamba-1) Pallas kernels: the scan over a prompt and
the one-step state update of a decode tick.

The recurrence of one state-space layer, per channel c of d_inner and
state index n of N:

    h_t[n, c] = exp(dt_t[c] * A[n, c]) * h_{t-1}[n, c]
                + dt_t[c] * x_t[c] * B_t[n]
    y_t[c]    = sum_n h_t[n, c] * C_t[n]            A = -exp(A_log)

Both kernels keep the state STATE-MAJOR, `(N, d_inner)`, so d_inner
lies along the lanes and the sublanes: a state row `h[n]` of 1,024
channels is one (8, 128) vreg, `B_t[n]` and `C_t[n]` are scalars read
from SMEM and splat, and every vector operation of the recurrence runs
on full vregs. `(T, d_inner, N)` is never formed, in VMEM or in HBM.

- `selective_scan_fwd` (prefill): grid (batch, channel blocks, time
  chunks), the time axis sequential; the state of a channel block
  lives in the output block across the chunks of a sequence. Takes an
  initial state and returns y and the final state. A position whose
  `dt` is 0 leaves the state as it was (exp(0) = 1, dt x B = 0): the
  caller zeroes `dt` on right padding, and the final state is the one
  at `valid_len`. VPU / EUP bound (N exp and ~6 N vector operations a
  channel a position); HBM needs a twentieth of its time.
- `ssm_state_update` (decode): ONE call a layer for every row of the
  tick, and everything of the layer that lies between its two big
  matmuls (`in_proj` before, `out_proj` after). In: the state pool
  `(R,) + state_shape` float32 and the tail pool `(R,) + tail_shape`
  in the model's dtype, both ALIASED in and out
  (`input_output_aliases`); `xz` `(R, 2 Dn)` as `in_proj` leaves it,
  its x and z halves taken by two `BlockSpec`s over the one array; a
  layer's small weights (`STEP_WEIGHTS`, 3.6 MB bf16 + 0.5 MB float32
  at Jamba2-3B's sizes) whole and resident; `active` in SMEM. Out: `g`
  `(R, Dn)` in the model's dtype, ready for `out_proj`. A block of
  rows a grid step: the convolution's taps and silu, x_proj and
  dt_proj on the MXU over the block's rows, Jamba's three norms, bias
  and softplus run with the ROWS on the sublanes, as `xz`, the tail
  and `g` lie in HBM; dt and dt * x are then re-laid to a row's
  `(Dn / 128, 128)` tile through a VMEM scratch (a strided store of
  every 128-channel chunk), B and C go to SMEM by a DMA, and the
  recurrence runs a row at a time as it always did; y comes back the
  same way for the gate. A row whose `active` is 0 gets state and tail
  written back as they were. HBM bound: the state is read and written
  once, 2 x N x Dn x 4 bytes a row, beside `xz`, both tails and `g`
  (18 Dn bytes in bf16); x, dt and y never leave VMEM.

There is no backward: nothing trains through these kernels.
Each has a jnp twin (`*_ref`) that is the CPU path and the tests'
yardstick; `MXNET_TPU_SCAN_INTERPRET=1` traces the kernels under the
Pallas interpreter.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import tuning
from .dispatch import KernelFallback, kernel_mode

__all__ = ["selective_scan", "selective_scan_ref", "ssm_state_update",
           "ssm_state_update_ref", "ssm_inputs", "gate", "state_shape",
           "tail_shape", "STEP_WEIGHTS"]

_scan_fallback = KernelFallback("selective-scan", "SCAN")
_step_fallback = KernelFallback("ssm-state-update", "SCAN")

_LANES = 128


def state_shape(n, dn):
    """One sequence's state as the kernels hold it and a pool stores
    it: `(N, d_inner / 128, 128)`, channels on sublanes and lanes. A
    pool kept `(N, d_inner)` would be re-laid-out whole on its way into
    every call (XLA tiles the two minor dims)."""
    if dn % _LANES:
        raise ValueError(f"d_inner {dn} is no whole number of "
                         f"{_LANES}-lane rows")
    return (n, dn // _LANES, _LANES)


def tail_shape(k, dn):
    """One sequence's convolution tail, the last d_conv - 1 inputs, as
    the step holds it and a pool stores it: `((d_conv - 1) * d_inner,)`,
    tap after tap along the lanes, so that a block of rows is the rows
    of `xz` beside it. (A pool kept `(d_conv - 1, d_inner)` a row is
    tiled over its 3 x 5,120 minor dimensions and is re-laid out on its
    way into every call.)"""
    return ((k - 1) * dn,)


def _channel_rows(dn):
    """Sublane rows of 128 channels a channel block holds: 8 (one vreg
    a state row) where d_inner allows, else all of them; 0 where
    d_inner is no whole number of lane rows (the jnp twin runs)."""
    if dn % _LANES:
        return 0
    rows = dn // _LANES
    return 8 if rows % 8 == 0 else rows


# -- jnp twins ---------------------------------------------------------------

def selective_scan_ref(x, dt, a_log, b, c, h0):
    """x, dt (B, T, Dn) f32; a_log (N, Dn); b, c (B, T, N); h0
    (B,) + state_shape. Returns y (B, T, Dn) f32 and the final
    state."""
    a = -jnp.exp(a_log.astype(jnp.float32))
    shape = h0.shape
    h0 = h0.reshape(shape[0], shape[1], -1)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None, :] * a) * h \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    seq = tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0)
                for v in (x, dt, b, c))
    h, y = jax.lax.scan(step, h0.astype(jnp.float32), seq)
    return jnp.moveaxis(y, 0, 1), h.reshape(shape)


STEP_WEIGHTS = ("conv_w", "conv_b", "x_proj", "dt_norm", "b_norm", "c_norm",
                "dt_proj", "dt_bias", "A_log", "D")


def ssm_inputs(w, xc, eps):
    """dt (float32, after softplus), B, C of the recurrence from the
    convolved input xc (..., Dn): x_proj, Jamba's three norms (on dt, B
    and C), dt_proj."""
    from .fused_norm import fused_rmsnorm as rms

    R, N = w["dt_proj"].shape[1], w["b_norm"].shape[0]
    p = xc @ w["x_proj"].T
    dt_r = rms(p[..., :R], w["dt_norm"], eps)
    b = rms(p[..., R:R + N], w["b_norm"], eps)
    c = rms(p[..., R + N:], w["c_norm"], eps)
    dt = jax.nn.softplus((dt_r @ w["dt_proj"].T).astype(jnp.float32)
                         + w["dt_bias"])
    return dt, b, c


def gate(w, y, xc, z):
    """(y + D x) * silu(z), the product in float32, in z's dtype."""
    g = (y + w["D"] * xc.astype(jnp.float32)) \
        * jax.nn.silu(z.astype(jnp.float32))
    return g.astype(z.dtype)


def ssm_state_update_ref(h, tail, xz, active, w, eps):
    """One token of every row between in_proj and out_proj: h (R,) +
    state_shape f32; tail (R,) + tail_shape and xz (R, 2 Dn) in the
    model's dtype; active (R,) bool; `w` a layer's STEP_WEIGHTS.
    Returns (g (R, Dn), h', tail'); an inactive row keeps its state and
    its tail."""
    f32 = jnp.float32
    R, Dn = xz.shape[0], xz.shape[-1] // 2
    xr, z = xz[:, :Dn], xz[:, Dn:]
    win = jnp.concatenate([tail.reshape(R, -1, Dn).astype(xr.dtype),
                           xr[:, None]], axis=1)
    acc = jnp.sum(win.astype(f32) * w["conv_w"], axis=1) + w["conv_b"]
    xc = jax.nn.silu(acc).astype(xz.dtype)
    dt, b, c = ssm_inputs(w, xc, eps)
    a = -jnp.exp(w["A_log"].astype(f32))
    hf = h.reshape(R, h.shape[1], -1).astype(f32)
    new = jnp.exp(dt[:, None, :] * a) * hf \
        + (dt * xc.astype(f32))[:, None, :] * b.astype(f32)[:, :, None]
    y = jnp.sum(new * c.astype(f32)[:, :, None], axis=1)
    keep = active[:, None, None]
    return gate(w, jnp.where(active[:, None], y, 0.0), xc, z), \
        jnp.where(keep, new, hf).reshape(h.shape).astype(h.dtype), \
        jnp.where(active[:, None],
                  win[:, 1:].reshape(R, -1).astype(tail.dtype), tail)


# -- the scan over a prompt ----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def selective_scan_fwd(x, dt, a_log, b, c, h0, *, chunk, interpret):
    """The Pallas scan. A jit of its own, so the layers of a prefill
    program share one trace and one Mosaic lowering of the body."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, Dn = x.shape
    N = a_log.shape[0]
    S = _channel_rows(Dn)
    rows = Dn // _LANES
    Tc = min(chunk, -(-T // 8) * 8)
    Tp = -(-T // Tc) * Tc
    if Tp != T:          # dt = 0 on the padding: the state stands still
        pad = ((0, 0), (0, Tp - T), (0, 0))
        x, dt, b, c = (jnp.pad(v, pad) for v in (x, dt, b, c))
    x4 = x.reshape(B, Tp, rows, _LANES)
    dt4 = dt.reshape(B, Tp, rows, _LANES)
    a3 = a_log.astype(jnp.float32).reshape(N, rows, _LANES)
    h4 = h0.astype(jnp.float32)

    def kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, h_ref,
               a_scr):
        @pl.when(pl.program_id(2) == 0)
        def _first_chunk():
            h_ref[...] = h0_ref[...]
            a_scr[...] = -jnp.exp(a_ref[...])

        def step(t, hs):
            dt_t = dt_ref[t]                             # (S, 128)
            dtx = dt_t * x_ref[t]
            y = jnp.zeros_like(dt_t)
            new = []
            for n in range(N):
                h = jnp.exp(dt_t * a_scr[n]) * hs[n] + dtx * b_ref[t, n]
                y = y + h * c_ref[t, n]
                new.append(h)
            y_ref[t] = y
            return tuple(new)

        hs = jax.lax.fori_loop(0, Tc, step,
                               tuple(h_ref[n] for n in range(N)))
        for n in range(N):
            h_ref[n] = hs[n]

    seq = pl.BlockSpec((None, Tc, S, _LANES),
                       lambda bi, j, i: (bi, i, j, 0))
    coef = pl.BlockSpec((None, Tc, N), lambda bi, j, i: (bi, i, 0),
                        memory_space=pltpu.SMEM)
    state = pl.BlockSpec((None, N, S, _LANES),
                         lambda bi, j, i: (bi, 0, j, 0))
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))}
    y, h = pl.pallas_call(
        kernel,
        grid=(B, rows // S, Tp // Tc),
        in_specs=[seq, seq,
                  pl.BlockSpec((N, S, _LANES),
                               lambda bi, j, i: (0, j, 0)),
                  coef, coef, state],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, rows, _LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((B, N, rows, _LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, S, _LANES), jnp.float32)],
        interpret=interpret,
        name="selective_scan_fwd",
        **params,
    )(x4, dt4, a3, b.astype(jnp.float32), c.astype(jnp.float32), h4)
    return y.reshape(B, Tp, Dn)[:, :T], h


def selective_scan(x, dt, a_log, b, c, h0, use_kernel=True):
    """y, final state of the recurrence over (B, T): the Pallas scan
    where the gate admits it, else the jnp twin. float32 in and out."""
    f32 = jnp.float32
    x, dt, b, c, h0 = (v.astype(f32) for v in (x, dt, b, c, h0))
    return _scan_fallback.run(
        kernel_mode("SCAN", x,
                    ok=use_kernel and _channel_rows(x.shape[-1])),
        lambda interpret: selective_scan_fwd(
            x, dt, a_log, b, c, h0,
            chunk=tuning.get("selective_scan", "time_chunk"),
            interpret=interpret),
        lambda: selective_scan_ref(x, dt, a_log, b, c, h0))


# -- one step for every row of a decode tick -----------------------------------

def _rows_per_step(R, want):
    """The largest divisor of R up to `want`: the pools are updated in
    place, so their rows cannot be padded to a block."""
    return max(r for r in range(1, min(R, want) + 1) if R % r == 0)


@functools.partial(jax.jit, static_argnames=("eps", "rows_per_step",
                                             "interpret"))
def _state_update(h, tail, xz, active, w, *, eps, rows_per_step,
                  interpret):
    """The Pallas step: `w` the STEP_WEIGHTS of one layer. A jit of
    its own, so the layers of a decode program share one trace and one
    Mosaic lowering of the body."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    R, N, rows, _ = h.shape
    Dn = rows * _LANES
    K = w["conv_w"].shape[0]
    Rk = w["dt_proj"].shape[1]
    Rb = rows_per_step
    dtype = xz.dtype
    G = 16 if Rb % 16 == 0 else 8 if Rb % 8 == 0 else Rb   # rows a group
    Cw = next(c for c in (1024, 640, 512, 384, 256, 128) if Dn % c == 0)
    nt = (((1,), (1,)), ((), ()))           # a @ b.T
    act = active.astype(jnp.int32)
    row = lambda v, dt: v.astype(dt).reshape(1, -1)  # noqa: E731

    def kernel(h_ref, tail_ref, xr_ref, z_ref, act_ref, live_ref, cw_ref,
               cb_ref, xp_ref, dtn_ref, bn_ref, cn_ref, dtp_ref, dtb_ref,
               a_ref, d_ref, ho_ref, tailo_ref, g_ref, a_scr, dt_scr,
               dtx_scr, y_scr, xc_scr, b_vm, c_vm, b_sm, c_sm, sem):
        base = pl.program_id(0) * Rb
        lane = lambda c, n=Cw: slice(c, c + n)  # noqa: E731

        # (1) the convolution's K taps and silu, rows on the sublanes;
        # the tail moves up by one tap where the row is live
        def conv(gi, _):
            sl = pl.ds(pl.multiple_of(gi * G, G), G)
            live = live_ref[sl, :] != 0                      # (G, 1)
            for c in range(0, Dn, Cw):
                taps = [tail_ref[sl, lane(j * Dn + c)].astype(f32)
                        for j in range(K - 1)]
                taps.append(xr_ref[sl, lane(c)].astype(f32))
                acc = cb_ref[:, lane(c)]
                for j in range(K):
                    acc = acc + taps[j] * cw_ref[j:j + 1, lane(c)]
                xc_scr[sl, lane(c)] = jax.nn.silu(acc).astype(dtype)
                for j in range(K - 1):
                    tailo_ref[sl, lane(j * Dn + c)] = jnp.where(
                        live, taps[j + 1], taps[j]).astype(dtype)

        jax.lax.fori_loop(0, Rb // G, conv, None)

        # (2) x_proj on the MXU over the block's rows, Jamba's three
        # norms; B and C go to SMEM, where the step splats them from
        xc = xc_scr[...]
        p = jax.lax.dot_general(xc, xp_ref[...], nt,
                                preferred_element_type=f32)

        def norm(v, g_ref):
            ms = jnp.mean(v * v, axis=-1, keepdims=True)
            return v * jax.lax.rsqrt(ms + eps) * g_ref[...].astype(f32)

        dt_r = norm(p[:, :Rk], dtn_ref).astype(dtype)
        b_vm[...] = norm(p[:, Rk:Rk + N], bn_ref)
        c_vm[...] = norm(p[:, Rk + N:], cn_ref)
        to_smem = [pltpu.make_async_copy(b_vm, b_sm, sem.at[0]),
                   pltpu.make_async_copy(c_vm, c_sm, sem.at[1])]
        for cp in to_smem:
            cp.start()

        # (3) dt_proj, bias, softplus; dt and dt * x re-laid from rows
        # on the sublanes to a row's (rows, 128) tile, where the state
        # lives: a chunk of 128 channels of every row lands `rows`
        # sublanes apart
        for c in range(0, Dn, Cw):
            dt = jax.nn.softplus(
                jnp.dot(dt_r, dtp_ref[:, lane(c)],
                        preferred_element_type=f32)
                + dtb_ref[:, lane(c)])
            dtx = dt * xc_scr[:, lane(c)].astype(f32)
            for j in range(c // _LANES, (c + Cw) // _LANES):
                at = lane(j * _LANES - c, _LANES)
                dt_scr[pl.ds(j, Rb, stride=rows), :] = dt[:, at]
                dtx_scr[pl.ds(j, Rb, stride=rows), :] = dtx[:, at]
        a = -jnp.exp(a_ref[...])                             # (N, Dn)
        for j in range(rows):
            a_scr[pl.ds(j, N, stride=rows), :] = a[:, lane(j * _LANES,
                                                           _LANES)]
        for cp in to_smem:
            cp.wait()

        # (4) the recurrence, a row at a time on its own tile
        def step(r, _):
            live = act_ref[base + r] != 0
            at = pl.ds(pl.multiple_of(r * rows, math.gcd(rows, 8)), rows)

            @pl.when(live)
            def _step():
                dt_r = dt_scr[at, :]                         # (rows, 128)
                dtx = dtx_scr[at, :]
                y = jnp.zeros_like(dt_r)
                for n in range(N):
                    hn = jnp.exp(dt_r * a_scr[n * rows:(n + 1) * rows]) \
                        * h_ref[r, n] + dtx * b_sm[r, n]
                    y = y + hn * c_sm[r, n]
                    ho_ref[r, n] = hn
                y_scr[at, :] = y

            @pl.when(jnp.logical_not(live))
            def _keep():
                ho_ref[r] = h_ref[r]
                y_scr[at, :] = jnp.zeros((rows, _LANES), f32)

        jax.lax.fori_loop(0, Rb, step, None)

        # (5) the gate, y back on rows-on-sublanes
        for j in range(rows):
            at = lane(j * _LANES, _LANES)
            y = y_scr[pl.ds(j, Rb, stride=rows), :]
            g = (y + d_ref[:, at] * xc_scr[:, at].astype(f32)) \
                * jax.nn.silu(z_ref[:, at].astype(f32))
            g_ref[:, at] = g.astype(dtype)

    state = pl.BlockSpec((Rb, N, rows, _LANES), lambda i: (i, 0, 0, 0))
    taps = pl.BlockSpec((Rb, (K - 1) * Dn), lambda i: (i, 0))
    half = lambda k: pl.BlockSpec((Rb, Dn), lambda i: (i, k))  # noqa: E731
    whole = lambda v: pl.BlockSpec(v.shape, lambda i: (0,) * v.ndim)  # noqa: E731
    # dt_proj goes in as (dt_rank, Dn): on the chip a (Dn, dt_rank)
    # array lies with Dn minor (XLA's layout where the last dimension
    # is no multiple of 128), so the transpose is a bitcast and the
    # array as it is would be copied row-major for every call
    weights = [w["conv_w"].astype(f32), row(w["conv_b"], f32),
               w["x_proj"].astype(dtype), row(w["dt_norm"], dtype),
               row(w["b_norm"], dtype), row(w["c_norm"], dtype),
               w["dt_proj"].T.astype(dtype), row(w["dt_bias"], f32),
               w["A_log"].astype(f32), row(w["D"], f32)]
    item = jnp.dtype(dtype).itemsize
    blocks = Rb * (2 * N * Dn * 4 + (2 * (K - 1) + 3) * Dn * item)
    held = sum(v.size * v.dtype.itemsize for v in weights)
    scratch = (N + 3 * Rb) * Dn * 4 + Rb * Dn * item
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=int(2 * blocks + 2 * held + scratch
                                 + (8 << 20)))}
    ho, tail_o, g = pl.pallas_call(
        kernel,
        grid=(R // Rb,),
        in_specs=[state, taps, half(0), half(1),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((Rb, 1), lambda i: (i, 0))]
        + [whole(v) for v in weights],
        out_specs=[state, taps, half(0)],
        out_shape=[jax.ShapeDtypeStruct(h.shape, f32),
                   jax.ShapeDtypeStruct(tail.shape, tail.dtype),
                   jax.ShapeDtypeStruct((R, Dn), dtype)],
        scratch_shapes=[pltpu.VMEM((N * rows, _LANES), f32),
                        pltpu.VMEM((Rb * rows, _LANES), f32),
                        pltpu.VMEM((Rb * rows, _LANES), f32),
                        pltpu.VMEM((Rb * rows, _LANES), f32),
                        pltpu.VMEM((Rb, Dn), dtype),
                        pltpu.VMEM((Rb, N), f32), pltpu.VMEM((Rb, N), f32),
                        pltpu.SMEM((Rb, N), f32), pltpu.SMEM((Rb, N), f32),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
        name="ssm_state_update",
        **params,
    )(h, tail, xz, xz, act, act[:, None], *weights)
    return g, ho, tail_o


def ssm_state_update(h, tail, xz, active, w, eps, use_kernel=True):
    """(g, h', tail') of one token for the rows `active`, everything a
    recurrent layer does between in_proj and out_proj; the other rows
    keep state and tail, and their g is nobody's. `h` (R,) + state_shape
    float32 and `tail` (R,) + tail_shape are updated in place where
    the caller donates them; `w` holds a layer's STEP_WEIGHTS."""
    return _step_fallback.run(
        kernel_mode("SCAN", h,
                    ok=use_kernel and h.dtype == jnp.float32),
        lambda interpret: _state_update(
            h, tail, xz, active, {k: w[k] for k in STEP_WEIGHTS},
            eps=eps, rows_per_step=_rows_per_step(
                h.shape[0], tuning.get("ssm_state_update", "rows")),
            interpret=interpret),
        lambda: ssm_state_update_ref(h, tail, xz, active, w, eps))
