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
- `ssm_state_update` (decode): one call a layer for every row of the
  tick. The state pool goes in and comes out ALIASED
  (`input_output_aliases`), a block of rows a grid step; a row whose
  `active` is 0 is written back as it was. HBM bound: the state is
  read and written once, 2 x N x d_inner x 4 bytes a row.

There is no backward: nothing trains through these kernels.
Each has a jnp twin (`*_ref`) that is the CPU path and the tests'
yardstick; `MXNET_TPU_SCAN_INTERPRET=1` traces the kernels under the
Pallas interpreter.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from . import tuning
from .dispatch import KernelFallback, operand_on_cpu

__all__ = ["selective_scan", "selective_scan_ref", "ssm_state_update",
           "ssm_state_update_ref", "state_shape"]

_scan_fallback = KernelFallback("selective-scan",
                                strict_envs=("MXNET_TPU_STRICT_SCAN",))
_step_fallback = KernelFallback("ssm-state-update",
                                strict_envs=("MXNET_TPU_STRICT_SCAN",))

_LANES = 128


def _pallas_mode(operand):
    if os.environ.get("MXNET_TPU_SCAN_INTERPRET", "0") == "1":
        return "interpret"
    if jax.default_backend() not in ("cpu",) \
            and not operand_on_cpu(operand):
        return "compiled"
    return None


def state_shape(n, dn):
    """One sequence's state as the kernels hold it and a pool stores
    it: `(N, d_inner / 128, 128)`, channels on sublanes and lanes. A
    pool kept `(N, d_inner)` would be re-laid-out whole on its way into
    every call (XLA tiles the two minor dims)."""
    if dn % _LANES:
        raise ValueError(f"d_inner {dn} is no whole number of "
                         f"{_LANES}-lane rows")
    return (n, dn // _LANES, _LANES)


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


def ssm_state_update_ref(h, x, dt, a_log, b, c, active):
    """h (R,) + state_shape f32; x, dt (R, Dn) f32; b, c (R, N);
    active (R,) bool. Returns (h', y (R, Dn)); an inactive row keeps
    its state."""
    a = -jnp.exp(a_log.astype(jnp.float32))
    shape = h.shape
    h = h.reshape(shape[0], shape[1], -1)
    new = jnp.exp(dt[:, None, :] * a) * h \
        + (dt * x)[:, None, :] * b[:, :, None]
    y = jnp.sum(new * c[:, :, None], axis=1)
    keep = active[:, None, None]
    return jnp.where(keep, new, h).reshape(shape), \
        jnp.where(active[:, None], y, 0.0)


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
    mode = _pallas_mode(x) if use_kernel else None
    if mode is not None and _channel_rows(x.shape[-1]):
        try:
            return selective_scan_fwd(
                x, dt, a_log, b, c, h0,
                chunk=tuning.get("selective_scan", "time_chunk"),
                interpret=mode == "interpret")
        except Exception as e:
            _scan_fallback.note(e)
    return selective_scan_ref(x, dt, a_log, b, c, h0)


# -- one step for every row of a decode tick -----------------------------------

def _rows_per_step(R, want):
    """The largest divisor of R up to `want`: the state pool is updated
    in place, so its rows cannot be padded to a block."""
    return max(r for r in range(1, min(R, want) + 1) if R % r == 0)


@functools.partial(jax.jit, static_argnames=("rows_per_step",
                                             "interpret"))
def _state_update(h, x, dt, a_log, b, c, active, *, rows_per_step,
                  interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h4 = h
    R, N, rows, _ = h.shape
    Dn = rows * _LANES
    Rb = rows_per_step
    x3 = x.reshape(R, rows, _LANES)
    dt3 = dt.reshape(R, rows, _LANES)
    a3 = a_log.astype(jnp.float32).reshape(N, rows, _LANES)

    def kernel(h_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, act_ref,
               ho_ref, y_ref, a_scr):
        a_scr[...] = -jnp.exp(a_ref[...])
        base = pl.program_id(0) * Rb

        def row(r, _):
            live = act_ref[base + r] != 0

            @pl.when(live)
            def _step():
                dt_r = dt_ref[r]                         # (rows, 128)
                dtx = dt_r * x_ref[r]
                y = jnp.zeros_like(dt_r)
                for n in range(N):
                    hn = jnp.exp(dt_r * a_scr[n]) * h_ref[r, n] \
                        + dtx * b_ref[base + r, n]
                    y = y + hn * c_ref[base + r, n]
                    ho_ref[r, n] = hn
                y_ref[r] = y

            @pl.when(jnp.logical_not(live))
            def _keep():
                ho_ref[r] = h_ref[r]
                y_ref[r] = jnp.zeros_like(y_ref[r])

        jax.lax.fori_loop(0, Rb, row, None)

    state = pl.BlockSpec((Rb, N, rows, _LANES), lambda i: (i, 0, 0, 0))
    vec = pl.BlockSpec((Rb, rows, _LANES), lambda i: (i, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=int(
                4 * Rb * (N + 3) * Dn * 4 + 3 * N * Dn * 4 + (4 << 20)))}
    ho, y = pl.pallas_call(
        kernel,
        grid=(R // Rb,),
        in_specs=[state, vec, vec,
                  pl.BlockSpec((N, rows, _LANES), lambda i: (0, 0, 0)),
                  smem, smem, smem],
        out_specs=[state, vec],
        out_shape=[jax.ShapeDtypeStruct(h4.shape, jnp.float32),
                   jax.ShapeDtypeStruct(x3.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, rows, _LANES), jnp.float32)],
        input_output_aliases={0: 0},
        interpret=interpret,
        name="ssm_state_update",
        **params,
    )(h4, x3, dt3, a3, b, c, active.astype(jnp.int32))
    return ho, y.reshape(R, Dn)


def ssm_state_update(h, x, dt, a_log, b, c, active, use_kernel=True):
    """(h', y) of one step for the rows `active`; the others keep their
    state and read y = 0. `h` (R,) + state_shape, float32, is updated
    in place where the caller donates it."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    mode = _pallas_mode(h) if use_kernel else None
    if mode is not None and h.dtype == f32:
        try:
            return _state_update(
                h, x, dt, a_log, b, c, active,
                rows_per_step=_rows_per_step(
                    h.shape[0], tuning.get("ssm_state_update", "rows")),
                interpret=mode == "interpret")
        except Exception as e:
            _step_fallback.note(e)
    hn, y = ssm_state_update_ref(h.astype(f32), x, dt, a_log, b, c,
                                 active)
    return hn.astype(h.dtype), y
