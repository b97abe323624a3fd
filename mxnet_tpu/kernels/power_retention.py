"""Power-retention kernels (gated linear attention with a degree-2
feature map): the chunked form over a prompt and the one-step state
update of a decode tick.

For one kv head with gate g_t in (0, 1], keys k_t, values v_t (d wide)
and a query head q_t of its group:

    a_{t,s} = (q_t . k_s)^2 * prod_{r=s+1..t} g_r          s <= t
    y_t     = sum_s a_{t,s} v_s / (sum_s a_{t,s} + eps)

and, the same thing as a recurrence over a matrix-valued state,

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

with phi(u) . phi(w) = (u . w)^2. The feature map is kept by CYCLIC
OFFSET, `phi(u)[o, i] = c_o u_i u_{(i - o) mod d}` for o = 0 .. d/2,
c_0 = 1, c_o = sqrt 2 for 0 < o < d/2 and c_{d/2} = 1: offset 0 is the
squares, an offset in between holds every unordered pair at that cyclic
distance once, and offset d/2 holds its d/2 pairs twice at weight 1
(2 * 1^2 = (sqrt 2)^2). That is `O = d/2 + 1` rows of d lanes, 8,320
entries at d = 128 where the d (d + 1) / 2 distinct products are 8,256
(0.8% of padding), and a row of phi is one lane rotation and two
multiplications of the vector itself: nothing is gathered, in the
kernels or outside them.

The state of a kv head is `S (O, d, d)` float32, `S[o, a, i]` the
coefficient of v's entry a against phi's entry (o, i), and `z (O, d)`:
an offset's slab is a (d, d) tile with phi's index on the lanes, so phi
rows enter as sublane broadcasts and the tile is the transposed
right-hand side of an MXU product as it stands (`state_shapes`).

- `power_retention_chunked` (prefill): grid (batch, kv heads, chunks),
  the chunk axis sequential, the state of a head resident in VMEM as
  the output block across a sequence's chunks. Inside a chunk the
  quadratic form with the decay (q k^T on the MXU, squared, times the
  decay mask, times v); across chunks phi(q) against the state and
  phi(k)^T v into it, offset by offset, phi built in VMEM from a lane
  rotation and never written to HBM. MXU bound: 2 * O * d * d FLOPs a
  position for every query head and every kv head. A position whose key
  is 0 and whose log-gate is 0 leaves the state as it was: the caller
  zeroes both on right padding.
- `power_retention_step` (decode): one call a layer for every row of
  the tick. The state pool goes in and comes out ALIASED, one kv head
  of one row (its whole state, 4.3 MB at d = 128) a grid step; the step
  applies the gate, adds phi(k) v^T, answers the group's query heads from the
  NEW state on the VPU (a 32-row slab of an offset's tile is updated,
  stored and multiplied into each head's accumulator while it is in
  registers) and writes the state back once. A row whose `active` is 0
  is copied through bit for bit. HBM bound: the state read and written
  once, 2 * K * O * d * (d + 1) * 4 bytes a row.

There is no backward. Each kernel has a jnp twin (`*_ref`, the
recurrence as written above) that is the CPU path and the tests'
yardstick; `MXNET_TPU_SCAN_INTERPRET=1` traces the kernels under the
Pallas interpreter (the recurrent kernels share the switch).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import tuning
from .dispatch import KernelFallback, kernel_mode

__all__ = ["power_retention_chunked", "power_retention_chunked_ref",
           "power_retention_step", "power_retention_step_ref",
           "state_shapes", "feature_map", "EPS"]

_chunked_fallback = KernelFallback("power-retention-chunked", "SCAN")
_step_fallback = KernelFallback("power-retention-step", "SCAN")

#: the normaliser's floor where the caller gives none (a model passes its
#: configuration's `retention_eps`)
EPS = 1e-6
_LANES = 128
_SQRT2 = math.sqrt(2.0)


def _offsets(d):
    if d % 2:
        raise ValueError(f"head_dim {d} is odd: the feature map pairs "
                         "entries at cyclic offsets up to d / 2")
    return d // 2 + 1


def _z_rows(O):
    """Rows z is stored with: O up to a whole sublane tile (the rows
    past O stay 0). At 65 rows XLA would lay a pool of z out with the
    kv heads minor to the offsets and re-lay it in and out of every
    call."""
    return -(-O // 8) * 8


def state_shapes(num_kv_heads, d):
    """{name: (shape, dtype)} of one sequence's state in one layer, as
    the kernels hold it and a pool stores it."""
    O = _offsets(d)
    return {"S": ((num_kv_heads, O, d, d), jnp.float32),
            "z": ((num_kv_heads, _z_rows(O), d), jnp.float32)}


def _coef(o, d):
    """c_o for a traced or a Python offset."""
    if isinstance(o, int):
        return 1.0 if o in (0, d // 2) else _SQRT2
    return jnp.where((o == 0) | (o == d // 2), 1.0, _SQRT2) \
        .astype(jnp.float32)


def feature_map(u):
    """phi of (..., d) -> (..., O, d) float32, by cyclic offset."""
    d = u.shape[-1]
    o = jnp.arange(_offsets(d))
    u = u.astype(jnp.float32)
    idx = (jnp.arange(d)[None, :] - o[:, None]) % d          # (O, d)
    return _coef(o, d)[:, None] * u[..., None, :] * u[..., idx]


# -- jnp twins ---------------------------------------------------------------

def _to_z_rows(pk):
    O = pk.shape[-2]
    return jnp.pad(pk, [(0, 0)] * (pk.ndim - 2)
                   + [(0, _z_rows(O) - O), (0, 0)])


def _answer(pq, S, z, eps):
    """y of the group's heads from a state: pq (..., K, G, O, d), S
    (..., K, O, d, d), z (..., K, O up to a tile, d) -> (..., K, G, d)."""
    # (float32 products whatever the backend's default: a TPU would
    # round phi and the state to bfloat16 here)
    num = jnp.einsum("...kgoi,...koai->...kga", pq, S,
                     precision=jax.lax.Precision.HIGHEST)
    den = jnp.einsum("...kgoi,...koi->...kg", pq,
                     z[..., :pq.shape[-2], :],
                     precision=jax.lax.Precision.HIGHEST)
    return num / (den[..., None] + eps)


def power_retention_chunked_ref(q, k, v, log_g, eps=EPS):
    """The recurrence over (B, T), from a zero state: q (B, T, H, d);
    k, v (B, T, K, d); log_g (B, T, K) float32. Returns y (B, T, H, d)
    float32 and the final state {"S", "z"}."""
    B, T, H, d = q.shape
    K = k.shape[2]
    G = H // K
    shapes = state_shapes(K, d)
    state = tuple(jnp.zeros((B,) + shapes[n][0], jnp.float32)
                  for n in ("S", "z"))

    def step(carry, inp):
        S, z = carry
        q_t, k_t, v_t, lg_t = inp
        g = jnp.exp(lg_t)
        pk = feature_map(k_t)                                # (B,K,O,d)
        S = g[..., None, None, None] * S \
            + pk[..., None, :] * v_t.astype(jnp.float32)[:, :, None, :,
                                                         None]
        z = g[..., None, None] * z + _to_z_rows(pk)
        pq = feature_map(q_t.reshape(B, K, G, d))
        return (S, z), _answer(pq, S, z, eps).reshape(B, H, d)

    seq = (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
           jnp.moveaxis(v, 1, 0),
           jnp.moveaxis(log_g.astype(jnp.float32), 1, 0))
    (S, z), y = jax.lax.scan(step, state, seq)
    return jnp.moveaxis(y, 0, 1), {"S": S, "z": z}


def power_retention_step_ref(S, z, q, k, v, log_g, active, eps=EPS):
    """One step for every row: S (R, K, O, d, d), z (R, K, O, d)
    float32; q (R, H, d); k, v (R, K, d); log_g (R, K); active (R,)
    bool. Returns (S', z', y (R, H, d) float32); an inactive row keeps
    its state and reads y = 0."""
    R, H, d = q.shape
    K = k.shape[1]
    g = jnp.exp(log_g.astype(jnp.float32))
    pk = feature_map(k)
    Sn = g[..., None, None, None] * S \
        + pk[..., None, :] * v.astype(jnp.float32)[:, :, None, :, None]
    zn = g[..., None, None] * z + _to_z_rows(pk)
    y = _answer(feature_map(q.reshape(R, K, H // K, d)), Sn, zn, eps) \
        .reshape(R, H, d)
    return (jnp.where(active[:, None, None, None, None], Sn, S),
            jnp.where(active[:, None, None, None], zn, z),
            jnp.where(active[:, None, None], y, 0.0))


# -- the chunked form over a prompt --------------------------------------------

def _lane_roll(interpret):
    """`roll(a, o)`: a's lanes rotated by o (traced or not), jnp.roll's
    way; Mosaic's rotate where the kernel is compiled."""
    if interpret:
        return lambda a, o: jnp.roll(a, o, axis=1)
    from jax.experimental.pallas import tpu as pltpu
    return lambda a, o: pltpu.roll(a, o, 1)


def _dot_nt(a, b):
    """a (M, n) against b (N, n), contracted over the minor dim of
    both: the MXU takes the right-hand side transposed as it stands."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "eps", "interpret"))
def power_retention_chunked_fwd(q, k, v, log_g, *, chunk, eps, interpret):
    """The Pallas chunked form. A jit of its own, so the layers of a
    prefill program share one trace and one Mosaic lowering."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, d = q.shape
    K = k.shape[2]
    G = H // K
    O = _offsets(d)
    C = min(chunk, -(-T // 8) * 8)
    Tp = -(-T // C) * C
    nc = Tp // C
    if Tp != T:     # key 0 and log-gate 0: the state stands still
        q, k, v = (jnp.pad(a, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
                   for a in (q, k, v))
        log_g = jnp.pad(log_g, ((0, 0), (0, Tp - T), (0, 0)))
    # the log-gates summed inside each chunk, as a column (for the
    # rows t) and as a row (for the columns s)
    gcs = jnp.cumsum(jnp.moveaxis(log_g.astype(jnp.float32), 2, 1)
                     .reshape(B, K, nc, C), axis=-1)
    mx = q.dtype
    roll = _lane_roll(interpret)

    def kernel(gend_ref, q_ref, k_ref, v_ref, gcol_ref, grow_ref, y_ref,
               S_ref, z_ref, qs_scr, num_scr, dint_scr, numi_scr,
               deni_scr):
        @pl.when(pl.program_id(2) == 0)
        def _first_chunk():
            S_ref[...] = jnp.zeros_like(S_ref)
            z_ref[...] = jnp.zeros_like(z_ref)

        kk, vv = k_ref[...], v_ref[...]
        gc, gr = gcol_ref[...], grow_ref[...]            # (C, 1), (1, C)
        # the chunk's whole log-gate and its exponential: scalars
        at = (pl.program_id(0), pl.program_id(1), pl.program_id(2))
        g_end, e_end = gend_ref[(0,) + at], gend_ref[(1,) + at]
        from_start = jnp.exp(gc)                         # S_prev's weight at t
        to_end = jnp.exp(g_end - gc)                     # s's weight in S_end
        tt = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        ss = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        decay = jnp.where(ss <= tt, jnp.exp(jnp.minimum(gc - gr, 0.0)),
                          0.0)
        # inside the chunk: the quadratic form, a head at a time
        for h in range(G):
            rows = slice(h * C, (h + 1) * C)
            qh = q_ref[:, h * d:(h + 1) * d]
            qs_scr[rows, :] = qh.astype(jnp.float32)
            sc = _dot_nt(qh, kk)
            a = sc * sc * decay
            num_scr[rows, :] = jnp.dot(
                a.astype(mx), vv, preferred_element_type=jnp.float32)
            dint_scr[rows, :] = jnp.sum(a, axis=1, keepdims=True)
        numi_scr[...] = jnp.zeros_like(numi_scr)
        deni_scr[...] = jnp.zeros_like(deni_scr)
        k32 = kk.astype(jnp.float32)
        vw_t = (vv.astype(jnp.float32) * to_end).T.astype(mx)   # (d, C)

        # across chunks: phi(q) against the state as the chunk found
        # it, then phi(k)^T v into it, an offset at a time
        def offset(o, carry):
            co = _coef(o, d)
            s_o = S_ref[o]                                   # (d, d)
            z_o = z_ref[pl.ds(o, 1), :]                      # (1, d)
            qs = qs_scr[...]
            pq = co * (qs * roll(qs, o))                     # (G C, d)
            numi_scr[...] += _dot_nt(pq.astype(mx), s_o.astype(mx))
            deni_scr[...] += pq * z_o
            pk = co * (k32 * roll(k32, o))                   # (C, d)
            S_ref[o] = e_end * s_o + jnp.dot(
                vw_t, pk.astype(mx), preferred_element_type=jnp.float32)
            z_ref[pl.ds(o, 1), :] = e_end * z_o + jnp.sum(
                pk * to_end, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, O, offset, None)
        for h in range(G):
            rows = slice(h * C, (h + 1) * C)
            num = num_scr[rows, :] + from_start * numi_scr[rows, :]
            den = dint_scr[rows, :] + from_start * jnp.sum(
                deni_scr[rows, :], axis=1, keepdims=True)
            y_ref[:, h * d:(h + 1) * d] = (num / (den + eps)).astype(
                y_ref.dtype)

    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=int(
                2 * O * d * (d + 1) * 4          # the state, two buffers
                + 5 * G * C * max(d, _LANES) * 4  # the five scratches
                + 8 * C * (G + 2) * d * 4        # q, k, v, y and values
                + 6 * C * C * 4 + (8 << 20)))}
    y, S, z = pl.pallas_call(
        kernel,
        grid=(B, K, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, C, G * d), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((None, C, d), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((None, C, d), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((None, None, C, 1),
                         lambda b, j, c: (b, j, c, 0)),
            pl.BlockSpec((None, None, None, 1, C),
                         lambda b, j, c: (b, j, c, 0, 0))],
        out_specs=[
            pl.BlockSpec((None, C, G * d), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((None, None, O, d, d),
                         lambda b, j, c: (b, j, 0, 0, 0)),
            pl.BlockSpec((None, None, _z_rows(O), d),
                         lambda b, j, c: (b, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, H * d), q.dtype),
                   jax.ShapeDtypeStruct((B, K, O, d, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, K, _z_rows(O), d),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((G * C, d), jnp.float32),
                        pltpu.VMEM((G * C, d), jnp.float32),
                        pltpu.VMEM((G * C, 1), jnp.float32),
                        pltpu.VMEM((G * C, d), jnp.float32),
                        pltpu.VMEM((G * C, d), jnp.float32)],
        interpret=interpret,
        name="power_retention_chunked",
        **params,
    )(jnp.stack([gcs[..., -1], jnp.exp(gcs[..., -1])]),
      q.reshape(B, Tp, H * d), k.reshape(B, Tp, K * d),
      v.reshape(B, Tp, K * d), gcs.reshape(B, K, Tp, 1),
      gcs.reshape(B, K, nc, 1, C))
    return y.reshape(B, Tp, H, d)[:, :T], {"S": S, "z": z}


def power_retention_chunked(q, k, v, log_g, use_kernel=True, eps=EPS):
    """y (B, T, H, d) in q's dtype and the final state of the
    recurrence over (B, T) from a zero state: the Pallas chunked form
    where the gate admits it, else the jnp twin. The products on the
    MXU take their operands in q's dtype (bfloat16 at the served model:
    phi and the state are rounded once where they enter a product, the
    state itself is kept and accumulated in float32)."""
    def twin():
        y, state = power_retention_chunked_ref(q, k, v, log_g, eps)
        return y.astype(q.dtype), state

    return _chunked_fallback.run(
        # compiled, the kernels slice the state in whole lane rows
        kernel_mode("SCAN", q, ok=use_kernel,
                    ok_compiled=q.shape[-1] % _LANES == 0),
        lambda interpret: power_retention_chunked_fwd(
            q, k.astype(q.dtype), v.astype(q.dtype), log_g,
            chunk=tuning.get("power_retention_chunked", "chunk"),
            eps=eps, interpret=interpret),
        twin)


# -- one step for every row of a decode tick -----------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _step(S, z, q, k, v, log_g, active, *, eps, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, K, O, d, _ = S.shape
    H = q.shape[1]
    G = H // K
    GP = -(-(G + 1) // 8) * 8       # the heads and the key, a sublane tile
    CH = min(32, d)                 # rows of an offset's tile in registers
    f32 = jnp.float32
    # the group's queries, then the key, rows of one tile
    qk = jnp.concatenate(
        [q.astype(f32).reshape(R, K, G, d),
         k.astype(f32).reshape(R, K, 1, d),
         jnp.zeros((R, K, GP - G - 1, d), f32)], axis=2)
    vcol = v.astype(f32).reshape(R, K, d, 1)
    gate = jnp.exp(log_g.astype(f32))
    roll = _lane_roll(interpret)

    def kernel(act_ref, g_ref, qk_ref, v_ref, S_ref, z_ref, So_ref,
               zo_ref, y_ref, phi_scr, acc_scr):
        r, j = pl.program_id(0), pl.program_id(1)
        live = act_ref[r] != 0

        @pl.when(jnp.logical_not(live))
        def _keep():
            So_ref[...] = S_ref[...]
            zo_ref[...] = z_ref[...]
            y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(live)
        def _live():
            g = g_ref[r, j]
            u = qk_ref[...]                                  # (GP, d)
            for o in range(O):
                phi_scr[o] = _coef(o, d) * (u * roll(u, o))
            zo_ref[...] = z_ref[...]        # the rows past O with it

            # z whole, and each head's normaliser from the new z
            def z_row(o, den):
                p = phi_scr[o]
                zn = g * z_ref[pl.ds(o, 1), :] + p[G:G + 1, :]
                zo_ref[pl.ds(o, 1), :] = zn
                return den + p * zn

            den = jnp.sum(jax.lax.fori_loop(
                0, O, z_row, jnp.zeros((GP, d), f32)),
                axis=1, keepdims=True)                       # (GP, 1)

            for c in range(d // CH):
                rows = slice(c * CH, (c + 1) * CH)
                vc = jnp.broadcast_to(v_ref[rows, :], (CH, d))

                def slab(o, accs):
                    p = phi_scr[o]
                    s = g * S_ref[o, rows, :] + vc * p[G:G + 1, :]
                    So_ref[o, rows, :] = s
                    return tuple(a + s * p[h:h + 1, :]
                                 for h, a in enumerate(accs))

                accs = jax.lax.fori_loop(
                    0, O, slab, (jnp.zeros((CH, d), f32),) * G)
                for h in range(G):
                    acc_scr[h, rows, :] = accs[h]

            for h in range(G):
                num = jnp.sum(acc_scr[h].T, axis=0, keepdims=True)
                y_ref[h:h + 1, :] = num / (den[h:h + 1, :] + eps)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    # a kv head's whole state a grid step: every smaller block of its
    # offsets was slower on the chip (tuned.json's note)
    s_spec = pl.BlockSpec((None, None, O, d, d),
                          lambda r, j: (r, j, 0, 0, 0))
    z_spec = pl.BlockSpec((None, None, z.shape[2], d),
                          lambda r, j: (r, j, 0, 0))
    small = lambda rows, cols: pl.BlockSpec(      # noqa: E731
        (None, None, rows, cols), lambda r, j: (r, j, 0, 0))
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=int(4 * O * d * d * 4 + 4 * O * d * 4
                                 + (O * GP + (G + GP) * d) * d * 4
                                 + (8 << 20)))}
    So, zo, y = pl.pallas_call(
        kernel,
        grid=(R, K),
        in_specs=[smem, smem, small(GP, d), small(d, 1), s_spec, z_spec],
        out_specs=[s_spec, z_spec, small(G, d)],
        out_shape=[jax.ShapeDtypeStruct(S.shape, f32),
                   jax.ShapeDtypeStruct(z.shape, f32),
                   jax.ShapeDtypeStruct((R, K, G, d), f32)],
        scratch_shapes=[pltpu.VMEM((O, GP, d), f32),
                        pltpu.VMEM((G, d, d), f32)],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        name="power_retention_step",
        **params,
    )(active.astype(jnp.int32), gate, qk, vcol, S, z)
    return So, zo, y.reshape(R, H, d)


def power_retention_step(S, z, q, k, v, log_g, active, use_kernel=True,
                         eps=EPS):
    """(S', z', y) of one step for the rows `active`; the others keep
    their state and read y = 0. `S`, `z` (R,) + state_shapes, float32,
    are updated in place where the caller donates them; y (R, H, d) in
    q's dtype."""
    def kernel(interpret):
        Sn, zn, y = _step(S, z, q, k, v, log_g, active, eps=eps,
                          interpret=interpret)
        return Sn, zn, y.astype(q.dtype)

    def twin():
        Sn, zn, y = power_retention_step_ref(
            S.astype(jnp.float32), z.astype(jnp.float32), q, k, v,
            log_g, active, eps)
        return Sn.astype(S.dtype), zn.astype(z.dtype), y.astype(q.dtype)

    return _step_fallback.run(
        kernel_mode("SCAN", S,
                    ok=use_kernel and S.dtype == jnp.float32,
                    ok_compiled=q.shape[-1] % _LANES == 0),
        kernel, twin)
