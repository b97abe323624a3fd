"""Grouped matmul for a sparse expert layer: rows sorted by expert,
each expert's rows in whole tiles, one weight matrix a group.

    out[r] = lhs[r] @ rhs[group of r's tile]            (rhs2 is None)
    out[r] = silu(lhs[r] @ rhs[g]) * (lhs[r] @ rhs2[g])   (SwiGLU's
             first half: gate and up in one pass over the rows)

The caller (`parallel/moe.py::held_expert_ffn`) lays the rows out: a
group's rows start on a tile boundary, `tile_group[i]` names the group
of row tile i and `n_tiles` says how many tiles hold rows. The grid is
as long as the worst case (every pair on a held expert); a step past
`n_tiles` computes nothing and names the blocks of the last live step,
so it moves nothing either. HBM reads therefore follow the experts that
were touched: in decode a held expert sees a handful of rows and the
call is bound by reading its matrices, in prefill by the MXU.

The Pallas path runs compiled on a TPU and interpreted under
`MXNET_TPU_MOE_INTERPRET=1`; elsewhere the same layout goes through
`jax.lax.ragged_dot`. A kernel failure on a TPU raises
(kernels/dispatch.py).

Both paths differentiate. `ragged_dot` brings its own transposes; the
Pallas call is a `custom_vjp`: the rows' gradient is the same kernel
against the matrices read transposed (`transpose_rhs`: the index map
swaps the block's axes, nothing is copied; SwiGLU's two halves come
back as one sum, `lhs2`), the matrices' gradient a second kernel,
`moe_grouped_matmul_wgrad`: x_g^T @ dy_g summed over each group's row
tiles into (G, K, N), zero for a group no row reached. The fused SwiGLU
pass saves its two pre-activations when it is differentiated (two more
stores of the tile it already holds). Rows of live tiles that no pair
owns must carry a zero cotangent (the layer's combine gives them one):
they are summed into the matrices' gradient like any other row. An
undifferentiated call traces the program it always did.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import tuning
from .dispatch import KernelFallback, float0_like, kernel_mode

__all__ = ["grouped_matmul", "reference_grouped_matmul",
           "grouped_matmul_mode", "row_tile"]

_fallback = KernelFallback("moe-grouped-matmul", "MOE")


def _tile(n, want):
    """Largest tile <= want that divides n in whole 128-lane columns,
    or n itself when it is no larger than want."""
    if n <= want:
        return n
    t = want - want % 128
    while t >= 128 and n % t:
        t -= 128
    return t if t >= 128 else n


def row_tile(rows):
    """Rows a tile for a layer that lays out `rows` (token, expert)
    pairs at worst: a decode tick's handful in tiles of 16, a prefill
    chunk's in MXU-high tiles of 128, a training chunk's in tiles that
    read an expert's matrices once for `block_m_large` rows — at 128
    rows a tile the product is bound by reading them again."""
    if rows <= 1024:
        return 16
    if rows < tuning.get("moe_grouped_matmul", "large_rows"):
        return 128
    return tuning.get("moe_grouped_matmul", "block_m_large")


def _blocks(K, N, tm):
    """(tk, tn) of a call whose row tiles are `tm` high."""
    if tm >= tuning.get("moe_grouped_matmul", "block_m_large"):
        want_k = want_n = tuning.get("moe_grouped_matmul", "block_large")
    else:
        want_k, want_n = (tuning.get("moe_grouped_matmul", k)
                          for k in ("block_k", "block_n"))
    return _tile(K, want_k), _tile(N, want_n)


def _params(interpret, vmem_bytes):
    """A step past the rows leans on the blocks the last live step
    left: in order, on one core. VMEM is asked for where a step's
    blocks outgrow Mosaic's 16 MiB default (the training tiles)."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    kw = {"dimension_semantics": ("arbitrary",) * 3}
    if vmem_bytes > (12 << 20):
        kw["vmem_limit_bytes"] = min(vmem_bytes * 3 // 2, 100 << 20)
    return {"compiler_params": pltpu.CompilerParams(**kw)}


def grouped_matmul_mode(operand):
    """None (ragged_dot), 'interpret' or 'compiled'."""
    return kernel_mode("MOE", operand)


def reference_grouped_matmul(lhs, rhs, tile_group, n_tiles, tm,
                             rhs2=None):
    """The same product through XLA's ragged_dot: group sizes are read
    back from the tile list (a group's tiles are adjacent)."""
    G = rhs.shape[0]
    live = jnp.arange(tile_group.shape[0]) < n_tiles
    sizes = jnp.zeros((G,), jnp.int32).at[tile_group].add(
        jnp.where(live, tm, 0))
    out = jax.lax.ragged_dot(lhs, rhs, sizes,
                             preferred_element_type=jnp.float32)
    if rhs2 is not None:
        out = jax.nn.silu(out) * jax.lax.ragged_dot(
            lhs, rhs2, sizes, preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)


@functools.partial(jax.jit, static_argnames=(
    "tm", "interpret", "transpose_rhs", "save_pre"))
def _grouped_matmul_pallas(lhs, rhs, rhs2, tile_group, n_tiles, *, tm,
                           interpret, lhs2=None, transpose_rhs=False,
                           save_pre=False):
    """A jit of its own: the layers of a program share one trace and
    one Mosaic lowering. `rhs2` alone: SwiGLU's first half (with
    `save_pre` the two pre-activations come back beside it); `lhs2` and
    `rhs2`: lhs @ rhs + lhs2 @ rhs2; `transpose_rhs`: the matrices are
    (G, N, K) and read transposed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    two = rhs2 is not None
    fused = two and lhs2 is None
    tk, tn = _blocks(K, N, tm)
    nk, nj = K // tk, N // tn
    n_out = 3 if save_pre else 1
    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))

    def dot(a, b):
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32)

    def kernel(tg_ref, nt_ref, *refs):
        n_in = 2 + 2 * two - fused
        ins, outs = refs[:n_in], refs[n_in:n_in + n_out]
        accs = refs[n_in + n_out:]
        lhs_ref, rhs_ref = ins[0], ins[1]
        acc_ref = accs[0]
        k = pl.program_id(2)

        @pl.when(pl.program_id(0) < nt_ref[0])
        def _live():
            @pl.when(k == 0)
            def _zero():
                for a in accs:
                    a[...] = jnp.zeros_like(a)

            a = lhs_ref[...]
            acc_ref[...] += dot(a, rhs_ref[...])
            if fused:
                accs[1][...] += dot(a, ins[2][...])
            elif two:
                acc_ref[...] += dot(ins[2][...], ins[3][...])

            @pl.when(k == nk - 1)
            def _store():
                r = acc_ref[...]
                if fused:
                    if save_pre:
                        outs[1][...] = r.astype(outs[1].dtype)
                        outs[2][...] = accs[1][...].astype(outs[2].dtype)
                    r = r * jax.nn.sigmoid(r) * accs[1][...]
                outs[0][...] = r.astype(outs[0].dtype)

    def held(i, j, k, nt):
        """The step's block indices, frozen at the last live step's
        once the rows have run out."""
        on = i < nt[0]
        last = jnp.maximum(nt[0] - 1, 0)
        return (jnp.where(on, i, last), jnp.where(on, j, nj - 1),
                jnp.where(on, k, nk - 1))

    def lhs_map(i, j, k, tg, nt):
        i, _, k = held(i, j, k, nt)
        return i, k

    def rhs_map(i, j, k, tg, nt):
        i, j, k = held(i, j, k, nt)
        return (tg[i], j, k) if transpose_rhs else (tg[i], k, j)

    def out_map(i, j, k, tg, nt):
        i, j, _ = held(i, j, k, nt)
        return i, j

    lhs_spec = pl.BlockSpec((tm, tk), lhs_map)
    rhs_spec = pl.BlockSpec((None, tn, tk) if transpose_rhs
                            else (None, tk, tn), rhs_map)
    out_spec = pl.BlockSpec((tm, tn), out_map)
    if fused:
        in_specs, operands = [lhs_spec, rhs_spec, rhs_spec], \
            (lhs, rhs, rhs2)
    elif two:
        in_specs, operands = [lhs_spec, rhs_spec] * 2, \
            (lhs, rhs, lhs2, rhs2)
    else:
        in_specs, operands = [lhs_spec, rhs_spec], (lhs, rhs)
    n_acc = 2 if fused else 1
    item = lhs.dtype.itemsize
    vmem = 2 * item * (len(in_specs) // 2 * tm * tk
                       + (len(in_specs) + 1) // 2 * tk * tn
                       + n_out * tm * tn) + n_acc * 4 * tm * tn
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // tm, nj, nk),
        in_specs=in_specs,
        out_specs=[out_spec] * n_out if save_pre else out_spec,
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * n_acc)
    out_shape = jax.ShapeDtypeStruct((M, N), lhs.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[out_shape] * n_out if save_pre else out_shape,
        interpret=interpret,
        name="moe_grouped_matmul",
        **_params(interpret, vmem),
    )(tile_group.astype(jnp.int32),
      jnp.reshape(n_tiles, (1,)).astype(jnp.int32), *operands)


@functools.partial(jax.jit, static_argnames=("n_groups", "tm",
                                             "interpret"))
def _grouped_wgrad_pallas(lhs, dout, tile_group, n_tiles, n_groups, *,
                          tm, interpret):
    """The matrices' gradient: out[g] = sum over g's row tiles of
    lhs_i^T @ dout_i, (G, K, N) in lhs's type, zero for a group with no
    tile. The row tiles are the grid's LAST axis: a group's tiles are
    adjacent, so its (tk, tn) block of the result gathers in a float32
    scratch while they pass and is stored with the group's last."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = lhs.shape
    N = dout.shape[1]
    tiles = M // tm
    want = tuning.get("moe_grouped_matmul", "block_large")
    tk, tn = _tile(K, want), _tile(N, want)

    def kernel(tg_ref, nt_ref, lhs_ref, dout_ref, out_ref, acc_ref):
        i, nt = pl.program_id(2), nt_ref[0]

        @pl.when(i < nt)
        def _live():
            g = tg_ref[i]

            @pl.when(jnp.logical_or(
                i == 0, tg_ref[jnp.maximum(i - 1, 0)] != g))
            def _first():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            acc_ref[...] += jax.lax.dot_general(
                lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

            @pl.when(jnp.logical_or(
                i == nt - 1, tg_ref[jnp.minimum(i + 1, tiles - 1)] != g))
            def _last():
                out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def tile(i, nt):
        return jnp.minimum(i, jnp.maximum(nt[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K // tk, N // tn, tiles),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda a, b, i, tg, nt: (tile(i, nt),
                                                            a)),
            pl.BlockSpec((tm, tn), lambda a, b, i, tg, nt: (tile(i, nt),
                                                            b))],
        out_specs=pl.BlockSpec(
            (None, tk, tn), lambda a, b, i, tg, nt: (tg[tile(i, nt)], a,
                                                     b)),
        scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)])
    item = lhs.dtype.itemsize
    vmem = 2 * item * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
    tg = tile_group.astype(jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_groups, K, N), lhs.dtype),
        interpret=interpret,
        name="moe_grouped_matmul_wgrad",
        **_params(interpret, vmem),
    )(tg, jnp.reshape(n_tiles, (1,)).astype(jnp.int32), lhs, dout)
    # a group no tile names was never stored: whatever the buffer held
    live = jnp.arange(tiles) < n_tiles
    reached = jnp.zeros((n_groups,), bool).at[tg].max(live)
    return jnp.where(reached[:, None, None], out, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped_pallas(lhs, rhs, rhs2, tile_group, n_tiles, tm, interpret):
    return _grouped_matmul_pallas(lhs, rhs, rhs2, tile_group, n_tiles,
                                  tm=tm, interpret=interpret)


def _grouped_pallas_fwd(lhs, rhs, rhs2, tile_group, n_tiles, tm,
                        interpret):
    if rhs2 is None:
        out, pre = _grouped_matmul_pallas(
            lhs, rhs, None, tile_group, n_tiles, tm=tm,
            interpret=interpret), None
    else:
        out, *pre = _grouped_matmul_pallas(
            lhs, rhs, rhs2, tile_group, n_tiles, tm=tm,
            interpret=interpret, save_pre=True)
    return out, (lhs, rhs, rhs2, tile_group, n_tiles, pre)


def _grouped_pallas_bwd(tm, interpret, res, g):
    lhs, rhs, rhs2, tile_group, n_tiles, pre = res
    kw = dict(tm=tm, interpret=interpret)
    wgrad = functools.partial(_grouped_wgrad_pallas, lhs,
                              tile_group=tile_group, n_tiles=n_tiles,
                              n_groups=rhs.shape[0], **kw)
    ints = (float0_like(tile_group), float0_like(n_tiles))
    g = g.astype(lhs.dtype)
    if rhs2 is None:
        dlhs = _grouped_matmul_pallas(g, rhs, None, tile_group, n_tiles,
                                      transpose_rhs=True, **kw)
        return (dlhs, wgrad(g), None) + ints
    a, b = (p.astype(jnp.float32) for p in pre)
    sig = jax.nn.sigmoid(a)
    gf = g.astype(jnp.float32)
    da = (gf * b * sig * (1.0 + a * (1.0 - sig))).astype(lhs.dtype)
    db = (gf * a * sig).astype(lhs.dtype)
    dlhs = _grouped_matmul_pallas(da, rhs, rhs2, tile_group, n_tiles,
                                  lhs2=db, transpose_rhs=True, **kw)
    return (dlhs, wgrad(da), wgrad(db)) + ints


_grouped_pallas.defvjp(_grouped_pallas_fwd, _grouped_pallas_bwd)


def grouped_matmul(lhs, rhs, tile_group, n_tiles, tm, rhs2=None,
                   use_kernel=True):
    """lhs (M, K) with M a multiple of `tm`; rhs, rhs2 (G, K, N);
    tile_group (M // tm,) int32; n_tiles () int32. Rows of tiles past
    `n_tiles` come back unwritten: the caller masks them (and their
    gradient, which comes back unwritten too)."""
    return _fallback.run(
        grouped_matmul_mode(lhs) if use_kernel else None,
        lambda interpret: _grouped_pallas(
            lhs, rhs, rhs2, tile_group, n_tiles, tm, interpret),
        lambda: reference_grouped_matmul(lhs, rhs, tile_group, n_tiles,
                                         tm, rhs2))
