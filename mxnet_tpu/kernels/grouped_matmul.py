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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import tuning
from .dispatch import KernelFallback, kernel_mode

__all__ = ["grouped_matmul", "reference_grouped_matmul",
           "grouped_matmul_mode"]

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


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _grouped_matmul_pallas(lhs, rhs, rhs2, tile_group, n_tiles, *, tm,
                           interpret):
    """A jit of its own: the layers of a program share one trace and
    one Mosaic lowering."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = lhs.shape
    N = rhs.shape[2]
    fused = rhs2 is not None
    tk = _tile(K, tuning.get("moe_grouped_matmul", "block_k"))
    tn = _tile(N, tuning.get("moe_grouped_matmul", "block_n"))
    nk, nj = K // tk, N // tn

    def kernel(tg_ref, nt_ref, lhs_ref, *refs):
        if fused:
            rhs_ref, rhs2_ref, out_ref, acc_ref, acc2_ref = refs
        else:
            rhs_ref, out_ref, acc_ref = refs
        k = pl.program_id(2)

        @pl.when(pl.program_id(0) < nt_ref[0])
        def _live():
            @pl.when(k == 0)
            def _zero():
                acc_ref[...] = jnp.zeros_like(acc_ref)
                if fused:
                    acc2_ref[...] = jnp.zeros_like(acc2_ref)

            a = lhs_ref[...]
            acc_ref[...] += jnp.dot(a, rhs_ref[...],
                                    preferred_element_type=jnp.float32)
            if fused:
                acc2_ref[...] += jnp.dot(
                    a, rhs2_ref[...],
                    preferred_element_type=jnp.float32)

            @pl.when(k == nk - 1)
            def _store():
                r = acc_ref[...]
                if fused:
                    r = r * jax.nn.sigmoid(r) * acc2_ref[...]
                out_ref[...] = r.astype(out_ref.dtype)

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
        return tg[i], k, j

    def out_map(i, j, k, tg, nt):
        i, j, _ = held(i, j, k, nt)
        return i, j

    rhs_spec = pl.BlockSpec((None, tk, tn), rhs_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // tm, nj, nk),
        in_specs=[pl.BlockSpec((tm, tk), lhs_map), rhs_spec]
        + ([rhs_spec] if fused else []),
        out_specs=pl.BlockSpec((tm, tn), out_map),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
        * (2 if fused else 1))
    # a step past the rows leans on the blocks the last live step
    # left: in order, on one core
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3)}
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        interpret=interpret,
        name="moe_grouped_matmul",
        **params,
    )(tile_group.astype(jnp.int32),
      jnp.reshape(n_tiles, (1,)).astype(jnp.int32), lhs, rhs,
      *((rhs2,) if fused else ()))


def grouped_matmul(lhs, rhs, tile_group, n_tiles, tm, rhs2=None,
                   use_kernel=True):
    """lhs (M, K) with M a multiple of `tm`; rhs, rhs2 (G, K, N);
    tile_group (M // tm,) int32; n_tiles () int32. Rows of tiles past
    `n_tiles` come back unwritten: the caller masks them."""
    return _fallback.run(
        grouped_matmul_mode(lhs) if use_kernel else None,
        lambda interpret: _grouped_matmul_pallas(
            lhs, rhs, rhs2, tile_group, n_tiles, tm=tm,
            interpret=interpret),
        lambda: reference_grouped_matmul(lhs, rhs, tile_group, n_tiles,
                                         tm, rhs2))
