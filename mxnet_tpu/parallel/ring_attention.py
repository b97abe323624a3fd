"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Long-context replacement for the reference's single-GPU fused attention
(ptrendx fork's interleaved MHA kernels): the sequence dimension is
sharded over the mesh's `sp` axis, so a context of length T costs each
chip T/sp of activation memory.

Two public strategies (both public-literature patterns):
  * ring_attention — K/V chunks rotate around the `sp` ring via
    `lax.ppermute` while each chip holds its Q shard; a flash-style
    online softmax (running max/sum) accumulates exact attention. sp
    steps, each overlapping compute with the ICI transfer XLA schedules.
  * ulysses_attention — all-to-all reshards (seq-sharded → head-sharded),
    runs plain local attention, and reshards back. Cheaper when
    heads % sp == 0 and T is moderate.

Both are exact: tests assert equality with full attention on the
8-device CPU mesh.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..ndarray import NDArray
from .mesh import current_mesh

__all__ = ["ring_attention", "ulysses_attention", "ring_attention_local",
           "full_attention"]

_NEG = -1e30  # large-negative mask value; avoids -inf NaN in exp


def _block_attn_update(carry, q, k, v, q_pos, k_pos, causal, scale):
    """One online-softmax accumulation step over a K/V block."""
    o, m, l = carry  # o:(B,H,Tq,D) m,l:(B,H,Tq)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = k_pos[None, :] <= q_pos[:, None]  # (Tq, Tk)
        s = jnp.where(mask[None, None], s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l = l * alpha + p.sum(axis=-1)
    o = o * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o, m_new, l


def ring_attention_local(q, k, v, axis_name, causal=True, scale=None):
    """Per-shard body: call inside shard_map with q/k/v seq-sharded.

    q, k, v: (B, H, T_local, D) local shards of the global sequence.
    K/V rotate around the ring; global positions derive from each
    step's source shard index so causal masking stays exact.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    q_pos = idx * Tq + jnp.arange(Tq)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def _vary(x):
        # mark the carry as device-varying over the ring axis so the scan
        # carry type matches its (q/k/v-dependent, hence varying) outputs
        return jax.lax.pcast(x, (axis_name,), to="varying")

    o0 = _vary(jnp.zeros((B, H, Tq, D), jnp.float32))
    m0 = _vary(jnp.full((B, H, Tq), _NEG, jnp.float32))
    l0 = _vary(jnp.zeros((B, H, Tq), jnp.float32))

    def body(carry, step):
        o, m, l, kc, vc = carry
        src = (idx - step) % n  # whose chunk we hold at this step
        k_pos = src * Tk + jnp.arange(Tk)
        o, m, l = _block_attn_update((o, m, l), q, kc, vc, q_pos, k_pos,
                                     causal, scale)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (o, m, l, kc, vc), ()

    (o, m, l, _, _), _ = jax.lax.scan(
        body, (o0, m0, l0, k, v), jnp.arange(n))
    return (o / l[..., None]).astype(q.dtype)


def _as_raw(x):
    return x._data if isinstance(x, NDArray) else x


def _wrap_like(out, x):
    return NDArray(out) if isinstance(x, NDArray) else out


def ring_attention(q, k, v, mesh=None, sp_axis="sp", causal=True,
                   scale=None):
    """Exact attention over a sequence sharded on `sp_axis`.

    q, k, v: (B, H, T, D) — T globally; shard_map splits T over the ring.
    Works eagerly (applies shard_map at call site) or inside a traced
    train step (the shard_map composes under jit).
    """
    mesh = mesh if mesh is not None else current_mesh()
    raw_q, raw_k, raw_v = _as_raw(q), _as_raw(k), _as_raw(v)
    if mesh is None or sp_axis not in mesh.axis_names:
        # single-shard fallback: plain attention
        out = full_attention(raw_q, raw_k, raw_v, causal, scale)
        return _wrap_like(out, q)
    spec = P(None, None, sp_axis, None)
    fn = shard_map(
        partial(ring_attention_local, axis_name=sp_axis, causal=causal,
                scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return _wrap_like(fn(raw_q, raw_k, raw_v), q)


def full_attention(q, k, v, causal=True, scale=None):
    """Plain (unsharded) softmax attention on (B, H, T, D) — the exact
    reference every parallel strategy here must match; also the local
    math TPSelfAttention reuses."""
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool))
        s = jnp.where(mask[None, None], s, _NEG)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(
        q.dtype)


def ulysses_attention(q, k, v, mesh=None, sp_axis="sp", causal=True,
                      scale=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern).

    Input is seq-sharded; `lax.all_to_all` reshards to head-sharded so
    each chip runs full-sequence attention on H/sp heads, then reshards
    back. Requires num_heads % sp == 0.
    """
    mesh = mesh if mesh is not None else current_mesh()
    raw_q, raw_k, raw_v = _as_raw(q), _as_raw(k), _as_raw(v)
    if mesh is None or sp_axis not in mesh.axis_names:
        out = full_attention(raw_q, raw_k, raw_v, causal, scale)
        return _wrap_like(out, q)
    H = raw_q.shape[1]
    sp = mesh.shape[sp_axis]
    if H % sp != 0:
        raise ValueError(f"num_heads={H} not divisible by sp={sp}")
    spec = P(None, None, sp_axis, None)

    def local(qc, kc, vc):
        # (B, H, T/sp, D) → all_to_all → (B, H/sp, T, D)
        def a2a(x, tiled):
            return jax.lax.all_to_all(
                x, sp_axis, split_axis=1 if not tiled else 2,
                concat_axis=2 if not tiled else 1, tiled=True)
        qh = a2a(qc, False)
        kh = a2a(kc, False)
        vh = a2a(vc, False)
        out = full_attention(qh, kh, vh, causal, scale)
        return a2a(out, True)  # back to seq-sharded

    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return _wrap_like(fn(raw_q, raw_k, raw_v), q)
