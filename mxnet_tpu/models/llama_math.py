"""Single-source Llama layer math (round-3 verdict item 3).

Every numerical definition of the Llama architecture — RMSNorm, RoPE,
GQA attention dispatch, SwiGLU, the residual layer wiring — lives HERE
and nowhere else. Consumers:

- `models/llama.py` (Gluon training path): `LlamaLayer.forward` routes
  one `invoke` through `decoder_layer`, so autograd/hybridize see a
  single fused op per layer.
- `models/llama_infer.py` (cached decode): prefill runs `decoder_layer`
  with ragged `lengths` (the SAME flash-attention dispatch as
  training); the per-token decode step reuses `layer_qkv` /
  `layer_finish` and keeps only its cache plumbing.

A change here (RoPE scaling, bias handling, eps) changes training,
prefill, and decode identically — `tests/test_llama_infer.py` asserts
a weight perturbation moves prefill and decode logits together.
All functions are pure jnp: (B, T, ...) in, same out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["rms", "rope_at", "layer_qkv", "swiglu", "layer_finish",
           "decoder_layer", "final_logits", "lora_delta"]


def lora_delta(h, ab):
    """Low-rank LoRA residual ``(h @ A) @ B`` for one target matmul.

    ``ab = (A, B)`` with A ``(din, r)`` / B ``(r, dout)`` — one adapter
    shared by the whole batch (training) — or A ``(B, din, r)`` /
    B ``(B, r, dout)`` — per-row factors gathered from a stacked
    adapter table (serving: every batch row can run a different
    adapter inside ONE executable). The all-zero identity adapter
    contributes an exact 0.0, so ``y + lora_delta`` is bit-identical
    to the base matmul for rows without an adapter."""
    a, b = ab
    if a.ndim == 2:
        return (h @ a) @ b
    return jnp.einsum("btr,bro->bto",
                      jnp.einsum("btd,bdr->btr", h, a), b)


def rms(x, g, eps):
    """RMSNorm in fp32 stats, output in x.dtype — dispatched through
    the fused Pallas kernel (kernels/fused_norm.py) exactly like
    nn.RMSNorm, so training AND decode get the one-VMEM-pass kernel on
    TPU (its jnp fallback is the same fp32-stats math)."""
    from ..kernels.fused_norm import fused_rmsnorm

    return fused_rmsnorm(x, g, eps=eps)


def rope_at(x, positions, base, inv=None):
    """Rotary embedding for (B, T, H, d) at absolute `positions`
    ((T,) or (B, T)); fp32 rotation, output in x.dtype. `inv` (d / 2,)
    gives the frequencies themselves where they are not the plain
    `base` ** (-i / half) (YaRN: `mla_math.yarn_inv_freq`)."""
    d = x.shape[-1]
    half = d // 2
    if inv is None:
        inv = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = jnp.asarray(positions, jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]
    ang = pos[..., None] * inv  # (B, T, half)
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def layer_qkv(lp, x, positions, eps, base, H, K, d, lora=None):
    """Pre-attention half of a layer: RMSNorm → q/k/v projections →
    RoPE. lp holds {ln1, wq, wk, wv} (Dense convention: y = x @ W.T).
    `lora` (optional) maps a target name among {"wq","wk","wv"} to its
    (A, B) factors — see :func:`lora_delta`. Returns (q (B,T,H,d),
    k (B,T,K,d), v (B,T,K,d)) — k/v post-RoPE, ready for the cache."""
    B, T, _ = x.shape
    h = rms(x, lp["ln1"], eps)
    q = h @ lp["wq"].T
    k = h @ lp["wk"].T
    v = h @ lp["wv"].T
    if lora:
        if "wq" in lora:
            q = q + lora_delta(h, lora["wq"])
        if "wk" in lora:
            k = k + lora_delta(h, lora["wk"])
        if "wv" in lora:
            v = v + lora_delta(h, lora["wv"])
    q = rope_at(q.reshape(B, T, H, d), positions, base)
    k = rope_at(k.reshape(B, T, K, d), positions, base)
    return q, k, v.reshape(B, T, K, d)


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate.T) * (h @ w_up.T)) @ w_down.T


def layer_finish(lp, x, att, eps, lora=None):
    """Post-attention half: o-projection residual, RMSNorm, SwiGLU
    residual. att: (B, T, H, d). `lora` may carry "wo" factors."""
    B, T, _ = x.shape
    a2 = att.reshape(B, T, -1)
    proj = a2 @ lp["wo"].T
    if lora and "wo" in lora:
        proj = proj + lora_delta(a2, lora["wo"])
    x = x + proj
    h2 = rms(x, lp["ln2"], eps)
    return x + swiglu(h2, lp["gate"], lp["up"], lp["down"])


def decoder_layer(lp, x, positions, eps, base, H, K, d, lengths=None,
                  return_kv=False, lora=None):
    """One full decoder layer on (B, T, D): the training forward and
    the prefill forward are THIS function (prefill passes ragged
    `lengths` and return_kv=True to harvest the cache rows).
    Attention dispatches through the same Pallas flash kernel as
    everything else (kernels/flash_attention.py)."""
    from ..kernels.flash_attention import flash_attention_raw

    q, k, v = layer_qkv(lp, x, positions, eps, base, H, K, d,
                        lora=lora)
    att = flash_attention_raw(q, k, v, causal=True,
                              scale=1.0 / math.sqrt(d), lengths=lengths)
    out = layer_finish(lp, x, att, eps, lora=lora)
    return (out, k, v) if return_kv else out


def final_logits(params, x, eps):
    """Closing RMSNorm + LM head over (B, T, D)."""
    return rms(x, params["norm"], eps) @ params["head"].T
