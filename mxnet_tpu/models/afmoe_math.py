"""Layer math of the afmoe decoder (Arcee Trinity family): gated
grouped-query attention with per-head q/k RMSNorm, sandwich norms, RoPE
on sliding-window layers only (full layers carry no position), a dense
SwiGLU or a sparse expert layer (sigmoid scores, selection bias, one
shared expert) whose routed part covers the experts HELD here.

Pure functions in `llama_math`'s style, and built from its `rms`,
`rope_at`, `swiglu` and `final_logits`: there is one definition of each.
Consumers: `models/afmoe.py` (Gluon forward) and the serving executables
through `AfmoeDecoder` (prefill = `decoder_layer`, decode = `layer_qkv`
+ paged attention + `layer_finish`).

A layer's parameters `lp`: ln_in, wq, wk, wv, wg (the attention gate),
wo, q_norm, k_norm, ln_post_attn, ln_pre_mlp, ln_post_mlp, then either
gate / up / down (dense) or router (E, D), bias (E,), sh_gate / sh_up /
sh_down (the shared expert) and ex_gate (n, D, I), ex_up (n, D, I),
ex_down (n, I, D): the held experts stacked, stored input-major so a
grouped matmul reads them as they lie. Dense matrices follow the Dense
convention (y = x @ W.T). `cfg` is an `AfmoeConfig`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .llama_math import final_logits, rms, rope_at, swiglu

__all__ = ["embed", "layer_qkv", "layer_finish", "decoder_layer",
           "mlp", "final_logits", "SLIDING", "FULL"]

SLIDING, FULL = "sliding", "full"


def embed(params, ids, cfg):
    """Token embedding, scaled by sqrt(hidden) (mup_enabled)."""
    x = params["embed"][ids]
    return x * jnp.asarray(cfg.embed_scale, x.dtype)


def layer_qkv(lp, x, positions, cfg, kind):
    """Pre-attention half: RMSNorm, q/k/v projections, per-head q/k
    RMSNorm, RoPE on sliding layers only. Returns (q (B,T,H,d),
    k (B,T,K,d), v (B,T,K,d), gate (B,T,H*d)): k is what the cache
    stores, `gate` the sigmoid gate `layer_finish` multiplies the
    attention output by."""
    B, T, _ = x.shape
    H, K, d, eps = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, \
        cfg.rms_eps
    u = rms(x, lp["ln_in"], eps)
    q = rms((u @ lp["wq"].T).reshape(B, T, H, d), lp["q_norm"], eps)
    k = rms((u @ lp["wk"].T).reshape(B, T, K, d), lp["k_norm"], eps)
    v = (u @ lp["wv"].T).reshape(B, T, K, d)
    if kind == SLIDING:
        q = rope_at(q, positions, cfg.rope_base)
        k = rope_at(k, positions, cfg.rope_base)
    return q, k, v, jax.nn.sigmoid(u @ lp["wg"].T)


def mlp(lp, m, cfg, valid=None):
    """The feed-forward of one layer on (B, T, D): dense SwiGLU, or the
    shared expert plus the held experts' part of the routed sum. Returns
    (f, counts): counts is None for a dense layer, else int32 (pairs,
    touched) of `parallel.moe.held_expert_ffn`."""
    if "router" not in lp:
        return swiglu(m, lp["gate"], lp["up"], lp["down"]), None
    from ..parallel.moe import held_expert_ffn, route_top_k

    B, T, D = m.shape
    routed, pairs, touched, _ = held_expert_ffn(
        m.reshape(B * T, D), lp["router"], lp["bias"], lp["ex_gate"],
        lp["ex_up"], lp["ex_down"], lo=cfg.held_lo, top_k=cfg.top_k,
        route=route_top_k, route_scale=cfg.route_scale,
        valid=None if valid is None else valid.reshape(B * T))
    f = swiglu(m, lp["sh_gate"], lp["sh_up"], lp["sh_down"]) \
        + routed.reshape(B, T, D).astype(m.dtype)
    return f, jnp.stack([pairs, touched])


def layer_finish(lp, x, att, gate, cfg, valid=None):
    """Post-attention half: gate, o-projection, sandwich norms, the
    feed-forward. att (B, T, H, d). Returns (x, counts)."""
    B, T, _ = x.shape
    a = att.reshape(B, T, -1) * gate
    x = x + rms(a @ lp["wo"].T, lp["ln_post_attn"], cfg.rms_eps)
    f, counts = mlp(lp, rms(x, lp["ln_pre_mlp"], cfg.rms_eps), cfg,
                    valid)
    return x + rms(f, lp["ln_post_mlp"], cfg.rms_eps), counts


def decoder_layer(lp, x, positions, cfg, kind, lengths=None):
    """One whole layer on (B, T, D): the Gluon forward and the serving
    prefill. Ragged `lengths` (B,) mask the keys past each row's end
    and keep the padding out of the experts. Returns (x, k, v,
    counts)."""
    from ..kernels.flash_attention import flash_attention_raw

    q, k, v, gate = layer_qkv(lp, x, positions, cfg, kind)
    att = flash_attention_raw(
        q, k, v, causal=True, scale=1.0 / math.sqrt(cfg.head_dim),
        lengths=lengths,
        window=cfg.window if kind == SLIDING else None)
    valid = None if lengths is None else \
        jnp.arange(x.shape[1])[None, :] < lengths[:, None]
    out, counts = layer_finish(lp, x, att, gate, cfg, valid)
    return out, k, v, counts
