"""Layer math of the Mellum decoder (JetBrains Mellum 2, `model_type`
`mellum`): grouped-query attention with per-head q / k RMSNorm, sliding
layers beside full ones, a rotation on BOTH kinds with different
frequencies (plain RoPE on the sliding layers, YaRN with its
`attention_factor` on the full ones), pre-norm residuals, and in every
layer a sparse expert layer with a softmax router whose top-k weights
are normalised — no shared expert, no bias, no route scale.

Pure functions in `llama_math`'s style, built from its `rms`, `rope_at`
and `final_logits`, from `mla_math.yarn_inv_freq`, from
`flash_attention_raw(window=)` and from `parallel.moe.held_expert_ffn`
(the routed part covers the experts HELD here): there is one
definition of each. The consumer is `models/mellum.py`, the Gluon net
that trains.

A layer's parameters `lp`: ln_in, wq (H * d, D), wk, wv (K * d, D),
q_norm, k_norm (d,), wo (D, H * d), ln_mlp, router (E, D) at the
PUBLISHED expert count, ex_gate, ex_up (n, D, I) and ex_down (n, I, D):
the held experts stacked, input-major. Dense convention (y = x @ W.T).
`cfg` is a `MellumConfig`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .afmoe_math import FULL, SLIDING
from .llama_math import final_logits, rms, rope_at
from .mla_math import yarn_inv_freq

__all__ = ["embed", "rotate", "attention", "experts", "decoder_layer",
           "final_logits", "SLIDING", "FULL", "COUNTS"]

#: what a sparse layer counts, int32, in this order
COUNTS = ("moe_pairs", "moe_touched", "moe_pairs_max")


def embed(params, ids, cfg):
    return params["embed"][ids]


def rotate(x, positions, cfg, kind):
    """The layer kind's rotation of (B, T, heads, d): plain RoPE on a
    sliding layer; on a full layer YaRN's blended frequencies with cos
    and sin both times `attention_factor` (so q . k carries its square),
    applied in float32 before the one rounding to x's type."""
    if kind == SLIDING:
        return rope_at(x, positions, cfg.rope_base)
    inv = jnp.asarray(yarn_inv_freq(
        cfg.head_dim, cfg.rope_base, cfg.yarn_factor, cfg.yarn_original,
        cfg.yarn_beta_fast, cfg.yarn_beta_slow))
    scaled = x.astype(jnp.float32) * cfg.attention_factor
    return rope_at(scaled, positions, cfg.rope_base, inv).astype(x.dtype)


def attention(lp, x, positions, cfg, kind):
    """x + the attention branch: RMSNorm, q / k / v, per-head q / k
    RMSNorm, the kind's rotation, causal flash attention (inside the
    window on a sliding layer), the output projection."""
    from ..kernels.flash_attention import flash_attention_raw

    B, T, _ = x.shape
    H, K, d, eps = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, \
        cfg.rms_eps
    u = rms(x, lp["ln_in"], eps)
    q = rms((u @ lp["wq"].T).reshape(B, T, H, d), lp["q_norm"], eps)
    k = rms((u @ lp["wk"].T).reshape(B, T, K, d), lp["k_norm"], eps)
    v = (u @ lp["wv"].T).reshape(B, T, K, d)
    att = flash_attention_raw(
        rotate(q, positions, cfg, kind), rotate(k, positions, cfg, kind),
        v, causal=True, scale=1.0 / math.sqrt(d),
        window=cfg.window if kind == SLIDING else None)
    return x + att.reshape(B, T, H * d) @ lp["wo"].T


def experts(lp, x, cfg):
    """x + the held experts' part of the routed sum over RMSNorm(x).
    Returns (x, counts (3,) int32 as `COUNTS` names them)."""
    from ..parallel.moe import held_expert_ffn, route_softmax_top_k

    B, T, D = x.shape
    m = rms(x, lp["ln_mlp"], cfg.rms_eps)
    routed, *counts = held_expert_ffn(
        m.reshape(B * T, D), lp["router"], None, lp["ex_gate"],
        lp["ex_up"], lp["ex_down"], lo=cfg.held_lo, top_k=cfg.top_k,
        route=route_softmax_top_k, remat=cfg.remat)
    return x + routed.reshape(B, T, D).astype(x.dtype), jnp.stack(counts)


def decoder_layer(lp, x, positions, cfg, kind):
    """One whole layer on (B, T, D). With `cfg.remat` the attention
    branch is rebuilt in the backward from the layer's input and the
    expert layer a chunk at a time from its own (`held_expert_ffn`):
    each is worked once more, nothing of either is kept."""
    attend = jax.checkpoint(attention, static_argnums=(3, 4)) \
        if cfg.remat else attention
    return experts(lp, attend(lp, x, positions, cfg, kind), cfg)
