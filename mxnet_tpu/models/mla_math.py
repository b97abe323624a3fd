"""Layer math of a latent-attention decoder over a sparse expert layer
(Sarvam-105B's `sarvam_mla`, the DeepSeek-V2 layer): every position is
cached as ONE row `[c | k_rope]` — a normed latent `c` of
`kv_lora_rank` entries from which every head's keys and values are
projected, and one rotated key of `qk_rope_head_dim` shared by all
heads — pre-norm residuals, a dense SwiGLU first and after it the
shared expert plus the experts HELD here (`afmoe_math.mlp`: sigmoid
scores, a bias that picks, top-k).

Two attention paths from one set of weights:

- prefill (`decoder_layer`): per-head keys `[k_nope_h | k_rope]` and
  values are materialised from the latent (`wkv_b`) and causal flash
  attention runs over them;
- decode (`layer_qkv` + the paged latent sweep + `layer_finish`): the
  same arithmetic re-associated. `W_uk` goes into the query
  (`q_nope_h @ W_uk_h`, as wide as the latent) and `W_uv` onto the
  output, so the scores and the values are read off the cached rows
  themselves and nothing per head is ever stored.

Pure functions in `llama_math`'s style, built from its `rms`, `rope_at`,
`swiglu` and `final_logits` and from `afmoe_math.mlp`: there is one
definition of each.

A layer's parameters `lp`: ln_in, wq (H * (nope + rope), D), q_norm
(nope + rope,), wkv_a (latent + rope, D), kv_norm (latent,), wkv_b
(H * (nope + v), latent) with a head's k_nope rows before its v rows,
wo (D, H * v), ln_mlp, then gate / up / down (dense) or router, bias,
sh_gate / sh_up / sh_down, ex_gate / ex_up / ex_down as afmoe's. Dense
convention (y = x @ W.T). `cfg` is a `SarvamConfig`.
"""
from __future__ import annotations

import math

import numpy as np

import jax.numpy as jnp

from .afmoe_math import mlp
from .llama_math import final_logits, rms, rope_at

__all__ = ["yarn_inv_freq", "yarn_mscale", "softmax_scale", "embed",
           "layer_qkv", "layer_finish", "decoder_layer", "final_logits"]


def yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """The `dim` / 2 rotation frequencies of `deepseek_yarn`, float32:
    the plain ones where a dim turns more than `beta_fast` times over
    the `original` positions, those of positions `factor` times closer
    where it turns less than `beta_slow` times, a linear ramp between."""
    half = dim // 2
    inv = base ** (-np.arange(half, dtype=np.float64) / half)

    def turns_at(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (inv / factor * ramp + inv * (1 - ramp)).astype(np.float32)


def yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg):
    """q_head_dim ** -0.5 times mscale(factor, mscale_all_dim) squared
    (the rotation's own cos / sin scale, mscale / mscale_all_dim, is 1
    at the published values and is not applied)."""
    return cfg.q_head_dim ** -0.5 \
        * yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim) ** 2


def embed(params, ids, cfg):
    return params["embed"][ids]


def _query_and_row(lp, x, positions, cfg):
    """-> (q_nope (B, T, H, nope), q_rope (B, T, H, rope) rotated,
    c (B, T, latent) normed, k_rope (B, T, 1, rope) rotated)."""
    B, T, _ = x.shape
    H, nope, eps = cfg.num_heads, cfg.qk_nope_head_dim, cfg.rms_eps
    u = rms(x, lp["ln_in"], eps)
    q = rms((u @ lp["wq"].T).reshape(B, T, H, cfg.q_head_dim),
            lp["q_norm"], eps)
    a = u @ lp["wkv_a"].T
    c = rms(a[..., :cfg.kv_lora_rank], lp["kv_norm"], eps)
    inv = jnp.asarray(cfg.rope_inv_freq)
    q_rope = rope_at(q[..., nope:], positions, cfg.rope_base, inv)
    k_rope = rope_at(a[..., None, cfg.kv_lora_rank:], positions,
                     cfg.rope_base, inv)
    return q[..., :nope], q_rope, c, k_rope


def _row_wide(parts, cfg):
    """`parts` joined along the last dim and zero-padded to the cached
    row's width: `[latent part | rope part | 0]`."""
    pad = cfg.cache_row - sum(p.shape[-1] for p in parts)
    zeros = jnp.zeros(parts[0].shape[:-1] + (pad,), parts[0].dtype)
    return jnp.concatenate(list(parts) + [zeros], axis=-1)


def _cached_row(c, k_rope, cfg):
    """(B, T, 1, row): `[c | k_rope | 0]`, the row as the pool holds
    it."""
    return _row_wide((c[:, :, None, :], k_rope), cfg)


def _up_weights(lp, cfg):
    """(W_uk, W_uv), each (H, head width, latent), of wkv_b."""
    w = lp["wkv_b"].reshape(cfg.num_heads, -1, cfg.kv_lora_rank)
    return w[:, :cfg.qk_nope_head_dim], w[:, cfg.qk_nope_head_dim:]


def layer_qkv(lp, x, positions, cfg):
    """The absorbed pre-attention half: q (B, T, H, row) =
    `[q_nope_h @ W_uk_h | q_rope_h | 0]` and the row to cache
    (B, T, 1, row); q . row is q_h . k_h of the materialised form."""
    q_nope, q_rope, c, k_rope = _query_and_row(lp, x, positions, cfg)
    w_uk, _ = _up_weights(lp, cfg)
    q_lat = jnp.einsum("bthn,hnl->bthl", q_nope, w_uk)
    return _row_wide((q_lat, q_rope), cfg), _cached_row(c, k_rope, cfg)


def _finish(lp, x, att, cfg, valid):
    """o-projection of att (B, T, H, v) and the feed-forward. Returns
    (x, counts)."""
    B, T, _ = x.shape
    x = x + att.reshape(B, T, -1) @ lp["wo"].T
    f, counts = mlp(lp, rms(x, lp["ln_mlp"], cfg.rms_eps), cfg, valid)
    return x + f, counts


def layer_finish(lp, x, att, cfg, valid=None):
    """The absorbed post-attention half: att (B, T, H, latent) is the
    probabilities' mix of the cached latents, `W_uv` takes it to the
    heads' values; then as `decoder_layer`. Returns (x, counts)."""
    att = jnp.einsum("bthl,hvl->bthv", att, _up_weights(lp, cfg)[1])
    return _finish(lp, x, att, cfg, valid)


def decoder_layer(lp, x, positions, cfg, lengths=None):
    """One whole layer on (B, T, D), keys and values materialised: the
    Gluon forward and the serving prefill. Returns (x, row, counts),
    `row` (B, T, 1, row) what the cache stores. The flash kernel takes
    one head width: the values ride zero-padded to the keys'."""
    from ..kernels.flash_attention import flash_attention_raw

    B, T, _ = x.shape
    H, nope, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_rope, c, k_rope = _query_and_row(lp, x, positions, cfg)
    kv = (c @ lp["wkv_b"].T).reshape(B, T, H, nope + dv)
    d = cfg.q_head_dim
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
    v = jnp.pad(kv[..., nope:], ((0, 0),) * 3 + ((0, d - dv),))
    att = flash_attention_raw(q, k, v, causal=True,
                              scale=softmax_scale(cfg), lengths=lengths)
    valid = None if lengths is None else \
        jnp.arange(T)[None, :] < lengths[:, None]
    out, counts = _finish(lp, x, att[..., :dv], cfg, valid)
    return out, _cached_row(c, k_rope, cfg), counts
