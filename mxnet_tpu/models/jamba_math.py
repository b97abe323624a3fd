"""Layer math of the Jamba decoder (AI21 Jamba family, `model_type`
jamba): Mamba-1 state-space layers with Jamba's three extra RMSNorms
(on dt, B and C), an attention layer with no positional term every
`attn_layer_period`, a dense SwiGLU after every mixer.

Pure functions in `llama_math`'s style, built from its `rms`, `swiglu`
and `final_logits`. Consumers: `models/jamba.py` (the Gluon forward)
and the serving executables through `JambaDecoder`: a RECURRENT layer
is `mamba_layer` whole over a prompt (returning the state at each row's
length) and `mamba_layer_step` for one token of every row; an attention
layer is `attention_qkv` + the paged call + `attention_finish`.

A Mamba layer's parameters `lp` (matrices in the Dense convention,
y = x @ W.T; `Dn` = d_inner, `N` = d_state, `R` = dt_rank): ln_in,
in_proj (2 Dn, D), conv_w (d_conv, Dn) tap-major with the LAST tap on
the current input, conv_b (Dn,), x_proj (R + 2 N, Dn), dt_norm (R,),
b_norm (N,), c_norm (N,), dt_proj (Dn, R), dt_bias (Dn,), A_log
(N, Dn) state-major, D (Dn,), out_proj (D, Dn), ln_ff, gate, up, down.
conv_w, conv_b, A_log, D and dt_bias are float32 whatever the model's
dtype (they enter float32 arithmetic: stored narrower, each would be
widened by an operation of its own every tick). The
published checkpoint stores conv_w (Dn, 1, d_conv) and A_log (Dn, N):
here d_inner lies last, along the lanes, so no call transposes them.
An attention layer's: ln_in, wq, wk, wv, wo, ln_ff, gate, up, down.

The state of one sequence in one layer: `h` (N, Dn / 128, 128) float32
(kernels/selective_scan.py::state_shape) and `tail`, the last
d_conv - 1 inputs of the convolution, ((d_conv - 1) * Dn,) in the
model's dtype, tap after tap (`tail_shape`: the layout the decode step
reads and writes in place beside `xz`; `mixer` views it
(d_conv - 1, Dn)). `mixer_step` is three operations: in_proj, ONE call
that does the rest of the layer with both pools in place
(`ssm_state_update`), out_proj. No function here has a backward
through the scan kernel: the net is for inference.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .llama_math import final_logits, rms, swiglu

__all__ = ["mamba_layer", "mamba_layer_step", "attention_qkv",
           "attention_finish", "attention_layer", "final_logits",
           "zero_state"]


def zero_state(cfg, batch):
    from ..kernels.selective_scan import state_shape, tail_shape

    return {"h": jnp.zeros((batch,) + state_shape(cfg.d_state,
                                                  cfg.d_inner),
                           jnp.float32),
            "tail": jnp.zeros((batch,) + tail_shape(cfg.d_conv,
                                                    cfg.d_inner),
                              jnp.dtype(cfg.dtype))}


def mixer(lp, u, cfg, lengths=None, state=None):
    """The Mamba-1 mixer over (B, T, D), from `state` (zeros when
    None). Returns (out, state'): with `lengths` (B,) the state is the
    one after each row's last valid position (dt = 0 on the right
    padding holds h still; the tail is cut at the length)."""
    from ..kernels.selective_scan import gate, selective_scan, ssm_inputs

    B, T, _ = u.shape
    Dn, k = cfg.d_inner, cfg.d_conv
    if state is None:
        state = zero_state(cfg, B)
    xz = u @ lp["in_proj"].T
    xr, z = xz[..., :Dn], xz[..., Dn:]
    xp = jnp.concatenate([state["tail"].reshape(B, k - 1, Dn)
                          .astype(xr.dtype), xr], axis=1)
    acc = lp["conv_b"]
    for j in range(k):
        acc = acc + xp[:, j:j + T].astype(jnp.float32) * lp["conv_w"][j]
    xc = jax.nn.silu(acc).astype(u.dtype)
    dt, b, c = ssm_inputs(lp, xc, cfg.rms_eps)
    if lengths is None:
        tail = xp[:, T:]
    else:
        dt = jnp.where(jnp.arange(T)[None, :, None]
                       < lengths[:, None, None], dt, 0.0)
        tail = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
            row, n, k - 1, axis=0))(xp, lengths)
    y, h = selective_scan(xc, dt, lp["A_log"], b, c, state["h"])
    return gate(lp, y, xc, z) @ lp["out_proj"].T, {
        "h": h, "tail": tail.reshape(B, -1).astype(u.dtype)}


def mixer_step(lp, u, cfg, state, active):
    """One token of every row: u (B, 1, D), `state` the rows' states.
    Three operations: in_proj, the step (kernels/selective_scan.py::
    ssm_state_update: convolution, x_proj, the norms, dt_proj, the
    recurrence and the gate in one call, `h` and `tail` in place),
    out_proj. A row whose `active` is False keeps its state (and its
    output is never read)."""
    from ..kernels.selective_scan import ssm_state_update

    g, h, tail = ssm_state_update(
        state["h"], state["tail"], u[:, 0] @ lp["in_proj"].T, active, lp,
        cfg.rms_eps)
    return (g @ lp["out_proj"].T)[:, None], {"h": h, "tail": tail}


def _feed_forward(lp, x, cfg):
    return x + swiglu(rms(x, lp["ln_ff"], cfg.rms_eps), lp["gate"],
                      lp["up"], lp["down"])


def mamba_layer(lp, x, cfg, lengths=None, state=None):
    """One whole RECURRENT layer on (B, T, D) -> (x, state')."""
    out, state = mixer(lp, rms(x, lp["ln_in"], cfg.rms_eps), cfg,
                       lengths, state)
    return _feed_forward(lp, x + out, cfg), state


def mamba_layer_step(lp, x, cfg, state, active):
    """One RECURRENT layer for one token a row: x (B, 1, D)."""
    out, state = mixer_step(lp, rms(x, lp["ln_in"], cfg.rms_eps), cfg,
                            state, active)
    return _feed_forward(lp, x + out, cfg), state


def attention_qkv(lp, x, cfg):
    """RMSNorm and the q / k / v projections; no rotation, no
    positional term. Returns (q (B,T,H,d), k (B,T,K,d), v)."""
    B, T, _ = x.shape
    H, K, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    u = rms(x, lp["ln_in"], cfg.rms_eps)
    return ((u @ lp["wq"].T).reshape(B, T, H, d),
            (u @ lp["wk"].T).reshape(B, T, K, d),
            (u @ lp["wv"].T).reshape(B, T, K, d))


def attention_finish(lp, x, att, cfg):
    B, T, _ = x.shape
    return _feed_forward(lp, x + att.reshape(B, T, -1) @ lp["wo"].T,
                         cfg)


def attention_layer(lp, x, cfg, lengths=None):
    """One whole attention layer on (B, T, D) -> (x, k, v)."""
    from ..kernels.flash_attention import flash_attention_raw

    q, k, v = attention_qkv(lp, x, cfg)
    att = flash_attention_raw(q, k, v, causal=True,
                              scale=1.0 / math.sqrt(cfg.head_dim),
                              lengths=lengths)
    return attention_finish(lp, x, att, cfg), k, v
