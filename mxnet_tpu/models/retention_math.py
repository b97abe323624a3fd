"""Layer math of a power-retention decoder (Manifest AI's Brumby
family, `model_type` brumby): the Qwen3 block (per-head q / k RMSNorm,
RoPE, grouped heads, a dense SwiGLU) with the softmax attention
replaced by a gated linear attention over a degree-2 feature map
(kernels/power_retention.py states the recurrence and the layout of the
state).

Pure functions in `llama_math`'s style, built from its `rms`, `rope_at`,
`swiglu` and `final_logits`. Consumers: `models/brumby.py` (the Gluon
forward) and the serving executables through `BrumbyDecoder`: a
RECURRENT layer is `retention_layer` whole over a prompt (returning the
state at each row's length) and `retention_layer_step` for one token of
every row. Unlike a state-space layer this one needs POSITIONS: q and k
are rotated.

A layer's parameters `lp` (matrices in the Dense convention, y = x @
W.T): ln_in, wq (H d, D), wk (K d, D), wv (K d, D), q_norm (d,), k_norm
(d,), wg (K, D) and bg (K,) float32 (the gate: one scalar a kv head a
position, `log sigmoid(x wg^T + bg)` in float32), wo (D, H d), ln_ff,
gate, up, down.

The state of one sequence in one layer: `S` (K, O, d, d) and `z`
(K, O up to a tile, d), float32, O = d / 2 + 1. No function here has a
backward through the kernels: the net is for inference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .llama_math import final_logits, rms, rope_at, swiglu

__all__ = ["retention_inputs", "retention_layer", "retention_layer_step",
           "final_logits", "zero_state"]


def zero_state(cfg, batch):
    from ..kernels.power_retention import state_shapes

    return {name: jnp.zeros((batch,) + shape, dt) for name, (shape, dt)
            in state_shapes(cfg.num_kv_heads, cfg.head_dim).items()}


def retention_inputs(lp, u, cfg, positions):
    """From the normed input u (B, T, D): q (B, T, H, d) and k
    (B, T, K, d) after their per-head RMSNorm and the rotation at
    `positions` ((T,) or (B, T)), v (B, T, K, d), and the log-gate
    (B, T, K) float32."""
    B, T, _ = u.shape
    H, K, d, eps = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, \
        cfg.rms_eps
    q = rms((u @ lp["wq"].T).reshape(B, T, H, d), lp["q_norm"], eps)
    k = rms((u @ lp["wk"].T).reshape(B, T, K, d), lp["k_norm"], eps)
    q = rope_at(q, positions, cfg.rope_base)
    k = rope_at(k, positions, cfg.rope_base)
    v = (u @ lp["wv"].T).reshape(B, T, K, d)
    log_g = jax.nn.log_sigmoid(jnp.dot(
        u, lp["wg"].T, preferred_element_type=jnp.float32) + lp["bg"])
    return q, k, v, log_g


def _feed_forward(lp, x, cfg):
    return x + swiglu(rms(x, lp["ln_ff"], cfg.rms_eps), lp["gate"],
                      lp["up"], lp["down"])


def retention_layer(lp, x, cfg, positions, lengths=None):
    """One whole RECURRENT layer on (B, T, D) from a zero state ->
    (x, state'). With `lengths` (B,) the state is the one after each
    row's last valid position: on the right padding the key is 0 and
    the gate 1, so the state stands still."""
    from ..kernels.power_retention import power_retention_chunked

    B, T, _ = x.shape
    q, k, v, log_g = retention_inputs(
        lp, rms(x, lp["ln_in"], cfg.rms_eps), cfg, positions)
    if lengths is not None:
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        k = jnp.where(valid[..., None, None], k, 0)
        log_g = jnp.where(valid[..., None], log_g, 0.0)
    y, state = power_retention_chunked(q, k, v, log_g,
                                       eps=cfg.retention_eps)
    x = x + y.reshape(B, T, -1) @ lp["wo"].T
    return _feed_forward(lp, x, cfg), state


def retention_layer_step(lp, x, cfg, positions, state, active):
    """One RECURRENT layer for one token a row: x (B, 1, D) at
    `positions` (B,), `state` the rows' states. A row whose `active`
    is False keeps its state (and its output is never read)."""
    from ..kernels.power_retention import power_retention_step

    B = x.shape[0]
    q, k, v, log_g = retention_inputs(
        lp, rms(x, lp["ln_in"], cfg.rms_eps), cfg, positions[:, None])
    S, z, y = power_retention_step(state["S"], state["z"], q[:, 0],
                                   k[:, 0], v[:, 0], log_g[:, 0], active,
                                   eps=cfg.retention_eps)
    x = x + y.reshape(B, 1, -1) @ lp["wo"].T
    return _feed_forward(lp, x, cfg), {"S": S, "z": z}
