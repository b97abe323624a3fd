"""Autoregressive decoding with a static-shape KV cache for the Llama
decoder (reference analogue: GluonNLP's sequence sampler / beam search
over cached decoder states).

TPU-first: one jitted prefill (prompt forward that fills the cache) and
one jitted `lax.scan` over decode steps — static shapes throughout (the
cache is allocated at `max_len` up front), so the whole generation loop
is exactly two XLA executables regardless of prompt/output length.
Both are PERSISTENT: they are built once per (shape, max_len, cache
dtype, sampling mode) signature and cached on the net through
mxnet_tpu.serving.executables, so repeat calls never retrace — the
continuous-batching server (mxnet_tpu/serving/) rides the same cache
with paged variants. Greedy or temperature/top-k/top-p sampling via
functional RNG keys; sampling params are traced per-row vectors, so
changing them never recompiles.

    net = mx.models.get_model("llama_tiny"); net.initialize()
    out = generate(net, prompt_ids, max_new_tokens=32, temperature=0.8)
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax

from ..ndarray import NDArray
from . import llama_math
from .decoder import FULL, DecoderDescription

__all__ = ["generate", "generate_beam", "build_decoder"]


def _params_tree(net):
    """Collect the decoder weights into a plain pytree keyed by role."""
    cfg = net.model.cfg
    ps = {n: p.data()._data for n, p in net.collect_params().items()}
    layers = []
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        layers.append({
            "ln1": ps[pre + "input_layernorm.gamma"],
            "wq": ps[pre + "self_attn.q_proj.weight"],
            "wk": ps[pre + "self_attn.k_proj.weight"],
            "wv": ps[pre + "self_attn.v_proj.weight"],
            "wo": ps[pre + "self_attn.o_proj.weight"],
            "ln2": ps[pre + "post_attention_layernorm.gamma"],
            "gate": ps[pre + "mlp.gate_proj.weight"],
            "up": ps[pre + "mlp.up_proj.weight"],
            "down": ps[pre + "mlp.down_proj.weight"],
        })
    return {"embed": ps["model.embed_tokens.weight"],
            "norm": ps["model.norm.gamma"],
            "head": ps["lm_head.weight"],
            "layers": layers}


class LlamaDecoder(DecoderDescription):
    """The Llama block (RMSNorm, RoPE, GQA, SwiGLU) for the serving
    executables: every layer FULL, the functions of `llama_math`."""

    supports = frozenset({"prefill_chunk", "speculative", "lora",
                          "int8", "prefix_cache", "kv_tier"})

    def __init__(self, cfg):
        super().__init__(cfg)
        self.layer_kinds = (FULL,) * cfg.num_layers
        self._shape = (cfg.rms_eps, cfg.rope_base, cfg.num_heads,
                       cfg.num_kv_heads, cfg.head_dim)

    def params_tree(self, net):
        return _params_tree(net)

    def embed(self, params, ids):
        return params["embed"][ids]

    def prefill_layer(self, li, lp, x, positions, lengths, lora=None):
        return llama_math.decoder_layer(
            lp, x, positions, *self._shape, lengths=lengths,
            return_kv=True, lora=lora) + (None,)

    def layer_qkv(self, li, lp, x, positions, lora=None):
        return llama_math.layer_qkv(lp, x, positions, *self._shape,
                                    lora=lora) + (None,)

    def layer_finish(self, li, lp, x, att, carry, lora=None,
                     valid=None):
        return llama_math.layer_finish(lp, x, att, self.cfg.rms_eps,
                                       lora=lora), None


def _params_device(params):
    """The device the decoder's weights live on. Serving state (page
    pools, adapter tables, logits rows) is committed next to them, so
    a server built on another chip of the host does not leave its
    state on the first one."""
    return min(params["embed"].devices(), key=lambda d: d.id)


# the layer math itself (RMSNorm, RoPE, SwiGLU, residual wiring) is
# single-sourced in llama_math.py — this module owns ONLY the cache
# plumbing and the sampling/beam loops


def _attend(q, k_cache, v_cache, valid_len, cfg):
    """q: (B, Tq, H, d); caches in CACHE-NATIVE (B, K, S, d) layout —
    kv-head major, matching the flash-decode kernel's block tiling so
    no per-step transpose of the cache is ever materialized. Attend to
    [0, valid_len).

    Tq == 1 (the decode step, HBM-bandwidth bound) dispatches to the
    Pallas flash-decode kernel, which streams the cache once per KV
    head with an online softmax (kernels/flash_decode.py); the general
    path below is the fallback (GQA folded into the einsum — no
    jnp.repeat)."""
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if q.shape[1] == 1:
        from ..kernels.flash_decode import flash_decode
        out = flash_decode(q[:, 0], k_cache, v_cache, valid_len,
                           scale=scale)
        return out[:, None]
    B, Tq, H, d = q.shape
    K, S = k_cache.shape[1], k_cache.shape[2]
    rep = H // K
    qr = q.reshape(B, Tq, K, rep, d).astype(jnp.float32)
    s = jnp.einsum("btkrd,bksd->bkrts", qr,
                   k_cache.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, :] < valid_len[:, None]  # (B, S)
    s = jnp.where(mask[:, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkrts,bksd->bkrtd", p,
                     v_cache.astype(jnp.float32))
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, d) \
        .astype(q.dtype)


def build_decoder(net, max_len: int, kv_cache_dtype: str = "model"):
    """Returns (params, prefill, step).

    prefill(params, ids, valid_len) -> (cache, last_logits): runs the
    prompt (right-padded to the jit shape) and fills the KV cache.
    step(params, cache, pos, tok) -> (cache, logits): one decode step.
    cache: per layer {k, v} of (B, K, max_len, d) — kv-head-major
    "cache-native" layout shared with the flash-decode kernel, so the
    per-token hot loop never transposes the cache.

    kv_cache_dtype="int8": the cache is stored int8 with per-token
    scales ({k, ks, v, vs}) and decode attends through the quantized
    flash-decode kernel — half the HBM traffic of the bf16 cache on
    the bandwidth-bound decode loop ("model" keeps the model dtype).
    """
    cfg = net.model.cfg
    params = _params_tree(net)
    q8 = kv_cache_dtype == "int8"
    H, K, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def prefill(params, ids, valid_len):
        B, T = ids.shape
        x = params["embed"][ids]
        positions = jnp.arange(T)
        cache = []
        for lp in params["layers"]:
            # THE training layer (llama_math.decoder_layer — same flash
            # -attention dispatch), with ragged prompt lengths; k/v come
            # back post-RoPE for the cache
            x, k, v = llama_math.decoder_layer(
                lp, x, positions, cfg.rms_eps, cfg.rope_base, H, K, d,
                lengths=valid_len, return_kv=True)
            # cache-native (B, K, S, d): one transpose per PREFILL, so
            # the per-token decode loop never copies the cache
            k_c = jnp.zeros((B, K, max_len, d), x.dtype)
            v_c = jnp.zeros_like(k_c)
            k_c = lax.dynamic_update_slice(
                k_c, k.transpose(0, 2, 1, 3), (0, 0, 0, 0))
            v_c = lax.dynamic_update_slice(
                v_c, v.transpose(0, 2, 1, 3), (0, 0, 0, 0))
            if q8:
                from ..kernels.flash_decode import quantize_kv
                k8_, ks_, v8_, vs_ = quantize_kv(k_c, v_c)
                cache.append({"k": k8_, "ks": ks_, "v": v8_,
                              "vs": vs_})
            else:
                cache.append({"k": k_c, "v": v_c})
        x = llama_math.rms(x, params["norm"], cfg.rms_eps)
        # logits at each batch row's last valid position
        idx = jnp.maximum(valid_len - 1, 0)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        return cache, last @ params["head"].T

    def step(params, cache, pos, tok):
        """pos: (B,) absolute position of `tok` (B,) being fed."""
        B = tok.shape[0]
        x = params["embed"][tok][:, None, :]  # (B, 1, D)

        def write_row(buf, row, p):
            # write the new token's K/V at (all kv heads, pos) in the
            # (K, S, ...) per-batch cache
            return jax.vmap(
                lambda b_, r_, p_: lax.dynamic_update_slice(
                    b_, r_, (0, p_) + (0,) * (b_.ndim - 2)))(
                        buf, row, p)

        new_cache = []
        for lp, c in zip(params["layers"], cache):
            q, k, v = llama_math.layer_qkv(lp, x, pos[:, None],
                                           cfg.rms_eps, cfg.rope_base,
                                           H, K, d)
            kt = k.transpose(0, 2, 1, 3)           # (B, K, 1, d)
            vt = v.transpose(0, 2, 1, 3)
            if q8:
                from ..kernels.flash_decode import (
                    flash_decode_quantized, quantize_kv)
                k8r, ksr, v8r, vsr = quantize_kv(kt, vt)
                nc = {"k": write_row(c["k"], k8r, pos),
                      "ks": write_row(c["ks"], ksr, pos),
                      "v": write_row(c["v"], v8r, pos),
                      "vs": write_row(c["vs"], vsr, pos)}
                att = flash_decode_quantized(
                    q[:, 0], nc["k"], nc["ks"], nc["v"], nc["vs"],
                    pos + 1)[:, None]
            else:
                nc = {"k": write_row(c["k"], kt, pos),
                      "v": write_row(c["v"], vt, pos)}
                att = _attend(q, nc["k"], nc["v"], pos + 1, cfg)
            x = llama_math.layer_finish(lp, x, att, cfg.rms_eps)
            new_cache.append(nc)
        return new_cache, llama_math.final_logits(params, x,
                                                  cfg.rms_eps)[:, 0]

    return params, prefill, step


def generate(net, prompt_ids, max_new_tokens: int, temperature=0.0,
             top_k: int = 0, top_p: float = 0.0, seed: int = 0,
             max_len: Optional[int] = None,
             kv_cache_dtype: str = "model",
             valid_len=None, eos_id: Optional[int] = None,
             return_finished: bool = False):
    """Autoregressive generation. prompt_ids: (B, T) NDArray/array of
    int32. Ragged prompts: right-pad shorter rows with any token and
    pass per-row true lengths as `valid_len` (B,) — padded positions
    are masked in prefill and each row's continuation starts at its
    own length. Generated tokens occupy columns [T, T+max_new) of the
    output regardless of the row's valid length.

    temperature 0 = greedy; top_k keeps the k best logits; top_p keeps
    the smallest nucleus whose probability mass reaches p (both
    compose with temperature). Scalars broadcast, or pass (B,) arrays
    for per-row sampling params.

    eos_id: rows freeze after emitting eos (remaining columns filled
    with eos) and decoding runs in fixed-size chunks so an early
    all-rows-finished batch stops paying for the tail.
    return_finished=True additionally returns (B,) finish positions —
    the index of eos within the generated tokens, or -1.

    Executables (prefill + scanned decode chunk) are built once per
    (shape, max_len, cache dtype, greedy/sample) signature and cached
    on the net via mxnet_tpu.serving.executables — repeat calls are
    warm, and sampling params never retrace (they are traced
    vectors). Returns (B, T + max_new_tokens) numpy."""
    from ..serving import executables as _exe

    ids = prompt_ids._data if isinstance(prompt_ids, NDArray) \
        else jnp.asarray(prompt_ids)
    ids = ids.astype(jnp.int32)
    B, T = ids.shape
    cfg = net.model.cfg
    if valid_len is None:
        valid = jnp.full((B,), T, jnp.int32)
    else:
        valid = jnp.asarray(
            valid_len.asnumpy() if isinstance(valid_len, NDArray)
            else valid_len).astype(jnp.int32).reshape(B)
        if not bool(jnp.all((valid >= 1) & (valid <= T))):
            raise ValueError("valid_len entries must lie in [1, T]")

    greedy = temperature is None or (
        _np.ndim(temperature) == 0 and float(temperature) <= 0.0)
    mode = "greedy" if greedy else "sample"

    # chunked decode: with an eos the scan runs CHUNK tokens at a
    # time so a finished batch exits early (and the chunk executable
    # is reused across every max_new_tokens). Without an eos a single
    # full-length chunk preserves the exact legacy cache footprint.
    if eos_id is None:
        chunk = max_new_tokens
    else:
        chunk = min(8, max_new_tokens)
    n_chunks = -(-max_new_tokens // chunk)
    padded_new = n_chunks * chunk
    cap = max_len or cfg.max_seq_len
    if T + padded_new > cap:          # cap hit: one exact-size chunk
        chunk, n_chunks, padded_new = max_new_tokens, 1, max_new_tokens
    if max_len is None:
        max_len = min(cfg.max_seq_len, T + padded_new)
    assert T + max_new_tokens <= max_len, "max_len too small"

    dec = _exe.decoder_programs(net, max_len, kv_cache_dtype)
    scan = _exe.scan_program(net, max_len, kv_cache_dtype, mode)
    params = _params_tree(net)
    cache, logits = dec["prefill"](params, ids, valid)

    as_vec = lambda v, dt: jnp.broadcast_to(
        jnp.asarray(v, dt), (B,)) if v is not None \
        else jnp.zeros((B,), dt)
    temps = as_vec(temperature, jnp.float32)
    ks = as_vec(top_k, jnp.int32)
    ps = as_vec(top_p, jnp.float32)
    eos = jnp.int32(-1 if eos_id is None else eos_id)
    finished = jnp.zeros((B,), bool)
    pos = valid

    if mode == "sample":
        all_keys = jax.random.split(jax.random.PRNGKey(seed),
                                    n_chunks * chunk)
    else:  # scanned over but never read
        all_keys = jnp.zeros((n_chunks * chunk, 2), jnp.uint32)

    pieces = []
    emitted = 0
    for c in range(n_chunks):
        cache, logits, pos, finished, toks = scan(
            params, cache, logits, pos, finished, eos, temps, ks, ps,
            all_keys[c * chunk:(c + 1) * chunk])
        pieces.append(_np.asarray(toks))         # (chunk, B)
        emitted += chunk
        if eos_id is not None and emitted < padded_new \
                and bool(_np.asarray(finished).all()):
            # every row froze: the remaining scans would only emit
            # eos — skip them (the early exit the satellite asks for)
            pieces.append(_np.full((padded_new - emitted, B), eos_id,
                                   _np.int32))
            break

    toks = _np.concatenate(pieces, axis=0)[:max_new_tokens]
    out = _np.concatenate([_np.asarray(ids), toks.T.astype(_np.int32)],
                          axis=1)
    if not return_finished:
        return out
    gen = out[:, T:]
    if eos_id is None:
        finish_pos = _np.full((B,), -1, _np.int64)
    else:
        hit = gen == eos_id
        finish_pos = _np.where(hit.any(axis=1), hit.argmax(axis=1), -1)
    return out, finish_pos


def generate_beam(net, prompt_ids, max_new_tokens: int, beam_size=4,
                  eos_id: Optional[int] = None, length_penalty=1.0,
                  max_len: Optional[int] = None,
                  kv_cache_dtype: str = "model"):
    """Beam-search decoding over the cached decoder (reference
    analogue: GluonNLP's BeamSearchSampler; the MT twin lives in
    models/beam_search.py). Static shapes throughout: (B*W) rows ride
    the same jitted step as sampling; beam bookkeeping is vectorized
    top-k over (B, W*V). Finished beams are frozen by forcing eos at
    log-prob 0. Returns (B, T + max_new_tokens) numpy — the best beam
    per batch row under score / len**length_penalty."""
    from ..serving import executables as _exe

    ids = prompt_ids._data if isinstance(prompt_ids, NDArray) \
        else jnp.asarray(prompt_ids)
    ids = ids.astype(jnp.int32)
    B, T = ids.shape
    W = beam_size
    cfg = net.model.cfg
    max_len = max_len or min(cfg.max_seq_len, T + max_new_tokens)
    assert T + max_new_tokens <= max_len, "max_len too small"
    # persistent executables shared with generate(): prefill and the
    # (B*W)-row step compile once per signature and stay cached
    dec = _exe.decoder_programs(net, max_len,
                                kv_cache_dtype=kv_cache_dtype)
    params = _params_tree(net)
    valid = jnp.full((B,), T, jnp.int32)
    cache, logits = dec["prefill"](params, ids, valid)

    # expand every batch row to W beams (contiguous blocks of W)
    rep = lambda x: jnp.repeat(x, W, axis=0)
    cache = jax.tree_util.tree_map(rep, cache)
    logits = rep(logits)                         # (B*W, V)
    V = logits.shape[-1]
    pos = rep(valid)                             # (B*W,)
    # only beam 0 is live initially, so the first top-k is not W
    # copies of the same candidate
    scores = jnp.full((B, W), -jnp.inf).at[:, 0].set(0.0)
    finished = jnp.zeros((B, W), bool)
    lengths = jnp.zeros((B, W), jnp.int32)
    toks = jnp.zeros((B, W, max_new_tokens), jnp.int32)

    from .beam_search import beam_expand_topk

    jstep = dec["step"]
    for t in range(max_new_tokens):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1) \
            .reshape(B, W, V)
        was_finished = finished
        scores, src, tok, finished = beam_expand_topk(
            scores, logp, finished, eos_id)
        gather = (jnp.arange(B)[:, None] * W + src).reshape(-1)
        toks = jnp.take_along_axis(toks, src[..., None], axis=1) \
            .at[:, :, t].set(tok)
        lengths = jnp.take_along_axis(lengths, src, axis=1)
        lengths = jnp.where(
            jnp.take_along_axis(was_finished, src, axis=1), lengths,
            lengths + 1)
        if eos_id is not None and bool(jnp.all(finished)):
            # remaining positions: eos padding (consistent with the
            # frozen-beam continuation the loop would have produced)
            toks = toks.at[:, :, t + 1:].set(eos_id)
            break
        if t < max_new_tokens - 1:  # last selection needs no logits
            cache = jax.tree_util.tree_map(lambda x: x[gather], cache)
            pos = pos[gather]
            cache, logits = jstep(params, cache, pos, tok.reshape(-1))
            pos = pos + 1

    norm = jnp.maximum(lengths, 1).astype(jnp.float32) ** length_penalty
    best = jnp.argmax(scores / norm, axis=1)      # (B,)
    best_toks = jnp.take_along_axis(
        toks, best[:, None, None], axis=1)[:, 0]  # (B, max_new)
    out = jnp.concatenate([ids, best_toks], axis=1)
    return _np.asarray(out)
