"""Llama-3-style decoder (BASELINE.json stretch config: "Llama-3-8B —
stretch Gluon HybridBlock to modern LLM"). No direct reference file; built
the TPU way: RMSNorm + RoPE + GQA + SwiGLU, causal attention as one fusible
op (Pallas flash-attention kernel on TPU, jnp fallback elsewhere — see
kernels/flash_attention.py), parameters carry PartitionSpec annotations so
FusedTrainStep/GSPMD shard them tensor-parallel over the 'tp' mesh axis
(column-parallel qkv/gate/up, row-parallel o/down — Megatron layout, but
expressed as shardings, not comms).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import NDArray, invoke
from ..parallel.mesh import P
from . import llama_math, register_model

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama_tiny",
           "llama_3_8b"]


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=4096,
                 intermediate_size=14336, num_layers=32, num_heads=32,
                 num_kv_heads=8, max_seq_len=8192, rope_base=500000.0,
                 rms_eps=1e-5, dtype="bfloat16", remat=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = hidden_size // num_heads
        self.max_seq_len = max_seq_len
        self.rope_base = rope_base
        self.rms_eps = rms_eps
        self.dtype = dtype
        self.remat = remat


def _dense(units, in_units, dtype, sharding):
    d = nn.Dense(units, use_bias=False, flatten=False, dtype=dtype,
                 in_units=in_units,
                 weight_initializer=None)
    d.weight.sharding = sharding
    return d


class LlamaAttention(HybridBlock):
    """Parameter container for the attention projections (TP-annotated
    Dense blocks). The forward math lives in llama_math.decoder_layer —
    LlamaLayer routes one invoke through it — so there is exactly ONE
    definition of the attention computation (no drift between training
    and the cached-decode path)."""

    def __init__(self, cfg: LlamaConfig, **kw):
        super().__init__(**kw)
        self.cfg = cfg
        D, H, K, d = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim)
        self.q_proj = _dense(H * d, D, cfg.dtype, P("tp", None))
        self.k_proj = _dense(K * d, D, cfg.dtype, P("tp", None))
        self.v_proj = _dense(K * d, D, cfg.dtype, P("tp", None))
        self.o_proj = _dense(D, H * d, cfg.dtype, P(None, "tp"))


class LlamaMLP(HybridBlock):
    """Parameter container for the SwiGLU projections (see
    LlamaAttention's docstring — the math is llama_math.swiglu)."""

    def __init__(self, cfg: LlamaConfig, **kw):
        super().__init__(**kw)
        D, I = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = _dense(I, D, cfg.dtype, P("tp", None))
        self.up_proj = _dense(I, D, cfg.dtype, P("tp", None))
        self.down_proj = _dense(D, I, cfg.dtype, P(None, "tp"))


class LlamaLayer(HybridBlock):
    def __init__(self, cfg: LlamaConfig, **kw):
        super().__init__(**kw)
        self.cfg = cfg
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x):
        # the entire layer is ONE invoke over llama_math.decoder_layer
        # — the same function the cached-decode prefill runs — so the
        # training and inference architectures cannot drift apart
        cfg = self.cfg
        attn, mlp = self.self_attn, self.mlp
        weights = [self.input_layernorm.gamma.data(),
                   attn.q_proj.weight.data(),
                   attn.k_proj.weight.data(),
                   attn.v_proj.weight.data(),
                   attn.o_proj.weight.data(),
                   self.post_attention_layernorm.gamma.data(),
                   mlp.gate_proj.weight.data(),
                   mlp.up_proj.weight.data(),
                   mlp.down_proj.weight.data()]

        def f(xr, ln1, wq, wk, wv, wo, ln2, gate, up, down):
            lp = {"ln1": ln1, "wq": wq, "wk": wk, "wv": wv, "wo": wo,
                  "ln2": ln2, "gate": gate, "up": up, "down": down}
            return llama_math.decoder_layer(
                lp, xr, jnp.arange(xr.shape[1]), cfg.rms_eps,
                cfg.rope_base, cfg.num_heads, cfg.num_kv_heads,
                cfg.head_dim)

        return invoke(f, [x] + weights)


class LlamaModel(HybridBlock):
    def __init__(self, cfg: LlamaConfig, **kw):
        super().__init__(**kw)
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         dtype=cfg.dtype)
        self.embed_tokens.weight.sharding = P("tp", None)
        self.layers = nn.HybridSequential()
        for _ in range(cfg.num_layers):
            self.layers.add(LlamaLayer(cfg))
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        if self.cfg.remat:
            # rematerialize each layer's activations in backward
            # (jax.checkpoint; HBM <-> FLOPs trade, SURVEY §2 remat)
            for layer in self.layers:
                x = _remat_call(layer, x)
        else:
            x = self.layers(x)
        return self.norm(x)


def _remat_call(layer, x):
    import jax
    entry_params = layer.collect_params()
    names = list(entry_params.keys())
    vals = [entry_params[n].data()._data for n in names]

    def pure(xr, *pv):
        saved = [entry_params[n]._data._data for n in names]
        try:
            for n, v in zip(names, pv):
                entry_params[n]._data._data = v
            out = layer(NDArray(xr))
            return out._data
        finally:
            for n, s in zip(names, saved):
                entry_params[n]._data._data = s

    fn = jax.checkpoint(pure)
    return invoke(fn, [x] + [NDArray(v) for v in vals])


class LlamaForCausalLM(HybridBlock):
    def __init__(self, cfg: LlamaConfig, **kw):
        super().__init__(**kw)
        self.model = LlamaModel(cfg)
        self.lm_head = _dense(cfg.vocab_size, cfg.hidden_size, cfg.dtype,
                              P("tp", None))

    def forward(self, input_ids):
        h = self.model(input_ids)
        return self.lm_head(h)

    def decoder(self):
        """The serving executables' description of this net
        (models/decoder.py)."""
        from .llama_infer import LlamaDecoder
        return LlamaDecoder(self.model.cfg)


@register_model("llama_tiny")
def llama_tiny(**kw):
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, max_seq_len=128, dtype="float32",
                      **kw)
    return LlamaForCausalLM(cfg)


@register_model("llama_3_8b")
def llama_3_8b(**kw):
    return LlamaForCausalLM(LlamaConfig(**kw))
