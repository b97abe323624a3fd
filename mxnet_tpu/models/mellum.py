"""Mellum decoder (JetBrains Mellum 2, `model_type` `mellum`) as a Gluon
net that TRAINS: GQA with q / k norm, sliding-window layers beside full
ones with a rotation of its own each (RoPE / YaRN), and in every layer
a dropless sparse expert layer (softmax router, top-k normalised, no
shared expert).

The forward is `mellum_math`'s functions and nothing else. As
`AfmoeConfig` has it, the constructor takes the PUBLISHED expert count
(the router's width) and the range of experts HELD here separately:
with `held_experts=(lo, n)` the net is one chip's share of an
expert-parallel deployment — it routes over all `num_experts`, holds
the weights of experts [lo, lo + n) and computes their part of the
routed sum, forward and backward (`parallel/moe.py::held_expert_ffn`).

`net(ids)` returns `(logits, counts)`: counts is int32 (3,), the sparse
layers' `mellum_math.COUNTS` summed. Lowered with
`ParallelPlan().lower(net, loss_fn, opt, counts=net.counts)` the counts
leave the compiled step beside the loss and ride `mx.train_step`
(parallel/data_parallel.py); `loss_fn` sees the logits alone.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ndarray import invoke
from . import mellum_math, register_model
from .afmoe import held_range, layer_kinds
from .mellum_math import COUNTS, FULL, SLIDING

__all__ = ["MellumConfig", "MellumForCausalLM", "mellum", "mellum_tiny"]


class MellumConfig:
    def __init__(self, vocab_size=98304, hidden_size=2304,
                 moe_intermediate_size=896, num_layers=28,
                 layer_types=None, num_heads=32, num_kv_heads=4,
                 head_dim=128, num_experts=64, held_experts=None,
                 top_k=8, window=1024, rope_base=500000.0,
                 yarn_factor=16.0, yarn_original=8192,
                 yarn_beta_fast=32.0, yarn_beta_slow=1.0,
                 attention_factor=1.2772588722239782, rms_eps=1e-6,
                 max_seq_len=131072, dtype="bfloat16", remat=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_layers = num_layers
        self.layer_kinds = layer_kinds(layer_types, num_layers)
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.num_experts = num_experts
        self.held_lo, self.num_held = held_range(held_experts,
                                                 num_experts)
        self.top_k = top_k
        self.window = window
        self.rope_base = rope_base
        self.yarn_factor = yarn_factor
        self.yarn_original = yarn_original
        self.yarn_beta_fast = yarn_beta_fast
        self.yarn_beta_slow = yarn_beta_slow
        self.attention_factor = attention_factor
        self.rms_eps = rms_eps
        self.max_seq_len = max_seq_len
        self.dtype = dtype
        # per-layer rematerialisation (as LlamaConfig.remat)
        self.remat = remat


class MellumLayer(HybridBlock):
    """One layer's parameters under `mellum_math`'s role names (norm
    gains float32, the rest in `cfg.dtype`); the forward is one invoke
    of `mellum_math.decoder_layer`, returning (x, counts)."""

    def __init__(self, cfg: MellumConfig, index: int, **kw):
        super().__init__(**kw)
        self.cfg = cfg
        self.kind = cfg.layer_kinds[index]
        D, H, K, d = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim)
        I, E, n = (cfg.moe_intermediate_size, cfg.num_experts,
                   cfg.num_held)
        shapes = {"ln_in": (D,), "wq": (H * d, D), "wk": (K * d, D),
                  "wv": (K * d, D), "q_norm": (d,), "k_norm": (d,),
                  "wo": (D, H * d), "ln_mlp": (D,), "router": (E, D),
                  "ex_gate": (n, D, I), "ex_up": (n, D, I),
                  "ex_down": (n, I, D)}
        self.roles = tuple(shapes)
        for role, shape in shapes.items():
            gain = len(shape) == 1
            setattr(self, role, Parameter(
                role, shape=shape, dtype="float32" if gain else cfg.dtype,
                init="ones" if gain else None))

    def forward(self, x):
        cfg, kind, roles = self.cfg, self.kind, self.roles

        def f(xr, *ws):
            return mellum_math.decoder_layer(
                dict(zip(roles, ws)), xr, jnp.arange(xr.shape[1]), cfg,
                kind)

        return invoke(f, [x] + [getattr(self, r).data() for r in roles],
                      n_out=2)


class MellumModel(HybridBlock):
    def __init__(self, cfg: MellumConfig, **kw):
        super().__init__(**kw)
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         dtype=cfg.dtype)
        self.layers = nn.HybridSequential()
        for i in range(cfg.num_layers):
            self.layers.add(MellumLayer(cfg, i))
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        counts = None
        for layer in self.layers:
            x, c = layer(x)
            counts = c if counts is None else counts + c
        return self.norm(x), counts


class MellumForCausalLM(HybridBlock):
    #: the names of `counts`' entries, for `ParallelPlan.lower(counts=)`
    counts = COUNTS

    def __init__(self, cfg: MellumConfig, **kw):
        super().__init__(**kw)
        self.model = MellumModel(cfg)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                flatten=False, dtype=cfg.dtype,
                                in_units=cfg.hidden_size,
                                weight_initializer=None)

    def forward(self, input_ids):
        h, counts = self.model(input_ids)
        return self.lm_head(h), counts


@register_model("mellum")
def mellum(**kw):
    """Mellum2-12B-A2.5B's published sizes by default; pass
    `num_experts` (published) and `held_experts=(lo, n)` (held here)
    separately."""
    return MellumForCausalLM(MellumConfig(**kw))


@register_model("mellum_tiny")
def mellum_tiny(**kw):
    cfg = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
               num_layers=4, layer_types=[SLIDING, SLIDING, SLIDING, FULL],
               num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
               top_k=2, window=16, yarn_original=32, max_seq_len=512,
               dtype="float32")
    cfg.update(kw)
    return MellumForCausalLM(MellumConfig(**cfg))
