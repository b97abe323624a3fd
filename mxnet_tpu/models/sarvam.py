"""Sarvam-105B's decoder (`sarvam_mla`) as a Gluon net: latent
attention (MLA: one cached row `[latent | rotated key]` a position,
shared by all heads) with YaRN-scaled rotation, one leading dense
layer, then sparse expert layers (sigmoid router with a selection bias,
one shared expert, top-k of `num_experts`).

The forward is `mla_math`'s functions and nothing else; serving takes
the same functions through `SarvamDecoder`, whose layers are LATENT
(models/decoder.py): prefill materialises keys and values from the
latent, decode attends the cached rows themselves. As afmoe's, the
constructor takes the PUBLISHED expert count (the router's width) and
the range of experts HELD here separately (`held_experts=(lo, n)`).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ndarray import invoke
from . import mla_math, register_model
from .decoder import LATENT, DecoderDescription

__all__ = ["SarvamConfig", "SarvamForCausalLM", "SarvamDecoder",
           "sarvam_mla", "sarvam_mla_tiny"]


class SarvamConfig:
    def __init__(self, vocab_size=262144, hidden_size=4096,
                 intermediate_size=16384, moe_intermediate_size=2048,
                 num_layers=32, num_dense_layers=1, num_heads=64,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, num_experts=128,
                 held_experts=None, top_k=8, route_scale=2.5,
                 rope_base=10000.0, rope_factor=40.0,
                 rope_original=4096, beta_fast=32, beta_slow=1,
                 mscale=1.0, mscale_all_dim=1.0, rms_eps=1e-6,
                 max_seq_len=131072, dtype="bfloat16"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_layers = num_layers
        self.num_dense_layers = num_dense_layers
        self.num_heads = num_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.q_head_dim = qk_nope_head_dim + qk_rope_head_dim
        #: a cached row in the pool: latent + rotated key, zero-padded
        #: to whole 128-lane tiles (docs/serving.md: the TPU lays a
        #: narrower row out that wide anyway)
        self.cache_row = -(-(kv_lora_rank + qk_rope_head_dim) // 128) * 128
        # what the cache is told of a layer: one "kv head" of a row
        self.num_kv_heads, self.head_dim = 1, self.cache_row
        self.num_experts = num_experts
        lo, n = held_experts if held_experts is not None \
            else (0, num_experts)
        if not (0 <= lo and n >= 1 and lo + n <= num_experts):
            raise ValueError(f"held_experts {(lo, n)} is no range of "
                             f"the {num_experts} experts")
        self.held_lo, self.num_held = int(lo), int(n)
        self.top_k = top_k
        self.route_scale = route_scale
        self.rope_base = rope_base
        self.rope_factor = rope_factor
        self.mscale, self.mscale_all_dim = mscale, mscale_all_dim
        if mla_math.yarn_mscale(rope_factor, mscale) != \
                mla_math.yarn_mscale(rope_factor, mscale_all_dim):
            raise NotImplementedError(
                "mscale != mscale_all_dim scales the rotation's cos and "
                "sin; mla_math applies no such scale")
        self.rope_inv_freq = mla_math.yarn_inv_freq(
            qk_rope_head_dim, rope_base, rope_factor, rope_original,
            beta_fast, beta_slow)
        self.rms_eps = rms_eps
        self.max_seq_len = max_seq_len
        self.dtype = dtype


class SarvamLayer(HybridBlock):
    """One layer's parameters under `mla_math`'s role names; the
    forward is one invoke of `mla_math.decoder_layer`."""

    def __init__(self, cfg: SarvamConfig, index: int, **kw):
        super().__init__(**kw)
        self.cfg = cfg
        D, H, L = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
        shapes = {"ln_in": (D,), "wq": (H * cfg.q_head_dim, D),
                  "q_norm": (cfg.q_head_dim,),
                  "wkv_a": (L + cfg.qk_rope_head_dim, D),
                  "kv_norm": (L,),
                  "wkv_b": (H * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                            L),
                  "wo": (D, H * cfg.v_head_dim), "ln_mlp": (D,)}
        if index < cfg.num_dense_layers:
            I = cfg.intermediate_size
            shapes.update(gate=(I, D), up=(I, D), down=(D, I))
        else:
            I, E, n = (cfg.moe_intermediate_size, cfg.num_experts,
                       cfg.num_held)
            shapes.update(router=(E, D), bias=(E,), sh_gate=(I, D),
                          sh_up=(I, D), sh_down=(D, I),
                          ex_gate=(n, D, I), ex_up=(n, D, I),
                          ex_down=(n, I, D))
        self.roles = tuple(shapes)
        for role, shape in shapes.items():
            init = "zeros" if role == "bias" else \
                "ones" if len(shape) == 1 else None
            setattr(self, role, Parameter(role, shape=shape,
                                          dtype=cfg.dtype, init=init))

    def forward(self, x):
        cfg, roles = self.cfg, self.roles

        def f(xr, *ws):
            return mla_math.decoder_layer(
                dict(zip(roles, ws)), xr, jnp.arange(xr.shape[1]), cfg)[0]

        return invoke(f, [x] + [getattr(self, r).data() for r in roles])


class SarvamModel(HybridBlock):
    def __init__(self, cfg: SarvamConfig, **kw):
        super().__init__(**kw)
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         dtype=cfg.dtype)
        self.layers = nn.HybridSequential()
        for i in range(cfg.num_layers):
            self.layers.add(SarvamLayer(cfg, i))
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)

    def forward(self, input_ids):
        return self.norm(self.layers(self.embed_tokens(input_ids)))


class SarvamForCausalLM(HybridBlock):
    def __init__(self, cfg: SarvamConfig, **kw):
        super().__init__(**kw)
        self.model = SarvamModel(cfg)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                flatten=False, dtype=cfg.dtype,
                                in_units=cfg.hidden_size,
                                weight_initializer=None)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

    def decoder(self):
        """The serving executables' description of this net
        (models/decoder.py)."""
        return SarvamDecoder(self.model.cfg)


class SarvamDecoder(DecoderDescription):
    """Sarvam's decoder for the serving executables: every layer
    LATENT, `mla_math`'s functions (prefill materialised, decode
    absorbed), and the expert layers' two counts a tick (`pairs`,
    `touched`, as afmoe's)."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.layer_kinds = (LATENT,) * cfg.num_layers
        self.counts = ("pairs", "touched") \
            if cfg.num_dense_layers < cfg.num_layers else ()

    def latent_shapes(self):
        cfg = self.cfg
        return {"latent": cfg.kv_lora_rank,
                "scale": mla_math.softmax_scale(cfg)}

    def params_tree(self, net):
        ps = {n: p.data()._data for n, p in net.collect_params().items()}
        layers = []
        for i, layer in enumerate(net.model.layers):
            pre = f"model.layers.{i}."
            layers.append({r: ps[pre + r] for r in layer.roles})
        return {"embed": ps["model.embed_tokens.weight"],
                "norm": ps["model.norm.gamma"],
                "head": ps["lm_head.weight"], "layers": layers}

    def embed(self, params, ids):
        return mla_math.embed(params, ids, self.cfg)

    def prefill_layer(self, li, lp, x, positions, lengths, lora=None):
        x, row, counts = mla_math.decoder_layer(lp, x, positions,
                                                self.cfg, lengths=lengths)
        return x, row, None, counts

    def layer_qkv(self, li, lp, x, positions, lora=None):
        q, row = mla_math.layer_qkv(lp, x, positions, self.cfg)
        return q, row, None, None

    def layer_finish(self, li, lp, x, att, carry, lora=None,
                     valid=None):
        return mla_math.layer_finish(lp, x, att, self.cfg, valid)


@register_model("sarvam_mla")
def sarvam_mla(**kw):
    """Sarvam-105B's published sizes by default; pass `num_experts`
    (published) and `held_experts=(lo, n)` (held here) separately."""
    return SarvamForCausalLM(SarvamConfig(**kw))


@register_model("sarvam_mla_tiny")
def sarvam_mla_tiny(**kw):
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_layers=3,
               num_dense_layers=1, num_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               num_experts=16, top_k=2, rope_original=32, rope_factor=8.0,
               max_seq_len=256, dtype="float32")
    cfg.update(kw)
    return SarvamForCausalLM(SarvamConfig(**cfg))
