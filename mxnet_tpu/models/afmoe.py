"""afmoe decoder (Arcee Trinity family) as a Gluon net: gated GQA with
q/k norm, sandwich norms, sliding-window layers beside full ones, dense
layers first and sparse expert layers after (sigmoid router with a
selection bias, one shared expert, top-k of `num_experts`).

The forward is `afmoe_math`'s functions and nothing else; serving takes
the same functions through `AfmoeDecoder`. The constructor takes the
PUBLISHED expert count (the router's width) and the range of experts
HELD here separately: with `held_experts=(lo, n)` the net is one
chip's share of an expert-parallel deployment — it routes over all
`num_experts`, holds the weights of experts [lo, lo + n) and computes
their part of the routed sum (`parallel/moe.py::held_expert_ffn`).
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ndarray import invoke
from . import afmoe_math, register_model
from .afmoe_math import FULL, SLIDING
from .decoder import DecoderDescription

__all__ = ["AfmoeConfig", "AfmoeForCausalLM", "AfmoeDecoder",
           "afmoe", "afmoe_tiny"]


def layer_kinds(layer_types, num_layers):
    """(SLIDING | FULL, ...) of a config's `layer_types` (None: the
    published pattern, every 4th layer full)."""
    if layer_types is None:
        layer_types = [FULL if (i + 1) % 4 == 0 else SLIDING
                       for i in range(num_layers)]
    kinds = tuple(str(t).replace("_attention", "") for t in layer_types)
    if len(kinds) != num_layers or set(kinds) - {FULL, SLIDING}:
        raise ValueError(f"layer_types must name {num_layers} "
                         f"layers as {FULL!r} or {SLIDING!r}: "
                         f"{layer_types}")
    return kinds


def held_range(held_experts, num_experts):
    """(lo, n) of the experts held here (None: all of them)."""
    lo, n = held_experts if held_experts is not None \
        else (0, num_experts)
    if not (0 <= lo and n >= 1 and lo + n <= num_experts):
        raise ValueError(f"held_experts {(lo, n)} is no range of "
                         f"the {num_experts} experts")
    return int(lo), int(n)


class AfmoeConfig:
    def __init__(self, vocab_size=200192, hidden_size=3072,
                 intermediate_size=12288, moe_intermediate_size=3072,
                 num_layers=60, num_dense_layers=6, layer_types=None,
                 num_heads=48, num_kv_heads=8, head_dim=128,
                 num_experts=256, held_experts=None, top_k=4,
                 route_scale=2.448, window=4096, rope_base=10000.0,
                 rms_eps=1e-5, mup_enabled=True, max_seq_len=262144,
                 dtype="bfloat16"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_layers = num_layers
        self.num_dense_layers = num_dense_layers
        self.layer_kinds = layer_kinds(layer_types, num_layers)
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.num_experts = num_experts
        self.held_lo, self.num_held = held_range(held_experts,
                                                 num_experts)
        self.top_k = top_k
        self.route_scale = route_scale
        self.window = window
        self.rope_base = rope_base
        self.rms_eps = rms_eps
        self.embed_scale = math.sqrt(hidden_size) if mup_enabled else 1.0
        self.max_seq_len = max_seq_len
        self.dtype = dtype


class AfmoeLayer(HybridBlock):
    """One layer's parameters under `afmoe_math`'s role names; the
    forward is one invoke of `afmoe_math.decoder_layer`."""

    def __init__(self, cfg: AfmoeConfig, index: int, **kw):
        super().__init__(**kw)
        self.cfg = cfg
        self.kind = cfg.layer_kinds[index]
        D, H, K, d = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim)
        shapes = {"ln_in": (D,), "wq": (H * d, D), "wk": (K * d, D),
                  "wv": (K * d, D), "wg": (H * d, D), "wo": (D, H * d),
                  "q_norm": (d,), "k_norm": (d,), "ln_post_attn": (D,),
                  "ln_pre_mlp": (D,), "ln_post_mlp": (D,)}
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
            init = "ones" if len(shape) == 1 and role != "bias" else \
                "zeros" if role == "bias" else None
            setattr(self, role, Parameter(role, shape=shape,
                                          dtype=cfg.dtype, init=init))

    def forward(self, x):
        cfg, kind, roles = self.cfg, self.kind, self.roles

        def f(xr, *ws):
            return afmoe_math.decoder_layer(
                dict(zip(roles, ws)), xr, jnp.arange(xr.shape[1]), cfg,
                kind)[0]

        return invoke(f, [x] + [getattr(self, r).data() for r in roles])


class AfmoeModel(HybridBlock):
    def __init__(self, cfg: AfmoeConfig, **kw):
        super().__init__(**kw)
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         dtype=cfg.dtype)
        self.layers = nn.HybridSequential()
        for i in range(cfg.num_layers):
            self.layers.add(AfmoeLayer(cfg, i))
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids) * self.cfg.embed_scale
        return self.norm(self.layers(x))


class AfmoeForCausalLM(HybridBlock):
    def __init__(self, cfg: AfmoeConfig, **kw):
        super().__init__(**kw)
        self.model = AfmoeModel(cfg)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                flatten=False, dtype=cfg.dtype,
                                in_units=cfg.hidden_size,
                                weight_initializer=None)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

    def decoder(self):
        """The serving executables' description of this net
        (models/decoder.py)."""
        return AfmoeDecoder(self.model.cfg)


class AfmoeDecoder(DecoderDescription):
    """afmoe for the serving executables: FULL and SLIDING layers as
    the config lists them, `afmoe_math`'s functions, and two counts a
    tick from the expert layers (`pairs`: token-expert pairs that fell
    on held experts; `touched`: held experts with at least one row;
    both summed over the expert layers)."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.layer_kinds = cfg.layer_kinds
        self.window = cfg.window if SLIDING in cfg.layer_kinds else None
        self.counts = ("pairs", "touched") \
            if cfg.num_dense_layers < cfg.num_layers else ()

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
        return afmoe_math.embed(params, ids, self.cfg)

    def prefill_layer(self, li, lp, x, positions, lengths, lora=None):
        return afmoe_math.decoder_layer(lp, x, positions, self.cfg,
                                        self.layer_kinds[li],
                                        lengths=lengths)

    def layer_qkv(self, li, lp, x, positions, lora=None):
        return afmoe_math.layer_qkv(lp, x, positions, self.cfg,
                                    self.layer_kinds[li])

    def layer_finish(self, li, lp, x, att, carry, lora=None,
                     valid=None):
        return afmoe_math.layer_finish(lp, x, att, carry, self.cfg,
                                       valid)


@register_model("afmoe")
def afmoe(**kw):
    """Arcee Trinity-Large-Preview's published sizes by default; pass
    `num_experts` (published) and `held_experts=(lo, n)` (held here)
    separately."""
    return AfmoeForCausalLM(AfmoeConfig(**kw))


@register_model("afmoe_tiny")
def afmoe_tiny(**kw):
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_layers=5,
               num_dense_layers=1,
               layer_types=[SLIDING, SLIDING, SLIDING, SLIDING, FULL],
               num_heads=4, num_kv_heads=2, head_dim=16, num_experts=16,
               top_k=2, window=32, max_seq_len=256, dtype="float32")
    cfg.update(kw)
    return AfmoeForCausalLM(AfmoeConfig(**cfg))
