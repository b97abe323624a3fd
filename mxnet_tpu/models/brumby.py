"""Power-retention decoder (Manifest AI's Brumby family) as a Gluon net:
the Qwen3 block with its softmax attention replaced by power retention
(a gated linear attention over a degree-2 feature map), every layer the
same kind, an untied head.

The forward is `retention_math`'s functions and nothing else; serving
takes the same functions through `BrumbyDecoder`, all of whose layers
are RECURRENT: a sequence keeps a fixed-size matrix-valued state a layer
and nothing a token, so the net is served with no block pool at all.
Inference only: the kernels have no backward.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import initializer as _init
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ndarray import invoke
from . import register_model, retention_math
from .decoder import RECURRENT, DecoderDescription

__all__ = ["BrumbyConfig", "BrumbyForCausalLM", "BrumbyDecoder",
           "brumby", "brumby_tiny"]


class BrumbyConfig:
    def __init__(self, vocab_size=151936, hidden_size=5120,
                 intermediate_size=17408, num_layers=40, num_heads=40,
                 num_kv_heads=8, head_dim=128, rope_base=1e6,
                 rms_eps=1e-6, max_seq_len=32768, dtype="bfloat16",
                 retention_degree=2, retention_eps=1e-6):
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads are no whole "
                             f"groups on {num_kv_heads} kv heads")
        if retention_degree != 2:
            raise NotImplementedError(
                f"retention_degree {retention_degree}: the feature map "
                "of kernels/power_retention.py is the degree-2 one "
                "(the products of pairs of entries)")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.rope_base = rope_base
        self.rms_eps = rms_eps
        self.max_seq_len = max_seq_len
        self.dtype = dtype
        self.retention_degree = retention_degree
        #: the normaliser's floor: y = num / (den + retention_eps)
        self.retention_eps = float(retention_eps)


def _layer_shapes(cfg):
    D, I = cfg.hidden_size, cfg.intermediate_size
    H, K, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {"ln_in": (D,), "wq": (H * d, D), "wk": (K * d, D),
            "wv": (K * d, D), "q_norm": (d,), "k_norm": (d,),
            "wg": (K, D), "bg": (K,), "wo": (D, H * d), "ln_ff": (D,),
            "gate": (I, D), "up": (I, D), "down": (D, I)}


class BrumbyLayer(HybridBlock):
    """One layer's parameters under `retention_math`'s role names; the
    forward is one invoke of its whole-layer function."""

    def __init__(self, cfg: BrumbyConfig, **kw):
        super().__init__(**kw)
        self.cfg = cfg
        shapes = _layer_shapes(cfg)
        self.roles = tuple(shapes)
        for role, shape in shapes.items():
            # a default a forward can run on (the gate's bias at 4: a
            # horizon of ~55 positions); a checkpoint or the benchmark's
            # seeded weights replace it
            init = "ones" if role in ("ln_in", "ln_ff", "q_norm",
                                      "k_norm") else \
                _init.Constant(4.0) if role == "bg" else None
            setattr(self, role, Parameter(
                role, shape=shape, init=init,
                dtype="float32" if role == "bg" else cfg.dtype))

    def forward(self, x):
        cfg, roles = self.cfg, self.roles

        def f(xr, *ws):
            return retention_math.retention_layer(
                dict(zip(roles, ws)), xr, cfg,
                jnp.arange(xr.shape[1]))[0]

        return invoke(f, [x] + [getattr(self, r).data() for r in roles])


class BrumbyModel(HybridBlock):
    def __init__(self, cfg: BrumbyConfig, **kw):
        super().__init__(**kw)
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         dtype=cfg.dtype)
        self.layers = nn.HybridSequential()
        for _ in range(cfg.num_layers):
            self.layers.add(BrumbyLayer(cfg))
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)

    def forward(self, input_ids):
        return self.norm(self.layers(self.embed_tokens(input_ids)))


class BrumbyForCausalLM(HybridBlock):
    def __init__(self, cfg: BrumbyConfig, **kw):
        super().__init__(**kw)
        self.model = BrumbyModel(cfg)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                flatten=False, dtype=cfg.dtype,
                                in_units=cfg.hidden_size,
                                weight_initializer=None)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

    def decoder(self):
        """The serving executables' description of this net
        (models/decoder.py)."""
        return BrumbyDecoder(self.model.cfg)


class BrumbyDecoder(DecoderDescription):
    """Brumby for the serving executables: every layer RECURRENT, with
    positions. Implemented: plain prefill and decode. NOT implemented,
    and refused by name through `require`: chunked prefill (the state
    would have to be carried from server chunk to server chunk),
    speculation (a rejected draft cannot be rewound out of a state),
    the prefix cache and the tiered cache (a state is not per-token: a
    prefix hit needs a snapshot at the shared length), int8 and
    LoRA."""

    supports = frozenset()

    def __init__(self, cfg):
        super().__init__(cfg)
        self.layer_kinds = (RECURRENT,) * cfg.num_layers

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
        return params["embed"][ids]

    def state_shapes(self):
        from ..kernels.power_retention import state_shapes

        return state_shapes(self.cfg.num_kv_heads, self.cfg.head_dim)

    def prefill_recurrent(self, li, lp, x, positions, lengths):
        return retention_math.retention_layer(
            lp, x, self.cfg, positions, lengths) + (None,)

    def decode_recurrent(self, li, lp, x, positions, state, active):
        return retention_math.retention_layer_step(
            lp, x, self.cfg, positions, state, active) + (None,)


@register_model("brumby")
def brumby(**kw):
    """Brumby-14B-Base's published sizes by default."""
    return BrumbyForCausalLM(BrumbyConfig(**kw))


@register_model("brumby_tiny")
def brumby_tiny(**kw):
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16,
               rope_base=10000.0, max_seq_len=256, dtype="float32")
    cfg.update(kw)
    return BrumbyForCausalLM(BrumbyConfig(**cfg))
