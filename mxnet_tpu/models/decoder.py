"""What the serving executables need to know of a decoder.

`serving/executables.py::paged_programs` and `InferenceServer` name no
model: they ask the net for its `DecoderDescription` (`net.decoder()`)
and build one prefill and one decode body from it. A description gives
the layer kinds (a FULL layer caches every position, a SLIDING one the
last `window`, a RECURRENT one a fixed-size state a sequence and
nothing a token), the parameter tree, and the layer in the forms the
programs use: an attention layer whole (prefill) and split around the
paged attention call (decode); a recurrent layer whole, handing back
the state at each row's length (prefill), and for one token of every
row, taking and returning the rows' states (decode), both with the
positions (a state-space layer ignores them, a retention layer rotates
its queries and keys by them). A decoder whose layers are ALL recurrent
is served with no block pool and no table. A LATENT layer
caches every position like a FULL one, but ONE row a position that all
its heads share and read twice, as keys whole and as values by its
first `latent` entries (`latent_shapes`): its `prefill_layer` and
`layer_qkv` hand back that row as `k` and None as `v`, and its queries
enter the paged call as wide as the row. The Llama block is the first
description (`llama_infer.LlamaDecoder`), afmoe the second
(`afmoe.AfmoeDecoder`), Jamba the third (`jamba.JambaDecoder`), Sarvam's
latent attention the fourth (`sarvam.SarvamDecoder`), power retention
the fifth (`brumby.BrumbyDecoder`: every layer RECURRENT, a
matrix-valued state).
"""
from __future__ import annotations

FULL, SLIDING, RECURRENT, LATENT = "full", "sliding", "recurrent", \
    "latent"


class DecoderDescription:
    """Base of the descriptions. `cfg` carries num_layers, num_heads,
    num_kv_heads, head_dim, vocab_size, rms_eps, dtype.

    layer_kinds  one of FULL / SLIDING / RECURRENT / LATENT a layer
    window       positions a SLIDING layer attends, else None
    counts       names of the int32 counts `layer_finish` /
                 `prefill_layer` return a layer (summed over layers
                 and handed back by the decode program), () for none
    supports     the server features the description's layer functions
                 implement, of: prefill_chunk, speculative, lora, int8,
                 prefix_cache, kv_tier
    decode_compiler_options
                 options the decode program is compiled with on a TPU,
                 None for the compiler's defaults
    """

    layer_kinds = ()
    window = None
    counts = ()
    supports = frozenset()
    decode_compiler_options = None

    def __init__(self, cfg):
        self.cfg = cfg

    @property
    def mixed(self):
        """True where the cache has to hold two kinds of attention
        layer, each with a pool and a table of its own."""
        return SLIDING in self.layer_kinds

    @property
    def recurrent(self):
        """True where some layer keeps a state a sequence."""
        return RECURRENT in self.layer_kinds

    @property
    def latent(self):
        """True where some layer caches one shared row a position."""
        return LATENT in self.layer_kinds

    def layer_window(self, li):
        return self.window if self.layer_kinds[li] == SLIDING else None

    def require(self, feature, what):
        if feature not in self.supports:
            raise NotImplementedError(
                f"{what} is not implemented for {type(self).__name__} "
                f"(layer kinds {sorted(set(self.layer_kinds))} of "
                f"{[FULL, SLIDING, RECURRENT, LATENT]}, counts "
                f"{list(self.counts)}; it implements "
                f"{sorted(self.supports) or 'plain prefill and decode'}"
                f"): serve this net without it")

    # -- what a description implements ------------------------------------
    def params_tree(self, net):
        """{"embed", "norm", "head", "layers": [lp, ...]}."""
        raise NotImplementedError

    def embed(self, params, ids):
        raise NotImplementedError

    def prefill_layer(self, li, lp, x, positions, lengths, lora=None):
        """The whole layer on (B, T, D) -> (x, k, v, counts | None)."""
        raise NotImplementedError

    # -- a RECURRENT layer's forms ------------------------------------------
    def state_shapes(self):
        """{name: (shape a sequence, dtype)} of a RECURRENT layer's
        state: what the cache keeps a slot a layer."""
        raise NotImplementedError

    def prefill_recurrent(self, li, lp, x, positions, lengths):
        """The whole layer on (B, T, D) at `positions` (T,) from a zero
        state -> (x, state, counts | None): `state` {name: (B,) + shape}
        as it stands after each row's `lengths` positions (right padding
        must not advance it)."""
        raise NotImplementedError

    def decode_recurrent(self, li, lp, x, positions, state, active):
        """One token of every row, x (B, 1, D) at `positions` (B,),
        `state` the rows' states -> (x, state, counts | None). A row
        whose `active` is False hands its state back untouched."""
        raise NotImplementedError

    # -- a LATENT layer's cache ----------------------------------------------
    def latent_shapes(self):
        """What the paged call of a LATENT layer takes: {"latent": the
        entries of a cached row read as values (its first ones; the
        scores read the whole row, `cfg.head_dim` wide), "scale": the
        softmax scale}."""
        raise NotImplementedError

    def layer_qkv(self, li, lp, x, positions, lora=None):
        """-> (q, k, v, carry): k, v as the cache stores them, `carry`
        whatever `layer_finish` needs beside the attention output. A
        LATENT layer: q (B, T, H, row), k (B, T, 1, row), v None, and
        `layer_finish` is given att (B, T, H, latent)."""
        raise NotImplementedError

    def layer_finish(self, li, lp, x, att, carry, lora=None,
                     valid=None):
        """-> (x, counts | None). `valid` (B, T) bool marks the rows
        that hold a token (idle slots and padding do not)."""
        raise NotImplementedError
