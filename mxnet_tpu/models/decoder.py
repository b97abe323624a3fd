"""What the serving executables need to know of a decoder.

`serving/executables.py::paged_programs` and `InferenceServer` name no
model: they ask the net for its `DecoderDescription` (`net.decoder()`)
and build one prefill and one decode body from it. A description gives
the layer kinds (a FULL layer caches every position, a SLIDING one the
last `window`), the parameter tree, and the layer in the three forms
the programs use: whole (prefill), and split around the paged attention
call (decode). The Llama block is the first description
(`llama_infer.LlamaDecoder`), afmoe the second (`afmoe.AfmoeDecoder`).
"""
from __future__ import annotations

FULL, SLIDING = "full", "sliding"


class DecoderDescription:
    """Base of the descriptions. `cfg` carries num_layers, num_heads,
    num_kv_heads, head_dim, vocab_size, rms_eps, dtype.

    layer_kinds  one of FULL / SLIDING a layer
    window       positions a SLIDING layer attends, else None
    counts       names of the int32 counts `layer_finish` /
                 `prefill_layer` return a layer (summed over layers
                 and handed back by the decode program), () for none
    supports     the server features the description's layer functions
                 implement, of: prefill_chunk, speculative, lora, int8,
                 prefix_cache, kv_tier
    """

    layer_kinds = ()
    window = None
    counts = ()
    supports = frozenset()

    def __init__(self, cfg):
        self.cfg = cfg

    @property
    def mixed(self):
        """True where the cache has to hold two kinds of layer."""
        return SLIDING in self.layer_kinds

    def layer_window(self, li):
        return self.window if self.layer_kinds[li] == SLIDING else None

    def require(self, feature, what):
        if feature not in self.supports:
            raise NotImplementedError(
                f"{what} is not implemented for {type(self).__name__} "
                f"(layer kinds {sorted(set(self.layer_kinds))}, counts "
                f"{list(self.counts)}): serve this net without it")

    # -- what a description implements ------------------------------------
    def params_tree(self, net):
        """{"embed", "norm", "head", "layers": [lp, ...]}."""
        raise NotImplementedError

    def embed(self, params, ids):
        raise NotImplementedError

    def prefill_layer(self, li, lp, x, positions, lengths, lora=None):
        """The whole layer on (B, T, D) -> (x, k, v, counts | None)."""
        raise NotImplementedError

    def layer_qkv(self, li, lp, x, positions, lora=None):
        """-> (q, k, v, carry): k, v as the cache stores them, `carry`
        whatever `layer_finish` needs beside the attention output."""
        raise NotImplementedError

    def layer_finish(self, li, lp, x, att, carry, lora=None,
                     valid=None):
        """-> (x, counts | None). `valid` (B, T) bool marks the rows
        that hold a token (idle slots and padding do not)."""
        raise NotImplementedError
