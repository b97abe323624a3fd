"""The system under test for Jamba serving: the repo's Jamba decoder
(``models/jamba.py`` + ``jamba_math.py``, the state-space kernels of
``kernels/selective_scan.py``) at the configuration's sizes, behind
``InferenceServer``: the whole model on one chip, its recurrent layers'
state in a pool beside the paged KV cache of its attention layers.

The weights are planted from the benchmark's seeded generator (the
reference makes the same values again); the head is the embedding.
Server settings a deployment fixes (slots, ``max_len``, the pool's
size) come from the traffic file's ``server`` object; everything a
later optimisation may retune (block size, kernel constants) stays at
the program's defaults.
"""
from perfbench.families import llama_decoder
from perfbench.reference import jamba_decoder as ref


class Served(llama_decoder.Served):
    """One ``InferenceServer`` over the Jamba net, with the calls the
    load generators make (those that name no model are inherited)."""

    def __init__(self, cfg, spec, seed, devices, control=False):
        import mxnet_tpu as mx
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.serving import InferenceServer

        if control:
            raise NotImplementedError(
                "the Jamba server has no lower-precision path of its "
                "own; its controls alter the reference "
                "(perfbench/reference/jamba_decoder.py::CONTROLS)")
        self.cfg = cfg
        net = mx.models.get_model(
            "jamba", vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            attn_layer_period=cfg["attn_layer_period"],
            attn_layer_offset=cfg["attn_layer_offset"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], d_state=cfg["mamba_d_state"],
            d_conv=cfg["mamba_d_conv"], expand=cfg["mamba_expand"],
            dt_rank=cfg["mamba_dt_rank"], rms_eps=cfg["rms_norm_eps"],
            max_seq_len=spec["max_len"], dtype=cfg["torch_dtype"])
        w = ref.make_weights(cfg, seed, devices[0])
        by_name = {"model.embed_tokens.weight": w["embed"],
                   "model.norm.gamma": w["norm"]}
        for i, lp in enumerate(w["layers"]):
            for role, arr in lp.items():
                by_name[f"model.layers.{i}.{role}"] = arr
        del w
        ctx = mx.context.current_context()
        for name, p in net.collect_params().items():
            arr = by_name.pop(name)
            if tuple(p.shape) != arr.shape:
                raise RuntimeError(f"{name}: the net wants {p.shape}, "
                                   f"the seeded weight is {arr.shape}")
            p.dtype = arr.dtype
            p._data = NDArray(arr, ctx=ctx)
            p._deferred = None
        if by_name:
            raise RuntimeError(f"unplanted weights: {sorted(by_name)}")
        kw = {k: spec[k] for k in ("batch_slots", "max_len",
                                   "max_prompt_len", "num_blocks")
              if spec.get(k) is not None}
        self.server = InferenceServer(
            net, kv_cache_dtype=spec["kv_cache_dtype"], **kw)
        self.slots = self.server.batch_slots

    def counters(self):
        out = super().counters()
        out["state_pool_bytes"] = self.server.cache.state_pool_bytes
        return out


def build(cfg, spec, seed, devices, control=False):
    return Served(cfg, spec, seed, devices, control)
