"""The system under test for afmoe serving: the repo's afmoe decoder
(``models/afmoe.py`` + ``afmoe_math.py``, the expert layer of
``parallel/moe.py``) at the configuration's sizes, behind
``InferenceServer``: one chip's share of the stated expert-parallel
deployment. The net is told the published expert count (the router's
width) and the range of experts it holds separately; the cache holds
the sliding-window layers in a pool of their own.

The weights are planted from the benchmark's seeded generator (the
reference makes the same values again). Server settings a deployment
fixes (slots, ``max_len``, the two pools' sizes) come from the traffic
file's ``server`` object; everything a later optimisation may retune
stays at the program's defaults.
"""
from perfbench.families import llama_decoder
from perfbench.reference import afmoe_decoder as ref


class Served(llama_decoder.Served):
    """One ``InferenceServer`` over the afmoe net, with the calls the
    load generators make (those that name no model are inherited)."""

    def __init__(self, cfg, spec, seed, devices, control=False):
        import mxnet_tpu as mx
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.serving import InferenceServer

        if control:
            raise NotImplementedError(
                "the afmoe server has no lower-precision path of its "
                "own; its controls alter the reference "
                "(perfbench/reference/afmoe_decoder.py::CONTROLS)")
        self.cfg = cfg
        net = mx.models.get_model(
            "afmoe", vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_dense_layers=cfg["num_dense_layers"],
            layer_types=cfg["layer_types"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            num_experts=cfg["num_experts_published"],
            held_experts=(cfg["held_experts_lo"], cfg["num_experts"]),
            top_k=cfg["num_experts_per_tok"],
            route_scale=cfg["route_scale"],
            window=cfg["sliding_window"], rope_base=cfg["rope_theta"],
            rms_eps=cfg["rms_norm_eps"], mup_enabled=cfg["mup_enabled"],
            max_seq_len=spec["max_len"], dtype=cfg["torch_dtype"])
        w = ref.make_weights(cfg, seed, devices[0])
        by_name = {"model.embed_tokens.weight": w["embed"],
                   "model.norm.gamma": w["norm"],
                   "lm_head.weight": w["head"]}
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
                                   "max_prompt_len", "num_blocks",
                                   "window_num_blocks")
              if spec.get(k) is not None}
        self.server = InferenceServer(
            net, kv_cache_dtype=spec["kv_cache_dtype"], **kw)
        self.slots = self.server.batch_slots
        self.moe_layers = cfg["num_hidden_layers"] \
            - cfg["num_dense_layers"]
        # blocks in use, by kind, summed over the ticks the driver
        # counts (it samples the cache once a counted tick)
        self.window_used_sum = self.global_used_sum = 0

    def kv_blocks_used(self):
        """Both kinds summed; the sample also feeds each kind's sum."""
        kv = self.server.cache
        self.window_used_sum += kv.window_blocks_used
        self.global_used_sum += kv.global_blocks_used
        return kv.window_blocks_used + kv.global_blocks_used

    def counters(self):
        from perfbench import harness

        kv = self.server.cache
        out = super().counters()
        # the expert layers' load since the server started (the traced
        # run's per-layer metrics read the window's share from spans)
        harness.say("experts", decode_calls=out["decode_calls"],
                    **self.server.decoder_counts)
        out.update(
            kv_blocks_capacity=kv.window_blocks_capacity
            + kv.global_blocks_capacity,
            kv_window_blocks_capacity=kv.window_blocks_capacity,
            kv_global_blocks_capacity=kv.global_blocks_capacity,
            kv_window_blocks_used_sum=self.window_used_sum,
            kv_global_blocks_used_sum=self.global_used_sum,
            moe_layers=self.moe_layers,
            held_experts=self.cfg["num_experts"])
        return out

def build(cfg, spec, seed, devices, control=False):
    return Served(cfg, spec, seed, devices, control)
