"""The system under test for latent-attention serving: the repo's
Sarvam decoder (``models/sarvam.py`` + ``mla_math.py``, the latent
sweep of ``kernels/flash_decode.py``, the expert layer of
``parallel/moe.py``) at the configuration's sizes, behind
``InferenceServer``: one chip's share of the stated expert-parallel
deployment. The net is told the published expert count (the router's
width) and the range of experts it holds separately; the cache holds
one row of latents a position a layer.

The weights are planted from the benchmark's seeded generator (the
reference makes the same values again). Server settings a deployment
fixes (slots, ``max_len``, the pool's size) come from the traffic
file's ``server`` object; everything a later optimisation may retune
stays at the program's defaults.
"""
from perfbench.families import llama_decoder
from perfbench.reference import mla_moe_decoder as ref


class Served(llama_decoder.Served):
    """One ``InferenceServer`` over the Sarvam net, with the calls the
    load generators make (those that name no model are inherited)."""

    def __init__(self, cfg, spec, seed, devices, control=False):
        import mxnet_tpu as mx
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.serving import InferenceServer

        if control:
            raise NotImplementedError(
                "the latent-attention server has no lower-precision "
                "path of its own; its controls alter the reference "
                "(perfbench/reference/mla_moe_decoder.py::CONTROLS)")
        self.cfg = cfg
        rs = cfg["rope_scaling"]
        if rs["type"] != "deepseek_yarn":
            raise NotImplementedError(f"rope_scaling {rs['type']!r}")
        net = mx.models.get_model(
            "sarvam_mla", vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_dense_layers=cfg["first_k_dense_replace"],
            num_heads=cfg["num_attention_heads"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            num_experts=cfg["num_experts_published"],
            held_experts=(cfg["held_experts_lo"], cfg["num_experts"]),
            top_k=cfg["num_experts_per_tok"],
            route_scale=cfg["routed_scaling_factor"],
            rope_base=cfg["rope_theta"], rope_factor=rs["factor"],
            rope_original=rs["original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"],
            rms_eps=cfg["rms_norm_eps"], max_seq_len=spec["max_len"],
            dtype=cfg["torch_dtype"])
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
                                   "max_prompt_len", "num_blocks")
              if spec.get(k) is not None}
        self.server = InferenceServer(
            net, kv_cache_dtype=spec["kv_cache_dtype"], **kw)
        self.slots = self.server.batch_slots
        self.moe_layers = cfg["num_hidden_layers"] \
            - cfg["first_k_dense_replace"]

    def counters(self):
        from perfbench import harness

        out = super().counters()
        stats = self.server.stats()
        # the expert layers' load since the server started (the traced
        # run's per-layer metrics read the window's share from spans)
        harness.say("experts", decode_calls=out["decode_calls"],
                    **self.server.decoder_counts)
        out.update(
            moe_layers=self.moe_layers,
            held_experts=self.cfg["num_experts"],
            latent_pool_bytes=stats["latent_pool_bytes"],
            latent_pool_tokens=stats["latent_pool_tokens"])
        return out


def build(cfg, spec, seed, devices, control=False):
    return Served(cfg, spec, seed, devices, control)
