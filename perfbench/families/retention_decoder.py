"""The system under test for power-retention serving: the repo's Brumby
decoder (``models/brumby.py`` + ``retention_math.py``, the kernels of
``kernels/power_retention.py``) at the configuration's sizes, behind
``InferenceServer``: a pipeline stage's layers with the embedding and
the head whole on one chip, every layer's matrix-valued state in the
state pool and no block pool at all.

The weights are planted from the benchmark's seeded generator (the
reference makes the same values again). Server settings a deployment
fixes (slots, ``max_len``) come from the traffic file's ``server``
object; everything a later optimisation may retune (kernel constants)
stays at the program's defaults.
"""
from perfbench.families import llama_decoder
from perfbench.reference import retention_decoder as ref


class Served(llama_decoder.Served):
    """One ``InferenceServer`` over the Brumby net, with the calls the
    load generators make (those that name no model are inherited)."""

    def __init__(self, cfg, spec, seed, devices, control=False):
        import mxnet_tpu as mx
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.serving import InferenceServer

        if control:
            raise NotImplementedError(
                "the Brumby server has no lower-precision path of its "
                "own; its controls alter the reference "
                "(perfbench/reference/retention_decoder.py::CONTROLS)")
        self.cfg = cfg
        net = mx.models.get_model(
            "brumby", vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], rope_base=cfg["rope_theta"],
            rms_eps=cfg["rms_norm_eps"], max_seq_len=spec["max_len"],
            dtype=cfg["torch_dtype"],
            retention_degree=cfg["retention_degree"],
            retention_eps=cfg["retention_eps"])
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
                                   "max_prompt_len")
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
