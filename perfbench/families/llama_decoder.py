"""The system under test for decoder serving: the repo's one decoder
(``models/llama.py`` + ``llama_math.py`` + ``llama_infer.py``) at the
configuration's sizes, behind ``InferenceServer`` (paged KV cache,
continuous batching, persistent prefill/decode executables).

The weights are planted from the benchmark's seeded generator (the
reference makes the same values again). Server settings a deployment
fixes (slots, ``max_len``, pool size, KV dtype) come from the traffic
file's ``server`` object; everything a later optimisation may retune
(``prefill_chunk_tokens``, block size, pool gates) stays at the
program's defaults.
"""
import gc

from perfbench.reference import llama_decoder as ref


class Served:
    """One ``InferenceServer`` with the calls the load generators
    make. ``control=True`` switches on the program's own lower-precision
    path (int8 KV cache): the control of the output check."""

    def __init__(self, cfg, spec, seed, devices, control=False):
        import mxnet_tpu as mx
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.serving import InferenceServer

        self.cfg = cfg
        net = mx.models.get_model(
            "llama_3_8b", vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            max_seq_len=spec["max_len"], rope_base=cfg["rope_theta"],
            rms_eps=cfg["rms_norm_eps"], dtype=cfg["torch_dtype"])
        w = ref.make_weights(cfg, seed, devices[0])
        by_name = {"model.embed_tokens.weight": w["embed"],
                   "model.norm.gamma": w["norm"],
                   "lm_head.weight": w["head"]}
        roles = {"ln1": "input_layernorm.gamma",
                 "wq": "self_attn.q_proj.weight",
                 "wk": "self_attn.k_proj.weight",
                 "wv": "self_attn.v_proj.weight",
                 "wo": "self_attn.o_proj.weight",
                 "ln2": "post_attention_layernorm.gamma",
                 "gate": "mlp.gate_proj.weight",
                 "up": "mlp.up_proj.weight",
                 "down": "mlp.down_proj.weight"}
        for i, lp in enumerate(w["layers"]):
            for role, leaf in roles.items():
                by_name[f"model.layers.{i}.{leaf}"] = lp[role]
        del w
        ctx = mx.context.current_context()
        for name, p in net.collect_params().items():
            arr = by_name.pop(name)
            p.shape = arr.shape
            p.dtype = arr.dtype
            p._data = NDArray(arr, ctx=ctx)
            p._deferred = None
        if by_name:
            raise RuntimeError(f"unplanted weights: {sorted(by_name)}")
        kw = {k: spec[k] for k in ("batch_slots", "max_len",
                                   "max_prompt_len", "num_blocks")
              if spec.get(k) is not None}
        self.server = InferenceServer(
            net, kv_cache_dtype="int8" if control
            else spec["kv_cache_dtype"], **kw)
        self.slots = self.server.batch_slots

    # -- what the load generators call --------------------------------------

    def submit(self, prompt_ids, new_tokens, sampling=None, seed=0):
        """Enqueue one request: greedy, or sampled with the traffic
        file's ``sampling`` settings."""
        s = sampling or {}
        return self.server.submit(
            prompt_ids, new_tokens, temperature=s.get("temperature", 0.0),
            top_k=s.get("top_k", 0), top_p=s.get("top_p", 0.0),
            seed=seed % (2 ** 31 - 1))

    def step(self):
        return self.server.step()

    def busy(self):
        s = self.server
        return bool(s.queue) or bool(s._active.any()) \
            or bool(s._prefilling.any())

    @staticmethod
    def emitted(req):
        return len(req.output_tokens)

    @staticmethod
    def done(req):
        return req.status is not None

    @staticmethod
    def ok(req):
        return req.status == "ok" \
            and len(req.output_tokens) == req.max_new_tokens

    @staticmethod
    def queue_wait_s(req):
        """The program's own span: submit to admission."""
        if req.t_admit is None:
            return None
        return req.t_admit - req.t_submit

    @staticmethod
    def tokens(req):
        return list(req.prompt), list(req.output_tokens)

    def kv_blocks_used(self):
        return self.server.cache.num_used_blocks

    def counters(self):
        """The program's counters the per-layer metrics read."""
        s = self.server
        cs = s.compile_stats()
        return {"kv_blocks_capacity": s.cache.num_blocks - 1,
                "preemptions": s.preemptions,
                "prefill_calls": cs["prefill_calls"],
                "decode_calls": cs["decode_calls"],
                "prefill_compiles": cs["prefill_compiles"],
                "decode_compiles": cs["decode_compiles"],
                "kernel_paged": bool(s._kernel_paged)}

    def kernel_fallbacks(self):
        from mxnet_tpu.kernels import dispatch
        return sum(dispatch.fallback_counts().values())

    def free(self):
        """Drop every device buffer of the program's (weights, page
        pools, logits rows) so the reference has the chip."""
        for p in self.server.net.collect_params().values():
            p._data = None
        self.server.cache.pages = None
        self.server._params = None
        self.server._last_logits = None
        self.server = None
        gc.collect()


def build(cfg, spec, seed, devices, control=False):
    return Served(cfg, spec, seed, devices, control)
