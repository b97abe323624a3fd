"""The system under test for BERT pre-training: ``mxnet_tpu``'s
``BERTForPretraining`` + AMP bf16 + AdamW lowered by
``ParallelPlan.lower`` into one ``FusedTrainStep``.

The build/loss recipe (model build, ``amp.convert_block``, MLM + NSP
loss, AdamW, fused step) was copied from the repo's first benchmark
script, which PR 29 deleted; this is its one copy. The weights are
planted from the benchmark's own seeded generator (the reference is
given the same values), and the shapes come from the configuration and
the job file.
"""
from perfbench.reference import bert_mlm_nsp as ref


class Trainer:
    """One compiled step with its state: what set-up builds, checks and
    hands to the window."""

    def __init__(self, cfg, job, seed, devices):
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import amp, gluon
        from mxnet_tpu.models.bert import BERTForPretraining
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.parallel.plan import ParallelPlan

        self.cfg, self.job = cfg, job
        plan = ParallelPlan(**job.get("plan", {}))
        self.chips = plan.total_devices
        self.batch = job["per_chip_batch"] * plan.dp
        vocab = cfg["vocab_size"]

        mx.random.seed(seed % (2 ** 31 - 1))
        net = BERTForPretraining(
            vocab_size=vocab, units=cfg["hidden_size"],
            hidden_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            max_length=cfg["max_position_embeddings"],
            token_types=cfg["type_vocab_size"],
            dropout=cfg["hidden_dropout_prob"])
        amp.init(cfg["compute_dtype"])
        # plant the benchmark's weights: every parameter made on the
        # device in one jitted call, in the type it is trained in (what
        # amp.convert_block would cast to), no host initializer and no
        # eager materializing forward
        weights = ref.make_weights(cfg, seed, devices[0])
        ctx = mx.context.current_context()
        for name, p in net.collect_params().items():
            arr = weights.pop(name)
            p.shape = arr.shape
            p.dtype = arr.dtype
            p._data = NDArray(arr, ctx=ctx)
            p._deferred = None
        if weights:
            raise RuntimeError(f"unplanted weights: {sorted(weights)}")
        self.net = net

        ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def loss_fn(mlm, nsp, labels, mask, nsp_labels):
            per = ce(mlm.reshape(-1, vocab), labels.reshape(-1))
            m = mask.reshape(-1).astype("float32")
            l1 = (per * m).sum() / mx.nd.maximum(m.sum(),
                                                 mx.nd.array([1.0]))
            return l1 + ce(nsp, nsp_labels).mean()

        h = job["optimizer"]
        opt = mx.optimizer.AdamW(
            learning_rate=h["learning_rate"], wd=h["wd"],
            beta1=h["beta1"], beta2=h["beta2"], epsilon=h["epsilon"],
            multi_precision=True)
        self.step = plan.lower(net, loss_fn, opt, n_model_inputs=3)
        self._beta1 = h["beta1"]
        self._jax = jax
        self._nd = mx.nd.array

    # -- the window's own call and feed -------------------------------------

    def __call__(self, batch):
        """One fused step on a host batch; returns the loss as a device
        scalar (nothing is fetched). The host arrays go in as NDArrays,
        the user's normal feed: the transfer to the device is part of
        every step. (A bare numpy model input would be baked into the
        trace as a constant — ``HybridBlock.trace_entry`` traces only
        NDArray arguments.)"""
        return self.step(*[self._nd(a, dtype=str(a.dtype))
                           for a in batch])._data

    # -- what the output check reads ----------------------------------------

    def _leaf_norms(self, tree):
        import jax.numpy as jnp
        fn = getattr(self, "_norm_fn", None)
        if fn is None:
            fn = self._norm_fn = self._jax.jit(lambda t: {
                n: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                for n, a in t.items()})
        return {n: float(v) for n, v in fn(tree).items()}

    def first_grad_norms(self):
        """Per-leaf norm of the first gradient as the optimizer got it,
        worked out from AdamW's first moment after ONE step:
        m1 = (1 - beta1) * g."""
        m = {n: st[0] for n, st in self.step._states.items()}
        return {n: v / (1.0 - self._beta1)
                for n, v in self._leaf_norms(m).items()}

    def weights(self):
        """{name: array} of the weights the step holds now."""
        return dict(self.step._tr)

    def change_norms(self, before):
        """Per-leaf norm of (weights now - ``before``)."""
        import jax.numpy as jnp
        fn = getattr(self, "_diff_fn", None)
        if fn is None:
            fn = self._diff_fn = self._jax.jit(lambda a, b: {
                n: jnp.sqrt(jnp.sum(jnp.square(
                    a[n].astype(jnp.float32) - b[n].astype(jnp.float32))))
                for n in a})
        now = self.weights()
        before = {n: self._jax.device_put(before[n], now[n].sharding)
                  for n in now}
        return {n: float(v) for n, v in fn(now, before).items()}

    def kernel_fallbacks(self):
        from mxnet_tpu.kernels import dispatch
        return dict(dispatch.fallback_counts())


def build(cfg, job, seed, devices):
    return Trainer(cfg, job, seed, devices)


make_batches = ref.make_batches
batch_work = ref.batch_work

