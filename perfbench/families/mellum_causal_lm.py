"""The system under test for pre-training one chip's share of a Mellum
decoder: ``mxnet_tpu``'s ``MellumForCausalLM`` + AdamW lowered by
``ParallelPlan.lower`` into one ``FusedTrainStep``, the loss by the
fused cross-entropy over the vocabulary slice, the sparse layers' counts
handed back beside it (they ride ``mx.train_step``).

The weights are planted from the benchmark's own seeded generator (the
reference is given the same values), and the shapes come from the
configuration and the job file. What the output check reads is
``bert_mlm_nsp.Trainer``'s, inherited.

**The load is held where the deployment's is.** On one chip's share
the absent experts add nothing and carry no gradient, so training
itself teaches the router to pick the held ones: a held expert's rows
double within 30 steps of lr 1e-4 and the step grows with them. No
deployment shows that (there the absent experts answer), so the job
file's ``restore_every_steps`` puts the seeded weights, zero moments
and step 0 back every so many steps, before the router has moved: the
steps' mathematics is untouched, the window is the same few steps from
the seed over and over, and the restore (a copy of 1.2 GB and a fill
of 4.8 GB inside the donated buffers, once in ``restore_every_steps``)
is paid inside the window like any other work.
"""
from mxnet_tpu.models.mellum import MellumConfig, MellumForCausalLM
from perfbench.families.bert_mlm_nsp import Trainer as _Trainer
from perfbench.reference import mellum_causal_lm as ref

# (the model is imported here and not in the Trainer: a program without
# it fails when the cell's family is looked up, before the reference
# has run)


def model_config(cfg, job):
    """The net's constructor arguments from the configuration file and
    the job's (rematerialisation is the job's: how the step is made to
    fit, not what the model is)."""
    full = cfg["rope_parameters"]["full_attention"]
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        layer_types=cfg["layer_types"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        num_experts=cfg["num_experts_published"],
        held_experts=(cfg["held_experts_lo"], cfg["num_experts"]),
        top_k=cfg["num_experts_per_tok"], window=cfg["sliding_window"],
        rope_base=float(full["rope_theta"]),
        yarn_factor=full["factor"],
        yarn_original=full["original_max_position_embeddings"],
        yarn_beta_fast=full["beta_fast"], yarn_beta_slow=full["beta_slow"],
        attention_factor=full["attention_factor"],
        rms_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
        dtype=cfg["torch_dtype"], remat=job["remat"])


class Trainer(_Trainer):
    """One compiled step with its state: what set-up builds, checks and
    hands to the window."""

    def __init__(self, cfg, job, seed, devices):
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.parallel.plan import ParallelPlan

        self.cfg, self.job = cfg, job
        plan = ParallelPlan(**job.get("plan", {}))
        self.chips = plan.total_devices
        vocab = cfg["vocab_size"]

        mx.random.seed(seed % (2 ** 31 - 1))
        net = MellumForCausalLM(MellumConfig(**model_config(cfg, job)))
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

        def loss_fn(logits, labels, mask):
            per = ce(logits.reshape(-1, vocab), labels.reshape(-1))
            m = mask.reshape(-1)
            return (per * m).sum() / m.sum()

        h = job["optimizer"]
        opt = mx.optimizer.AdamW(
            learning_rate=h["learning_rate"], wd=h["wd"],
            beta1=h["beta1"], beta2=h["beta2"], epsilon=h["epsilon"],
            multi_precision=True)
        self.step = plan.lower(net, loss_fn, opt, n_model_inputs=1,
                               counts=net.counts)
        self._beta1 = h["beta1"]
        self._jax = jax
        self._nd = mx.nd.array
        # the restore: a second copy of the seeded weights stays on the
        # device (the step donates its own); the first cycle begins
        # once the followed steps have been read
        self._every = job["restore_every_steps"]
        self._lead = job["check_steps"]
        self._calls = 0
        self._seeded = ref.make_weights(cfg, seed, devices[0])
        self._restore = None

    def __call__(self, batch):
        if self._calls >= self._lead and \
                (self._calls - self._lead) % self._every == 0:
            self.restore()
        self._calls += 1
        loss = super().__call__(batch)
        if self._restore is None:
            # compiled in set-up, against the state the first call made
            import jax.numpy as jnp
            tree = self._jax.tree_util.tree_map
            tr, states = self.step._tr, self.step._states
            self._restore = self._jax.jit(
                lambda tr, states, seeded: (
                    {n: jnp.copy(seeded[n]) for n in tr},
                    tree(jnp.zeros_like, states)),
                # (kept though unread: a donated argument that is
                # dropped gives its buffer to no output)
                donate_argnums=(0, 1), keep_unused=True,
                out_shardings=(tree(lambda a: a.sharding, tr),
                               tree(lambda a: a.sharding, states))
            ).lower(tr, states, self._seeded).compile()
        return loss

    def restore(self):
        """The step's state as the seed made it: weights, AdamW's
        moments (zero) and its step counter. Nothing is waited for."""
        step = self.step
        step._tr, step._states = self._restore(step._tr, step._states,
                                               self._seeded)
        step._step_count = 0


def build(cfg, job, seed, devices):
    return Trainer(cfg, job, seed, devices)


make_batches = ref.make_batches
batch_work = ref.batch_work
