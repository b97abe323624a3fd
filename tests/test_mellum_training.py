"""Mellum2 (sliding + full attention with a rotation each, a dropless
sparse expert layer in every layer) TRAINED through the normal path and
held to the plain reference at the tiny preset on the CPU: forward,
loss and every leaf's gradient; three fused steps against the
reference's `follow`; the six controls; the four shares of a deployment
adding up to the uncut layer, forward and gradient.
"""
import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.parallel import moe  # noqa: E402
from perfbench import harness, rehearse  # noqa: E402
from perfbench.families import mellum_causal_lm as family  # noqa: E402
from perfbench.generators import train_job  # noqa: E402
from perfbench.reference import mellum_causal_lm as ref  # noqa: E402

CELL = "mellum2_12b.pretrain8k"
SEED = 2 ** 31 + 77


@pytest.fixture
def interpret(monkeypatch):
    """The real Pallas kernels under the interpreter."""
    for fam in ("FLASH", "NORM", "CE", "MOE"):
        monkeypatch.setenv(f"MXNET_TPU_{fam}_INTERPRET", "1")


def tiny():
    cell = rehearse.tiny_cell(CELL)
    return cell, cell.config, cell.traffic


def test_the_tiny_preset_is_the_block():
    """4 layers sliding x3 + full, a window shorter than the sequence,
    8 published experts with 4 held at lo != 0, top-2."""
    _, cfg, job = tiny()
    assert cfg["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert cfg["sliding_window"] < job["seq_len"]
    assert (cfg["num_experts_published"], cfg["num_experts"],
            cfg["held_experts_lo"], cfg["num_experts_per_tok"]) \
        == (8, 4, 2, 2)
    assert "mellum" in mx.models.list_models()


def _net_function(cfg, job, w):
    """The Gluon net as `FusedTrainStep` sees it: (loss, counts) of the
    trainable leaves and one batch."""
    from mxnet_tpu.models.mellum import MellumConfig, MellumForCausalLM
    from mxnet_tpu.ndarray import NDArray

    net = MellumForCausalLM(MellumConfig(**family.model_config(cfg, job)))
    for name, p in net.collect_params().items():
        p.shape, p.dtype = w[name].shape, w[name].dtype
        p._data, p._deferred = NDArray(w[name]), None
    ids0 = mx.nd.array(np.zeros((job["per_chip_batch"], job["seq_len"]),
                                np.int32), dtype="int32")
    entry = net.trace_entry([ids0], training=True)
    assert sorted(entry.tr_names) == sorted(w)

    def loss(tr, ids, labels, mask):
        (logits, counts), _ = entry.raw_fn(tr, {}, jax.random.PRNGKey(0),
                                           ids)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        pick = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.sum((lse - pick) * mask) / jnp.sum(mask), counts

    return loss


def _reference_counts(cfg, w, ids):
    """moe_pairs, moe_touched, moe_pairs_max as the reference's own
    router gives them, summed over the layers, all sequences routed
    together as the program routes them."""
    pairs = touched = fullest = 0
    qq = lambda a: a  # noqa: E731
    xs = [w["model.embed_tokens.weight"][r] for r in ids]
    lo, n = cfg["held_experts_lo"], cfg["num_experts"]

    @functools.partial(jax.jit, static_argnums=2)
    def walk(lp, x, kind):
        # the layer's router sees RMS(x + attention branch): take
        # the layer apart as the reference defines it
        T = x.shape[0]
        u = ref._rms(x, lp["ln_in"], cfg["rms_norm_eps"])
        H, K, d = (cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
        q = ref._rms((u @ lp["wq"].T).reshape(T, H, d), lp["q_norm"],
                     cfg["rms_norm_eps"])
        k = ref._rms((u @ lp["wk"].T).reshape(T, K, d), lp["k_norm"],
                     cfg["rms_norm_eps"])
        v = (u @ lp["wv"].T).reshape(T, K, d)
        cos, sin = ref.rotation(cfg, kind, T)
        att = ref._attention(
            ref._rotate(q, cos, sin), ref._rotate(k, cos, sin), v,
            cfg["sliding_window"] if kind == ref.SLIDING else None,
            64, qq)
        x1 = x + att @ lp["wo"].T
        m = ref._rms(x1, lp["ln_mlp"], cfg["rms_norm_eps"])
        sel, _ = ref.route(cfg, lp, m)
        return sel, x1 + ref.experts(cfg, lp, m, qq)

    for l, kind in enumerate(cfg["layer_types"]):
        lp = ref.layer_params(w, l)
        hits = np.zeros(n, int)
        for i, x in enumerate(xs):
            sel, xs[i] = walk(lp, x, kind)
            for e in range(n):
                hits[e] += int((np.asarray(sel) == lo + e).sum())
        pairs, touched, fullest = (pairs + hits.sum(),
                                   touched + (hits > 0).sum(),
                                   fullest + hits.max())
    return int(pairs), int(touched), int(fullest)


@pytest.mark.parametrize("kernels", ["jnp", "interpreted"])
def test_net_equals_reference_forward_loss_and_every_gradient(
        kernels, monkeypatch):
    if kernels == "interpreted":
        for fam in ("FLASH", "NORM", "CE", "MOE"):
            monkeypatch.setenv(f"MXNET_TPU_{fam}_INTERPRET", "1")
    _, cfg, job = tiny()
    w = ref.make_weights(cfg, SEED, jax.devices()[0])
    ids, labels, mask = ref.make_batches(cfg, job, SEED, 1,
                                         job["per_chip_batch"])[0]
    (got, counts), g_got = jax.jit(jax.value_and_grad(
        _net_function(cfg, job, w), has_aux=True))(w, ids, labels, mask)

    def ref_loss(p):
        return sum(ref.sequence_loss_sum(p, cfg, ids[r], labels[r],
                                         mask[r], block=64)
                   for r in range(ids.shape[0])) / mask.sum()

    with jax.default_matmul_precision("highest"):
        want, g_want = jax.jit(jax.value_and_grad(ref_loss))(w)
        if kernels == "jnp":    # once: the counts are integers' work
            assert tuple(int(c) for c in counts) == _reference_counts(
                cfg, w, ids)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert sorted(g_got) == sorted(g_want)
    for name in sorted(g_want):
        scale = float(jnp.abs(g_want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(
            g_got[name], g_want[name], atol=2e-4 * scale, rtol=2e-4,
            err_msg=name)


def test_three_fused_steps_follow_the_reference(interpret):
    """`ParallelPlan.lower` -> one `FusedTrainStep`, fused CE, the
    counts riding out: the first steps' losses, the first gradient and
    the parameters' change against `follow`, at the rehearsal's limits;
    the step counts what the router did."""
    cell, cfg, job = tiny()
    devices = jax.devices()[:1]
    ring = family.make_batches(cfg, job, SEED, job["check_steps"],
                               job["per_chip_batch"])
    w0 = ref.make_weights(cfg, SEED, devices[0])
    want = ref.follow(cfg, job, w0, ring, job["optimizer"], 1)
    trainer = family.build(cfg, job, SEED, devices)
    got = train_job.follow_program(trainer, cfg, SEED, ring, devices)
    checks = harness.Checks()
    train_job.compare_with_reference(checks, job["limits"], got, want)
    checks.print()
    assert checks.ok
    assert want["losses"][2] < want["losses"][0]        # it learns
    # the steps are done (their losses were read): what the spans of
    # the later calls did not take is ready now
    jax.block_until_ready(trainer.weights())
    counts = trainer.step._ready_counts()
    steps = counts["counted_steps"]
    assert 1 <= steps <= 3 and not trainer.step._counts_pending
    pairs_all = steps * ring[0][0].size * len(cfg["layer_types"]) \
        * cfg["num_experts_per_tok"]
    # 4 of the 8 experts are held: about half the pairs, never all
    assert 0.3 * pairs_all < counts["moe_pairs"] < 0.7 * pairs_all
    assert counts["moe_pairs_max"] * cfg["num_experts"] \
        >= counts["moe_pairs"]


def test_the_restore_puts_the_seeded_state_back():
    """Every `restore_every_steps` calls past the followed ones the
    family's trainer steps from the seed again: the same batch gives
    the same losses cycle after cycle, bit for bit, and the weights
    the restore reads are not the ones the step donates."""
    _, cfg, job = tiny()
    assert (job["check_steps"], job["restore_every_steps"]) == (3, 2)
    devices = jax.devices()[:1]
    batch = family.make_batches(cfg, job, SEED, 1,
                                job["per_chip_batch"])[0]
    trainer = family.build(cfg, job, SEED, devices)
    losses = [float(trainer(batch)) for _ in range(9)]
    # calls 1-3 are followed from the seed; then cycles of two
    assert losses[3:5] == losses[:2] == losses[5:7] == losses[7:9]
    assert losses[2] < losses[1] < losses[0]
    assert trainer.step._step_count == 2
    held = list(trainer.weights().values()) + [
        m for st in trainer.step._states.values() for m in st]
    trainer.restore()
    # written into the buffers the step held: no second state beside it
    assert all(a.is_deleted() for a in held)
    w0 = ref.make_weights(cfg, SEED, devices[0])
    assert set(trainer.change_norms(w0).values()) == {0.0}
    assert not any(np.asarray(m).any() for st in
                   trainer.step._states.values() for m in st)


@pytest.mark.parametrize("how", ["grad_accum", "run_steps"])
def test_counts_ride_only_the_plain_fused_step(how):
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep
    net = mx.models.get_model("mellum_tiny")
    if how == "grad_accum":
        with pytest.raises(ValueError, match="plain fused step"):
            FusedTrainStep(net, lambda *a: a[0], mx.optimizer.AdamW(),
                           counts=net.counts, grad_accum=2)
        return
    step = FusedTrainStep(net, lambda *a: a[0], mx.optimizer.AdamW(),
                          counts=net.counts)
    with pytest.raises(ValueError, match="single dispatches"):
        step.run_steps([(mx.nd.zeros((1, 8), dtype="int32"),)] * 2)


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_each_control_changes_the_answer(control):
    _, cfg, job = tiny()
    w = ref.make_weights(cfg, SEED, jax.devices()[0])
    ids, labels, mask = ref.make_batches(cfg, job, SEED, 1, 1)[0]
    with jax.default_matmul_precision("highest"):
        sound, other = (float(jax.jit(
            lambda w, c=c: ref.sequence_loss_sum(
                w, cfg, ids[0], labels[0], mask[0], c, 64))(w))
            for c in (None, control))
    assert abs(other - sound) > 1e-4 * abs(sound), (sound, other)


def test_a_control_fails_the_cells_comparison():
    """`perfbench/control_check_train.py`'s reading at the tiny preset:
    a control followed in the program's place fails at least one of
    the rehearsal's limits (two of the six here, for the seconds; all
    six were read on the chip at the cell's size: PERF.md, section 2)."""
    from perfbench import control_check_train
    cell, _, _ = tiny()
    out = control_check_train.run(cell, SEED, jax.devices()[0],
                                  ["fp8", "no_window"])
    assert set(out) == {"fp8", "no_window"}
    assert not any(r["correct"] for r in out.values()), out


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each rank of a 4-way expert-parallel deployment holds 2 of the 8
    published experts; the routed sums the program computes for the
    four `held_experts=(2r, 2)` ranks add up to the reference's whole
    layer (every expert held), forward and in the gradient with
    respect to the layer's input (and the router's, which every rank
    holds whole: its shares add up too)."""
    _, cfg, _ = tiny()
    uncut = dict(cfg, num_experts=cfg["num_experts_published"],
                 held_experts_lo=0)
    lp = ref.layer_params(ref.make_weights(uncut, SEED), 1)
    m = jax.random.normal(jax.random.PRNGKey(4), (37, cfg["hidden_size"]))
    ct = jax.random.normal(jax.random.PRNGKey(5), m.shape)
    k = cfg["num_experts_per_tok"]

    def whole(m, router):
        return jnp.sum(ref.experts(uncut, dict(lp, router=router), m,
                                   lambda a: a) * ct)

    def share(lo):
        def f(m, router):
            y, *_ = moe.held_expert_ffn(
                m, router, None, *(lp[r][lo:lo + 2] for r in (
                    "ex_gate", "ex_up", "ex_down")), lo=lo, top_k=k,
                route=moe.route_softmax_top_k)
            return jnp.sum(y * ct)
        return f

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(whole, argnums=(0, 1)))(
            m, lp["router"])
        parts = [jax.jit(jax.value_and_grad(share(2 * r), argnums=(0, 1)))(
            m, lp["router"]) for r in range(4)]
    np.testing.assert_allclose(sum(p[0] for p in parts), want[0],
                               rtol=1e-5)
    for i, name in enumerate(("input", "router")):
        total = sum(p[1][i] for p in parts)
        np.testing.assert_allclose(total, want[1][i], atol=2e-5,
                                   err_msg=name)
        assert float(jnp.abs(want[1][i]).max()) > 1e-2
    # no rank alone is the layer
    assert abs(float(parts[0][0]) - float(want[0])) > 1e-3


def test_yarn_tables_agree():
    """The program's frequencies (mla_math.yarn_inv_freq) against the
    reference's own table, at the published numbers."""
    from mxnet_tpu.models.mla_math import yarn_inv_freq
    rope = harness.load_json(harness.HERE, "configs", "mellum2_12b.json")[
        "rope_parameters"]["full_attention"]
    got = yarn_inv_freq(128, rope["rope_theta"], rope["factor"],
                        rope["original_max_position_embeddings"],
                        rope["beta_fast"], rope["beta_slow"])
    want = ref.yarn_frequencies(rope, 128)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 500000.0 ** (-np.arange(64) / 64)
    assert np.allclose(want[:8], plain[:8])             # fast dims keep
    assert np.allclose(want[-8:], plain[-8:] / 16)      # slow dims stretch
