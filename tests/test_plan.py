"""ParallelPlan — one composable pp × tp × dp(+ZeRO) × MoE declaration.

The fuzz grid trains every valid {pp, tp, zero, virtual, compression}
cell through plan.lower() and checks parity against the plain fused
step on the same 8 virtual devices: SGD cells are bit-exact at tp=1
(atol 1e-6 like the existing pipeline parity tests), tp=2 cells allow
the split-matmul reduction-order drift, compressed cells allow the int8
wire quantization. Rejection tests pin the compatibility matrix: every
violation in ONE PlanError, no warn-and-degrade."""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt_mod
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.block import HybridSequential
from mxnet_tpu.gluon.loss import L2Loss
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel.mesh import hybrid_mesh, local_mesh
from mxnet_tpu.parallel.plan import ParallelPlan, PlanError
from mxnet_tpu.parallel.data_parallel import FusedTrainStep


# -- harness ----------------------------------------------------------------

def _dense_chain(n_blocks=8, d=8, seed=0):
    net = HybridSequential()
    for _ in range(n_blocks):
        net.add(nn.Dense(d, activation="tanh", in_units=d, flatten=False))
    mx.random.seed(seed)
    net.initialize()
    return net


def _tp_chain(n_blocks=8, seed=0):
    from mxnet_tpu.parallel.tensor_parallel import TPMLP
    net = HybridSequential()
    for _ in range(n_blocks):
        net.add(TPMLP(8, 16))
    mx.random.seed(seed)
    net.initialize()
    return net


def _train(target, net_fn, steps=3, opt_name="sgd", opt_kw=None,
           shape=(32, 8), **lower_kw):
    """3 fixed steps through a plan (lowered) or a mesh (plain fused
    step); returns (losses, weights, step)."""
    net = net_fn()
    opt = opt_mod.create(opt_name, **(opt_kw or {"learning_rate": 0.1,
                                                 "momentum": 0.9}))
    if isinstance(target, ParallelPlan):
        step = target.lower(net, L2Loss(), opt, **lower_kw)
    else:
        step = FusedTrainStep(net, L2Loss(), opt, mesh=target)
    rs = np.random.RandomState(42)
    losses = []
    for _ in range(steps):
        x = NDArray(jnp.asarray(rs.rand(*shape), jnp.float32))
        y = NDArray(jnp.asarray(rs.rand(*shape), jnp.float32))
        losses.append(float(step(x, y)))
    step.sync_to_params()
    weights = {k: np.asarray(p.data()._data)
               for k, p in net.collect_params().items()}
    return losses, weights, step


_REFS = {}


def _reference(kind, opt_name="sgd"):
    """Plain fused-step reference, cached across grid cells."""
    key = (kind, opt_name)
    if key not in _REFS:
        if kind == "dense":
            kw = ({"learning_rate": 0.01} if opt_name == "adam"
                  else None)
            _REFS[key] = _train(local_mesh(8), _dense_chain,
                                opt_name=opt_name, opt_kw=kw)[:2]
        else:  # tp nets need the tp axis in the reference mesh
            _REFS[key] = _train(hybrid_mesh(dp=4, tp=2), _tp_chain,
                                shape=(32, 4, 8))[:2]
    return _REFS[key]


# -- compatibility matrix: every violation, one loud error -------------------

def test_plan_error_collects_every_violation():
    with pytest.raises(PlanError) as ei:
        ParallelPlan(dp=2, tp=2, pp=2, ep=2, zero=1, virtual=2)
    v = ei.value.violations
    assert len(v) >= 4
    joined = "\n".join(v)
    assert "microbatches" in joined
    assert "tp x zero" in joined
    assert "tp x ep" in joined
    assert "ep x pp" in joined
    # the exception text itself lists them all
    assert all(m in str(ei.value) for m in v)


@pytest.mark.parametrize("kw,frag", [
    (dict(dp=1, zero=1), "dp >= 2"),
    (dict(pp=2), "microbatches"),
    (dict(dp=2, microbatches=4), "pipeline knob"),
    (dict(dp=2, virtual=2), "needs pp > 1"),
    (dict(pp=2, microbatches=7, virtual=2), "% pp == 0"),
    (dict(dp=2, tp=2, zero=1), "tp x zero"),
    (dict(tp=2, ep=2, dp=2), "tp x ep"),
    (dict(ep=2, dp=2, pp=2, microbatches=4), "ep x pp"),
    (dict(ep=2, dp=4), "ep == dp"),
    (dict(ep=2, dp=2, zero=2), "ep x zero"),
    (dict(dp=2, tp=2, compression={"grads": "int8"}), "compression x tp"),
    (dict(dp=2, pp=2, microbatches=4,
          compression={"grads": "int8"}), "compression x pp"),
    (dict(dp=2, ep=2, compression={"grads": "int8"}), "compression x ep"),
    (dict(dp=2, compression={"activations": "int8"}), "needs pp > 1"),
    (dict(dp=2, compression={"weights": "int8"}), "needs zero >= 1"),
    (dict(dp=2, zero=2, compression={"weights": {"type": "int8",
                                                 "residual": True}}),
     "needs zero=3"),
    (dict(dp=2, pp=2, microbatches=4, zero=3,
          compression={"weights": {"type": "int8", "residual": True}}),
     "residual"),
    (dict(dp=0), ">= 1"),
    (dict(zero=5), "zero must be"),
])
def test_plan_rejects(kw, frag):
    with pytest.raises(PlanError, match="(?s)" + frag.replace(
            "(", r"\(").replace(")", r"\)").replace("+", r"\+")
            .replace("*", r"\*").replace("%", "%")):
        ParallelPlan(**kw)


def test_plan_valid_constructions_and_describe():
    p = ParallelPlan(dp=2, pp=4, zero=3, microbatches=8, virtual=2,
                     compression={"activations": "int8",
                                  "weights": "int8"})
    assert p.total_devices == 8
    d = p.describe()
    assert "zero=3" in d and "virtual=2" in d
    assert "activations" in d and "weights" in d
    mesh = p.build_mesh()
    assert mesh.shape == {"dp": 2, "pp": 4, "tp": 1}
    # legacy flat compression dict counts as grads
    g, w, a = ParallelPlan(dp=2, compression={"type": "int8"})._comp_parts()
    assert g == {"type": "int8"} and w is None and a is None
    # frozen: plans are immutable signatures
    with pytest.raises(Exception):
        p.zero = 1


def test_plan_pp_tp_needs_elementwise_optimizer():
    net = _tp_chain()
    opt = opt_mod.create("lamb", learning_rate=0.01)
    plan = ParallelPlan(dp=2, pp=2, tp=2, microbatches=4)
    with pytest.raises(PlanError, match="elementwise"):
        plan.lower(net, L2Loss(), opt)


# -- composition fuzz grid ----------------------------------------------------

def _grid_cells():
    """Every valid {pp, tp, zero, virtual, compression} cell on 8
    devices (dp = 8 / (pp*tp)); invalid combos are matrix-rejected and
    covered by test_plan_rejects."""
    cells = []
    for pp in (2, 4):
        for tp in (1, 2):
            dp = 8 // (pp * tp)
            for zero in (0, 1, 2, 3):
                if zero >= 1 and (dp < 2 or tp > 1):
                    continue
                for virtual in (1, 2):
                    for comp in (False, True):
                        cells.append((dp, pp, tp, zero, virtual, comp))
    return cells


def _cell_id(c):
    dp, pp, tp, zero, virtual, comp = c
    return (f"dp{dp}-pp{pp}-tp{tp}-z{zero}-v{virtual}-"
            f"{'q' if comp else 'raw'}")


def _check_cell(dp, pp, tp, zero, virtual, comp):
    kw = {}
    if comp:
        kw["compression"] = {"activations": "int8"}
        if zero >= 1:
            kw["compression"]["weights"] = "int8"
    plan = ParallelPlan(dp=dp, pp=pp, tp=tp, zero=zero,
                        microbatches=8, virtual=virtual, **kw)
    if tp == 1:
        l_ref, w_ref = _reference("dense")
        losses, weights, step = _train(plan, _dense_chain)
    else:
        l_ref, w_ref = _reference("tp")
        losses, weights, step = _train(plan, _tp_chain, shape=(32, 4, 8))
    assert step.zero_stage in (zero, None) or step.zero_stage == zero
    if comp:
        # int8 wire with error feedback: small bounded drift
        np.testing.assert_allclose(losses, l_ref, rtol=5e-3, atol=5e-4)
    elif tp == 2:
        # split matmul: reduction-order drift amplified by momentum
        np.testing.assert_allclose(losses, l_ref, rtol=1e-4, atol=1e-6)
        for k in w_ref:
            np.testing.assert_allclose(weights[k], w_ref[k],
                                       rtol=1e-3, atol=1e-5)
    else:
        # SGD, full-precision wire: bit-exact-level parity
        np.testing.assert_allclose(losses, l_ref, atol=1e-6)
        for k in w_ref:
            np.testing.assert_allclose(weights[k], w_ref[k], atol=1e-6)


_CORE = [
    (4, 2, 1, 1, 1, False),
    (4, 2, 1, 3, 2, True),
    (2, 4, 1, 0, 2, False),
    (2, 4, 1, 2, 1, True),
    (2, 2, 2, 0, 1, False),
    (1, 4, 2, 0, 2, False),
]


@pytest.mark.parametrize("cell", _CORE, ids=_cell_id)
def test_plan_grid_core(cell):
    _check_cell(*cell)


@pytest.mark.slow
@pytest.mark.parametrize(
    "cell", [c for c in _grid_cells() if c not in _CORE], ids=_cell_id)
def test_plan_grid_full(cell):
    _check_cell(*cell)


def test_plan_adam_zero3_parity():
    kw = dict(opt_name="adam", opt_kw={"learning_rate": 0.01})
    l_ref, w_ref = _reference("dense", "adam")
    plan = ParallelPlan(dp=2, pp=4, zero=3, microbatches=8, virtual=2)
    losses, weights, step = _train(plan, _dense_chain, **kw)
    assert step.zero_stage == 3
    np.testing.assert_allclose(losses, l_ref, atol=1e-5)
    for k in w_ref:
        np.testing.assert_allclose(weights[k], w_ref[k], atol=1e-5)


def test_plan_zero3_not_clamped_no_warning():
    # the legacy path warns and clamps pipeline zero=3 -> 2; the plan
    # path runs real zero=3 with NO degrade warning
    plan = ParallelPlan(dp=2, pp=4, zero=3, microbatches=8)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _, _, step = _train(plan, _dense_chain, steps=1)
    assert step.zero_stage == 3
    assert not any("clamp" in str(x.message).lower() for x in w), \
        [str(x.message) for x in w]


def test_plan_one_executable_per_signature(caplog):
    import logging
    plan = ParallelPlan(dp=2, pp=4, microbatches=8, virtual=2)
    net = _dense_chain()
    step = plan.lower(net, L2Loss(),
                      opt_mod.create("sgd", learning_rate=0.1))
    rs = np.random.RandomState(0)
    old = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    try:
        with caplog.at_level(logging.WARNING):
            for _ in range(3):
                x = NDArray(jnp.asarray(rs.rand(32, 8), jnp.float32))
                step(x, x)
    finally:
        jax.config.update("jax_log_compiles", old)
    # the traced chunk index keeps every virtual chunk inside ONE
    # executable — the step function XLA-compiles exactly once
    compiles = [r.getMessage() for r in caplog.records
                if "fn_step" in r.getMessage()
                and "compilation" in r.getMessage().lower()]
    assert len(compiles) == 1, compiles


def test_plan_virtual_bubble_ratio_telemetry():
    from mxnet_tpu import telemetry as tm
    from mxnet_tpu.parallel.pipeline import (bubble_ratio,
                                             interleaved_bubble_ratio)
    tm.disable()
    tm.reset()
    try:
        tm.enable()
        plan = ParallelPlan(dp=2, pp=4, microbatches=8, virtual=2)
        _train(plan, _dense_chain, steps=2)
        snap = tm.snapshot()
        meas = snap["gauges"]["pipeline_bubble_ratio"]
        # interleaving cuts the bubble below the classic (n-1)/(M+n-1)
        assert meas == pytest.approx(
            interleaved_bubble_ratio(2 * 8 * 2 + 2 * 3, 8, 2))
        assert meas < bubble_ratio(4, 8)
        assert snap["gauges"]["pipeline_virtual_stages"] == 2
    finally:
        tm.disable()
        tm.reset()


def test_plan_goodput_axis_labels(monkeypatch):
    from mxnet_tpu import goodput as gp
    from mxnet_tpu import telemetry as tm
    tm.disable()
    tm.reset()
    gp.reset()
    # MFU only exists against a table peak; stand in for a v5e
    monkeypatch.setattr(gp, "peak_flops", lambda device=None: 197e12)
    try:
        tm.enable()
        gp.enable()
        # lower() records the plan's axis sizes for goodput attribution
        plan = ParallelPlan(dp=2, pp=4, microbatches=8)
        plan.lower(_dense_chain(), L2Loss(),
                   opt_mod.create("sgd", learning_rate=0.1))
        gp.note_train_step(1.0, model_flops=1e12, hw_flops=2e12)
        keys = [k for k in tm.snapshot()["gauges"]
                if k.startswith("goodput_mfu")
                or k.startswith("goodput_hfu")]
        assert keys
        assert all("dp=2" in k and "pp=4" in k and "tp=1" in k
                   and "ep=1" in k for k in keys), keys
        # reset clears the axis labels so later tests read unlabelled
        gp.reset()
        assert gp._PLAN_AXES == {}
    finally:
        gp.disable()
        gp.reset()
        tm.disable()
        tm.reset()


# -- expert parallelism through the plan --------------------------------------

def _moe_net(seed=0):
    from mxnet_tpu.parallel.moe import MoEMLP
    net = HybridSequential()
    net.add(nn.Dense(8, activation="tanh", in_units=8, flatten=False))
    # capacity_factor high enough that no token drops: local (per-rank)
    # routing then matches global routing exactly
    net.add(MoEMLP(8, 16, num_experts=4, top_k=2, capacity_factor=4.0,
                   ep_axis="dp"))
    net.add(nn.Dense(8, in_units=8, flatten=False))
    mx.random.seed(seed)
    net.initialize()
    return net


@pytest.mark.slow
def test_plan_ep_zero1_parity():
    kw = dict(opt_name="adam", opt_kw={"learning_rate": 0.01},
              shape=(16, 4, 8))
    l_ref, w_ref, _ = _train(local_mesh(1), _moe_net, **kw)
    plan = ParallelPlan(dp=2, ep=2, zero=1)
    losses, weights, step = _train(plan, _moe_net, **kw)
    np.testing.assert_allclose(losses, l_ref, rtol=1e-4, atol=1e-5)
    for k in w_ref:
        np.testing.assert_allclose(weights[k], w_ref[k],
                                   rtol=1e-3, atol=1e-5)


def test_plan_ep_rejects_outside_plan():
    # expert-sharded params hitting the legacy zero path (no plan) stay
    # a loud error pointing at ParallelPlan
    net = _moe_net()
    opt = opt_mod.create("adam", learning_rate=0.01)
    step = FusedTrainStep(net, L2Loss(), opt, mesh=local_mesh(2),
                          zero=1)
    x = NDArray(jnp.zeros((16, 4, 8), jnp.float32))
    with pytest.raises(ValueError, match="ParallelPlan"):
        step(x, x)


# -- double-buffered feed (run_steps next_batches=) ---------------------------

def test_run_steps_feed_double_buffer():
    from mxnet_tpu import telemetry as tm
    net = _dense_chain(4)
    opt = opt_mod.create("sgd", learning_rate=0.1)
    step = FusedTrainStep(net, L2Loss(), opt, mesh=local_mesh(8))
    rs = np.random.RandomState(0)

    def window():
        return [(NDArray(jnp.asarray(rs.rand(32, 8), jnp.float32)),
                 NDArray(jnp.asarray(rs.rand(32, 8), jnp.float32)))
                for _ in range(3)]

    tm.disable()
    tm.reset()
    try:
        tm.enable()
        w1, w2 = window(), window()
        l1 = step.run_steps(w1, next_batches=w2)
        l2 = step.run_steps(w2)  # consumes the staged window
        snap = tm.snapshot()
        assert snap["counters"].get("train_feed_windows_staged_total") == 1
        assert snap["counters"].get("train_feed_window_hits_total") == 1
        assert "train_feed_overlap_ms" in snap["gauges"]
        assert len(l1) == 3 and len(l2) == 3
        # a stale staging (different objects) falls through harmlessly
        step.run_steps(window(), next_batches=window())
        l3 = step.run_steps(window())
        assert len(l3) == 3
        snap = tm.snapshot()
        assert snap["counters"]["train_feed_window_hits_total"] == 1
    finally:
        tm.disable()
        tm.reset()


def test_run_steps_feed_parity():
    # staged-feed windows produce the same losses as unstaged
    def run(staged):
        net = _dense_chain(4)
        opt = opt_mod.create("sgd", learning_rate=0.1)
        step = FusedTrainStep(net, L2Loss(), opt, mesh=local_mesh(8))
        rs = np.random.RandomState(5)
        wins = [[(NDArray(jnp.asarray(rs.rand(32, 8), jnp.float32)),
                  NDArray(jnp.asarray(rs.rand(32, 8), jnp.float32)))
                 for _ in range(2)] for _ in range(3)]
        out = []
        for i, w in enumerate(wins):
            nxt = wins[i + 1] if staged and i + 1 < len(wins) else None
            out.extend(float(v) for v in
                       step.run_steps(w, next_batches=nxt))
        return out

    np.testing.assert_allclose(run(True), run(False), atol=0)


def test_train_loop_stages_next_window():
    from mxnet_tpu.train_loop import TrainLoop
    net = _dense_chain(4)
    opt = opt_mod.create("sgd", learning_rate=0.1)
    step = FusedTrainStep(net, L2Loss(), opt, mesh=local_mesh(8))
    rs = np.random.RandomState(9)
    data = [(NDArray(jnp.asarray(rs.rand(32, 8), jnp.float32)),
             NDArray(jnp.asarray(rs.rand(32, 8), jnp.float32)))
            for _ in range(6)]
    from mxnet_tpu import telemetry as tm
    tm.disable()
    tm.reset()
    try:
        tm.enable()
        loop = TrainLoop(step, k=2)
        loop.run(data)
        snap = tm.snapshot()
        # 3 windows -> the loop staged 2 lookaheads, both consumed
        assert snap["counters"]["train_feed_windows_staged_total"] == 2
        assert snap["counters"]["train_feed_window_hits_total"] == 2
    finally:
        tm.disable()
        tm.reset()
