"""Pipeline parallelism: GPipe / 1F1B schedules ≡ sequential stage
application (SURVEY §4), auto-staging of HybridSequential, and the
FusedTrainStep(pipeline=M) training path incl. ZeRO composition."""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import make_mesh, set_mesh
from mxnet_tpu.parallel.mesh import hybrid_mesh, local_mesh
from mxnet_tpu.parallel.pipeline import (
    bubble_ratio, gpipe, one_f_one_b, pipeline_stages, sequential_apply,
    stack_stage_params, stash_slots)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def _stage_fn(p, h):
    h = jnp.tanh(h @ p["w1"] + p["b1"])
    return h @ p["w2"] + p["b2"]


def _make_params(n_stages, d, hidden, seed=0):
    rs = np.random.RandomState(seed)
    ps = [{"w1": jnp.asarray(rs.randn(d, hidden).astype(np.float32) * 0.3),
           "b1": jnp.asarray(rs.randn(hidden).astype(np.float32) * 0.1),
           "w2": jnp.asarray(rs.randn(hidden, d).astype(np.float32) * 0.3),
           "b2": jnp.asarray(rs.randn(d).astype(np.float32) * 0.1)}
          for _ in range(n_stages)]
    return stack_stage_params(ps)


@pytest.fixture
def pp_mesh():
    m = make_mesh([4], ["pp"])
    set_mesh(m)
    yield m
    set_mesh(None)


@pytest.mark.parametrize("num_microbatches", [4, 8])
def test_gpipe_equals_sequential(pp_mesh, num_microbatches):
    params = _make_params(4, 8, 16)
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.rand(16, 8).astype(np.float32))
    ref = sequential_apply(_stage_fn, params, x)
    out = gpipe(_stage_fn, params, x, num_microbatches, mesh=pp_mesh)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_gpipe_grad_matches(pp_mesh):
    params = _make_params(4, 6, 12, seed=2)
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.rand(8, 6).astype(np.float32))

    def loss_pipe(p):
        return (gpipe(_stage_fn, p, x, 4, mesh=pp_mesh) ** 2).sum()

    def loss_seq(p):
        return (sequential_apply(_stage_fn, p, x) ** 2).sum()

    g_pipe = jax.grad(loss_pipe)(params)
    g_seq = jax.grad(loss_seq)(params)
    for k in g_seq:
        assert np.allclose(np.asarray(g_pipe[k]), np.asarray(g_seq[k]),
                           atol=1e-3), k


def test_gpipe_under_jit(pp_mesh):
    params = _make_params(4, 8, 16, seed=4)
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.rand(8, 8).astype(np.float32))

    out = jax.jit(lambda p, x_: gpipe(_stage_fn, p, x_, 4,
                                      mesh=pp_mesh))(params, x)
    ref = sequential_apply(_stage_fn, params, x)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_gpipe_no_mesh_fallback():
    set_mesh(None)
    params = _make_params(3, 4, 8, seed=6)
    x = jnp.asarray(np.random.RandomState(7).rand(6, 4).astype(np.float32))
    out = gpipe(_stage_fn, params, x, 2, mesh=None)
    ref = sequential_apply(_stage_fn, params, x)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def _mse_loss(out, y):
    return ((out - y) ** 2).mean()


@pytest.mark.parametrize("num_microbatches", [4, 8])
@pytest.mark.slow
def test_1f1b_matches_sequential_grads(pp_mesh, num_microbatches):
    from mxnet_tpu.parallel.pipeline import one_f_one_b
    params = _make_params(4, 6, 12, seed=8)
    rs = np.random.RandomState(9)
    B = 2 * num_microbatches
    x = jnp.asarray(rs.rand(B, 6).astype(np.float32))
    y = jnp.asarray(rs.rand(B, 6).astype(np.float32))

    loss, grads = one_f_one_b(_stage_fn, params, x, y, _mse_loss,
                              num_microbatches, mesh=pp_mesh)
    loss_ref, grads_ref = one_f_one_b(_stage_fn, params, x, y, _mse_loss,
                                      num_microbatches, mesh=None)
    assert np.allclose(float(loss), float(loss_ref), atol=1e-5)
    for k in grads_ref:
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(grads_ref[k]),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_1f1b_matches_autodiff(pp_mesh):
    # cross-check the schedule against plain jax.grad of the sequential
    # mean-microbatch loss
    from mxnet_tpu.parallel.pipeline import one_f_one_b, sequential_apply
    params = _make_params(4, 4, 8, seed=10)
    rs = np.random.RandomState(11)
    M, mb = 6, 3
    x = jnp.asarray(rs.rand(M * mb, 4).astype(np.float32))
    y = jnp.asarray(rs.rand(M * mb, 4).astype(np.float32))

    def total(p):
        outs = sequential_apply(_stage_fn, p,
                                x.reshape(M * mb, 4))
        return _mse_loss(outs.reshape(M, mb, 4),
                         y.reshape(M, mb, 4))

    g_ref = jax.grad(total)(params)
    loss, grads = one_f_one_b(_stage_fn, params, x, y, _mse_loss, M,
                              mesh=pp_mesh)
    for k in g_ref:
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(g_ref[k]),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_1f1b_under_jit(pp_mesh):
    from mxnet_tpu.parallel.pipeline import one_f_one_b
    params = _make_params(4, 4, 8, seed=12)
    rs = np.random.RandomState(13)
    x = jnp.asarray(rs.rand(8, 4).astype(np.float32))
    y = jnp.asarray(rs.rand(8, 4).astype(np.float32))
    f = jax.jit(lambda p, x_, y_: one_f_one_b(
        _stage_fn, p, x_, y_, _mse_loss, 4, mesh=pp_mesh))
    loss, grads = f(params, x, y)
    loss_ref, grads_ref = one_f_one_b(_stage_fn, params, x, y,
                                      _mse_loss, 4, mesh=None)
    assert np.allclose(float(loss), float(loss_ref), atol=1e-5)
    for k in grads_ref:
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(grads_ref[k]),
                                   rtol=1e-4, atol=1e-5)


# -- schedule-equivalence fuzz grids ----------------------------------------
# random (num_stages, M, mb, dtype) including M < n and M not a
# multiple of the in-flight slot count; each case builds its own pp mesh

_FUZZ_GRID = [
    (2, 3, 2, "float32"),   # M not a multiple of n
    (4, 2, 2, "float32"),   # M < n (pipeline mostly bubble)
    (3, 5, 1, "float32"),   # mb=1, n does not divide M
    (8, 4, 2, "float32"),   # all 8 devices, M < n
    (4, 8, 3, "bfloat16"),  # bf16 end to end
]


def _fuzz_case(n, M, mb, dtype, seed):
    rs = np.random.RandomState(seed)
    d = 6
    params = stack_stage_params(
        [{"w1": jnp.asarray(rs.randn(d, 10) * 0.3, dtype),
          "b1": jnp.asarray(rs.randn(10) * 0.1, dtype),
          "w2": jnp.asarray(rs.randn(10, d) * 0.3, dtype),
          "b2": jnp.asarray(rs.randn(d) * 0.1, dtype)}
         for _ in range(n)])
    x = jnp.asarray(rs.rand(M * mb, d), dtype)
    y = jnp.asarray(rs.rand(M * mb, d), dtype)
    return params, x, y


@pytest.mark.parametrize("n,M,mb,dtype", _FUZZ_GRID)
def test_fuzz_gpipe_equals_sequential(n, M, mb, dtype):
    params, x, _ = _fuzz_case(n, M, mb, dtype, seed=n * 100 + M)
    mesh = make_mesh([n], ["pp"])
    ref = sequential_apply(_stage_fn, params, x)
    out = gpipe(_stage_fn, params, x, M, mesh=mesh)
    assert out.dtype == ref.dtype
    tol = 1e-5 if dtype == "float32" else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("n,M,mb,dtype", _FUZZ_GRID)
def test_fuzz_1f1b_equals_sequential(n, M, mb, dtype):
    params, x, y = _fuzz_case(n, M, mb, dtype, seed=n * 10 + M)
    mesh = make_mesh([n], ["pp"])
    loss, grads = one_f_one_b(_stage_fn, params, x, y, _mse_loss, M,
                              mesh=mesh)
    loss_ref, grads_ref = one_f_one_b(_stage_fn, params, x, y,
                                      _mse_loss, M, mesh=None)
    if dtype == "float32":
        assert np.allclose(float(loss), float(loss_ref), atol=1e-5)
        for k in grads_ref:
            np.testing.assert_allclose(np.asarray(grads[k]),
                                       np.asarray(grads_ref[k]),
                                       rtol=1e-4, atol=1e-5), k
    else:
        # bf16 end to end: schedule vs sequential differ only by
        # accumulation order, bounded by bf16 resolution
        assert abs(float(loss) - float(loss_ref)) < 0.05
        for k in grads_ref:
            np.testing.assert_allclose(
                np.asarray(grads[k], np.float32),
                np.asarray(grads_ref[k], np.float32),
                rtol=0.2, atol=0.08), k


def test_1f1b_bf16_keeps_loss_and_cotangent_dtype(pp_mesh):
    # the loss accumulator matches the loss dtype (not hardcoded fp32)
    # and cotangents ride the pipeline in the activation dtype
    params, x, y = _fuzz_case(4, 4, 2, "bfloat16", seed=21)
    loss, grads = one_f_one_b(_stage_fn, params, x, y, _mse_loss, 4,
                              mesh=pp_mesh)
    assert loss.dtype == jnp.bfloat16
    assert grads["w1"].dtype == jnp.bfloat16
    loss_f, grads_f = one_f_one_b(_stage_fn, params, x, y, _mse_loss, 4,
                                  mesh=None)
    assert loss_f.dtype == jnp.bfloat16


def test_stack_stage_params_mismatch_errors():
    # shape mismatch names the stage index
    with pytest.raises(ValueError, match="stage 1"):
        stack_stage_params([{"w": jnp.zeros((2, 3))},
                            {"w": jnp.zeros((3, 3))}])
    # dtype mismatch too
    with pytest.raises(ValueError, match="stage 2"):
        stack_stage_params([{"w": jnp.zeros((2,))},
                            {"w": jnp.zeros((2,))},
                            {"w": jnp.zeros((2,), jnp.bfloat16)}])
    # treedef mismatch
    with pytest.raises(ValueError, match="stage 1.*structure"):
        stack_stage_params([{"w": jnp.zeros((2,))},
                            {"v": jnp.zeros((2,))}])
    with pytest.raises(ValueError, match="empty"):
        stack_stage_params([])


def test_bubble_math_helpers():
    assert bubble_ratio(4, 8) == pytest.approx(3 / 11)
    assert bubble_ratio(1, 8) == 0.0
    assert stash_slots(4) == 7   # O(num_stages), not O(M)
    assert stash_slots(1) == 1


def test_1f1b_stash_is_bounded_by_stages_not_microbatches(pp_mesh):
    """The stash claim as a count: the compiled 1F1B step keeps 2n-1
    stage inputs where GPipe under plain reverse-mode AD keeps all M,
    so at n=4, M=16 its temporaries (`memory_analysis()`, bytes) are
    under half of GPipe's; the slot count alone says 16/7."""
    n, M, mb, d = 4, 16, 8, 64
    params = _make_params(n, d, d)
    x = jax.ShapeDtypeStruct((M * mb, d), jnp.float32)

    def f1b(p, x_, y_):
        return one_f_one_b(_stage_fn, p, x_, y_, _mse_loss, M,
                           mesh=pp_mesh)

    def gpipe_ad(p, x_, y_):
        return jax.grad(lambda q: _mse_loss(
            gpipe(_stage_fn, q, x_, M, mesh=pp_mesh), y_))(p)

    temp = [jax.jit(f).lower(params, x, x).compile()
            .memory_analysis().temp_size_in_bytes
            for f in (f1b, gpipe_ad)]
    assert 0 < 2 * temp[0] <= temp[1], temp


# -- auto-staging a HybridSequential ----------------------------------------

def _dense_chain(n_blocks, d=8, seed=0):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.block import HybridSequential
    net = HybridSequential()
    for _ in range(n_blocks):
        net.add(nn.Dense(d, activation="tanh", in_units=d,
                         flatten=False))
    mx.random.seed(seed)
    net.initialize()
    return net


def test_pipeline_stages_balanced_and_equivalent():
    from mxnet_tpu.ndarray import NDArray
    net = _dense_chain(6)
    x = NDArray(jnp.asarray(np.random.RandomState(0).rand(8, 8),
                            jnp.float32))
    ref = net(x)._data
    staged = pipeline_stages(net, 4, sample=x)
    # 6 blocks over 4 stages: contiguous, non-empty, max 2 slots,
    # short stages identity-padded via the mask
    assert [b for run in staged.assignment for b in run] == list(range(6))
    assert all(run for run in staged.assignment)
    assert staged.num_slots == 2
    assert staged.mask.shape == (4, 2)
    assert float(staged.mask.sum()) == 6.0
    out = sequential_apply(staged.stage_fn, staged.params, x._data)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6)
    mesh = make_mesh([4], ["pp"])
    # restack() commits leaves to the default device; detach so the
    # 4-device pp mesh can place them
    host = jax.tree_util.tree_map(lambda a: jnp.asarray(np.asarray(a)),
                                  staged.params)
    out_p = gpipe(staged.stage_fn, host, x._data, 4, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(ref),
                               atol=1e-5)


def test_pipeline_stages_padded_slots_get_zero_grads():
    from mxnet_tpu.ndarray import NDArray
    net = _dense_chain(3)
    x = NDArray(jnp.asarray(np.random.RandomState(1).rand(8, 8),
                            jnp.float32))
    staged = pipeline_stages(net, 2, sample=x)   # stages of 2 and 1
    y = jnp.asarray(np.random.RandomState(2).rand(8, 8), jnp.float32)
    mesh = make_mesh([2], ["pp"])
    host = jax.tree_util.tree_map(lambda a: jnp.asarray(np.asarray(a)),
                                  staged.params)
    _, grads = one_f_one_b(staged.stage_fn, host, x._data, y,
                           _mse_loss, 2, mesh=mesh)
    pad_i, pad_j = [(i, j) for i in range(2) for j in range(2)
                    if (i, j) not in staged.slot_map][0]
    for k in staged.param_names:
        g = np.asarray(grads[k])
        assert np.all(g[pad_i, pad_j] == 0.0), k  # masked slot: no grad
        assert np.any(g != 0.0), k                # real slots learn


def test_hybrid_sequential_pipeline_stages_method():
    from mxnet_tpu.ndarray import NDArray
    net = _dense_chain(4)
    x = NDArray(jnp.asarray(np.random.RandomState(3).rand(4, 8),
                            jnp.float32))
    staged = net.pipeline_stages(2, sample=x)
    assert staged.num_stages == 2 and staged.num_slots == 2
    out = sequential_apply(staged.stage_fn, staged.params, x._data)
    np.testing.assert_allclose(np.asarray(out), np.asarray(net(x)._data),
                               atol=1e-6)


def test_pipeline_stages_clear_errors():
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.block import HybridSequential
    from mxnet_tpu.ndarray import NDArray
    import mxnet_tpu as mx
    x = NDArray(jnp.zeros((4, 8), jnp.float32))

    net = _dense_chain(2)
    with pytest.raises(ValueError,
                       match=r"at least pp\*virtual=4 blocks"):
        pipeline_stages(net, 4, sample=x)
    with pytest.raises(ValueError, match="sample"):
        pipeline_stages(_dense_chain(4), 2)

    mixed = HybridSequential()
    mixed.add(nn.Dense(8, in_units=8, flatten=False))
    mixed.add(nn.Activation("tanh"))
    mx.random.seed(0)
    mixed.initialize()
    with pytest.raises(ValueError, match="mixed block classes"):
        pipeline_stages(mixed, 2, sample=x)

    hetero = HybridSequential()
    hetero.add(nn.Dense(8, in_units=8, flatten=False))
    hetero.add(nn.Dense(8, in_units=8, use_bias=False, flatten=False))
    mx.random.seed(0)
    hetero.initialize()
    with pytest.raises(ValueError, match="block 1"):
        pipeline_stages(hetero, 2, sample=x)

    widen = HybridSequential()
    widen.add(nn.Dense(16, in_units=8, flatten=False))
    widen.add(nn.Dense(16, in_units=16, flatten=False))
    mx.random.seed(0)
    widen.initialize()
    with pytest.raises(ValueError, match="block 1 parameter"):
        # same class but different shapes -> not stackable
        pipeline_stages(widen, 2, sample=x)

    bn = HybridSequential()
    bn.add(nn.BatchNorm(in_channels=8))
    bn.add(nn.BatchNorm(in_channels=8))
    mx.random.seed(0)
    bn.initialize()
    with pytest.raises(ValueError, match="aux parameter"):
        pipeline_stages(bn, 2, sample=x)


# -- FusedTrainStep(pipeline=M): the 1F1B training path ---------------------

def _fused_run(pipeline, zero, mesh, opt_name="sgd", opt_kw=None,
               steps=3, seed=0, n_blocks=8, **fkw):
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.gluon.loss import L2Loss
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep
    net = _dense_chain(n_blocks, seed=seed)
    opt = opt_mod.create(opt_name, **(opt_kw or {"learning_rate": 0.1,
                                                 "momentum": 0.9}))
    step = FusedTrainStep(net, L2Loss(), opt, mesh=mesh,
                          pipeline=pipeline, zero=zero, **fkw)
    rs = np.random.RandomState(42)
    losses = []
    for _ in range(steps):
        x = NDArray(jnp.asarray(rs.rand(32, 8), jnp.float32))
        y = NDArray(jnp.asarray(rs.rand(32, 8), jnp.float32))
        losses.append(float(step(x, y)))
    step.sync_to_params()
    weights = {k: np.asarray(p.data()._data)
               for k, p in net.collect_params().items()}
    return losses, weights, step


def test_fused_pipeline_pp_dp_zero1_parity_sgd():
    # acceptance: pp=4 x dp=2, pipeline=8, zero=1 matches the
    # unpipelined dp=8 reference (SGD at float-rounding level)
    l_ref, w_ref, _ = _fused_run(None, None, local_mesh(8))
    l_pp, w_pp, step = _fused_run(8, 1, hybrid_mesh(dp=2, pp=4))
    assert step.zero_stage == 1 and step._pp_staged is not None
    np.testing.assert_allclose(l_pp, l_ref, atol=1e-6)
    for k in w_ref:
        np.testing.assert_allclose(w_pp[k], w_ref[k], atol=1e-6), k


def test_fused_pipeline_pp_dp_zero1_parity_adam():
    kw = dict(opt_name="adam", opt_kw={"learning_rate": 0.01})
    l_ref, w_ref, _ = _fused_run(None, None, local_mesh(8), **kw)
    l_pp, w_pp, _ = _fused_run(8, 1, hybrid_mesh(dp=2, pp=4), **kw)
    np.testing.assert_allclose(l_pp, l_ref, atol=1e-5)
    for k in w_ref:
        np.testing.assert_allclose(w_pp[k], w_ref[k], atol=1e-5), k


@pytest.mark.slow
def test_fused_pipeline_zero2_and_accum_parity():
    kw = dict(opt_name="adam", opt_kw={"learning_rate": 0.01})
    l_ref, w_ref, _ = _fused_run(None, None, local_mesh(8),
                                 grad_accum=2, **kw)
    l_pp, w_pp, _ = _fused_run(4, 2, hybrid_mesh(dp=2, pp=4),
                               grad_accum=2, **kw)
    np.testing.assert_allclose(l_pp, l_ref, atol=1e-5)
    for k in w_ref:
        np.testing.assert_allclose(w_pp[k], w_ref[k], atol=1e-5), k


@pytest.mark.slow
def test_fused_pipeline_compression_composes_with_zero():
    # int8 codes ride the dp collective; zero=1 must be bit-identical
    # to the unsharded compressed pipeline update
    comp = {"type": "int8"}
    _, w0, _ = _fused_run(8, None, hybrid_mesh(dp=2, pp=4),
                          compression=comp)
    _, w1, _ = _fused_run(8, 1, hybrid_mesh(dp=2, pp=4),
                          compression=comp)
    for k in w0:
        np.testing.assert_allclose(w1[k], w0[k], atol=0), k


def test_fused_pipeline_degrades_without_pp_axis():
    with warnings.catch_warnings(record=True) as wlist:
        warnings.simplefilter("always")
        l_d, w_d, step = _fused_run(8, None, local_mesh(8))
    assert any("no 'pp' axis" in str(w.message) for w in wlist)
    assert step._pp_staged is None  # plain path, sequential semantics
    l_ref, w_ref, _ = _fused_run(None, None, local_mesh(8))
    np.testing.assert_allclose(l_d, l_ref, atol=0)
    for k in w_ref:
        np.testing.assert_allclose(w_d[k], w_ref[k], atol=0), k


def test_fused_pipeline_norm_rule_degrades_zero():
    with warnings.catch_warnings(record=True) as wlist:
        warnings.simplefilter("always")
        _, _, step = _fused_run(8, 1, hybrid_mesh(dp=2, pp=4),
                                opt_name="lamb",
                                opt_kw={"learning_rate": 0.01}, steps=1)
    assert any("elementwise update rule" in str(w.message)
               for w in wlist)
    assert step.zero_stage == 0  # unsharded; per-slot vmap keeps norms


def test_fused_pipeline_zero3_clamps_to_2():
    with warnings.catch_warnings(record=True) as wlist:
        warnings.simplefilter("always")
        _, _, step = _fused_run(4, 3, hybrid_mesh(dp=2, pp=4), steps=1)
    assert any("clamped to zero=2" in str(w.message) for w in wlist)
    assert step.zero_stage == 2


def test_fused_pipeline_batch_divisibility_error():
    from mxnet_tpu.gluon.loss import L2Loss
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep
    net = _dense_chain(4)
    step = FusedTrainStep(net, L2Loss(),
                          opt_mod.create("sgd", learning_rate=0.1),
                          mesh=hybrid_mesh(dp=2, pp=4), pipeline=8)
    x = NDArray(jnp.zeros((24, 8), jnp.float32))  # 24 % (2*8) != 0
    with pytest.raises(ValueError, match="must divide"):
        step(x, x)


def test_fused_pipeline_telemetry_bubble_ratio():
    from mxnet_tpu import telemetry as tm
    tm.disable()
    tm.reset()
    try:
        tm.enable()
        _fused_run(8, None, hybrid_mesh(dp=1, pp=4), steps=2,
                   n_blocks=4)
        snap = tm.snapshot()
        assert snap["gauges"]["pipeline_bubble_ratio"] == \
            pytest.approx(bubble_ratio(4, 8))
        hist = snap["histograms"]["step_time_breakdown{phase=pipeline_fill}"]
        assert hist["count"] >= 2
        assert "step_time_breakdown{phase=pipeline_steady}" in \
            snap["histograms"]
        assert "step_time_breakdown{phase=pipeline_drain}" in \
            snap["histograms"]
    finally:
        tm.disable()
        tm.reset()


def test_fused_pipeline_resident_bytes_pp_sharded():
    _, _, step = _fused_run(8, 1, hybrid_mesh(dp=2, pp=4), steps=1)
    res = step.fused_resident_bytes()
    tot = sum(v.nbytes for v in jax.tree_util.tree_leaves(step._tr))
    # stacked weights shard over pp: per-replica is global/4
    assert res["weights"] == tot // 4
    assert res["opt_state"] > 0


def test_trainer_pipeline_passthrough():
    from mxnet_tpu.gluon.trainer import Trainer
    from mxnet_tpu.gluon.loss import L2Loss
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep
    net = _dense_chain(4)
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1}, pipeline=4)
    step = FusedTrainStep(net, L2Loss(), trainer,
                          mesh=hybrid_mesh(dp=2, pp=4))
    assert step.pipeline == 4
    x = NDArray(jnp.asarray(np.random.RandomState(5).rand(16, 8),
                            jnp.float32))
    float(step(x, x))  # builds and runs the pipelined executable
    assert step._pp_staged is not None
    with pytest.raises(ValueError, match="positive microbatch"):
        Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                pipeline=0)


# -- interleaved virtual-stage schedule (Megatron arXiv:2104.04473) ---------

def test_interleaved_schedule_tables_are_consistent():
    """Every (m, virtual stage) runs exactly one fwd and one bwd, in
    dependency order with the 1-tick wire latency, and the measured
    length beats the non-interleaved schedule's tick count."""
    from mxnet_tpu.parallel.pipeline import interleaved_schedule
    n, v, M = 4, 2, 8
    sch = interleaved_schedule(n, v, M)
    L = n * v
    col = {f: i for i, f in enumerate(sch.FIELDS)}
    done = {}
    for t in range(sch.total_ticks):
        for r in range(n):
            row = sch.table[t, r]
            kind = int(row[col["op_kind"]])
            if kind == 0:
                continue
            m, c = int(row[col["op_m"]]), int(row[col["op_c"]])
            s = c * n + r
            key = ("f" if kind == 1 else "b", m, s)
            assert key not in done, key       # each op exactly once
            done[key] = t
            if kind == 1 and s > 0:
                assert done[("f", m, s - 1)] < t
            if kind == 2:
                if s == L - 1:
                    assert done[("f", m, s)] < t
                else:
                    assert done[("b", m, s + 1)] < t
    assert len(done) == 2 * M * L
    # measured bubble below the classic (n-1)/(M+n-1) floor
    assert sch.bubble_ratio() < bubble_ratio(n, M)
    assert sch.total_ticks == 2 * M * v + 2 * (n - 1)  # Megatron optimum


def test_interleaved_schedule_rejects_uneven_microbatches():
    from mxnet_tpu.parallel.pipeline import InterleavedSchedule
    with pytest.raises(ValueError, match="divisible by pp"):
        InterleavedSchedule(4, 2, 6)
    with pytest.raises(ValueError, match="pp >= 2"):
        InterleavedSchedule(1, 2, 8)


def test_interleaved_bubble_ratio_formula():
    from mxnet_tpu.parallel.pipeline import interleaved_bubble_ratio
    # at the optimum T = 2Mv + 2(n-1) the ratio is (n-1)/(Mv + n-1)
    n, v, M = 4, 2, 8
    T = 2 * M * v + 2 * (n - 1)
    assert interleaved_bubble_ratio(T, M, v) == pytest.approx(
        (n - 1) / (M * v + n - 1))
    # v=1 at T = 2M + 2(n-1) reduces to the classic ratio
    T1 = 2 * M + 2 * (n - 1)
    assert interleaved_bubble_ratio(T1, M, 1) == pytest.approx(
        bubble_ratio(n, M))
