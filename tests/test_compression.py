"""Gradient compression: quantized allreduce with error feedback
(reference: src/kvstore/gradient_compression.cc 2-bit scheme; TPU-first
redesign compresses the collective itself — parallel/compression.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.compression import (
    compressed_psum, dequantize_2bit, quantize_2bit, quantize_int8)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def test_quantize_2bit_codes():
    x = jnp.asarray([-2.0, -0.4, 0.0, 0.4, 2.0])
    codes = quantize_2bit(x, 0.5)
    np.testing.assert_array_equal(np.asarray(codes), [-1, 0, 0, 0, 1])
    deq = dequantize_2bit(codes, 0.5)
    np.testing.assert_allclose(np.asarray(deq), [-0.5, 0, 0, 0, 0.5])


def test_quantize_int8_roundtrip():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(64).astype(np.float32))
    scale = jnp.max(jnp.abs(x)) / 127.0
    deq = quantize_int8(x, scale).astype(jnp.float32) * scale
    assert float(jnp.max(jnp.abs(deq - x))) <= float(scale) / 2 + 1e-7


@pytest.mark.parametrize("scheme", ["2bit", "int8"])
def test_compressed_psum_error_feedback_converges(scheme):
    # with error feedback, the *running sum* of reduced gradients tracks
    # the running sum of true mean gradients (residual never grows)
    mesh = make_mesh([8], ["dp"])
    rs = np.random.RandomState(1)
    gs = jnp.asarray(rs.randn(8, 32).astype(np.float32))  # per-dev grads
    true_mean = np.asarray(gs.mean(axis=0))

    # 2bit sends at most +-threshold per step, so pick the threshold
    # above the gradient scale (the sawtooth regime where the running
    # average is exact up to r_end/N); int8 is scale-adaptive
    threshold = 4.0

    def one_step(g, r):
        return compressed_psum(g[0], r[0], "dp", scheme,
                               threshold=threshold)

    f = jax.jit(shard_map(
        lambda g, r: jax.tree_util.tree_map(
            lambda x: x[None], one_step(g, r)),
        mesh=mesh, in_specs=(P("dp"), P("dp")), out_specs=P("dp")))

    N = 100
    r = jnp.zeros((8, 32), jnp.float32)
    acc = np.zeros(32, np.float32)
    for step in range(N):
        red, r = f(gs, r)
        acc += np.asarray(red[0])  # reduced value replicated; any shard
    # running average == true mean - mean(residual)/N: error feedback
    # guarantees nothing is lost beyond the final residual
    np.testing.assert_allclose(acc / N, true_mean, atol=0.1)
    # residual stays bounded (threshold + max|g|)
    assert float(jnp.max(jnp.abs(r))) < threshold + float(
        jnp.max(jnp.abs(gs))) + 1e-5


@pytest.mark.parametrize("scheme", ["int8", "2bit"])
def test_fused_step_compressed_converges(scheme):
    # DP training with quantized allreduce reaches parity with fp32 DP
    # on a toy classification problem
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep
    mesh = make_mesh([8], ["dp"])
    rs = np.random.RandomState(2)
    X = rs.rand(64, 10).astype(np.float32)
    W = rs.randn(10, 3).astype(np.float32)
    y = np.argmax(X @ W + 0.05 * rs.randn(64, 3), axis=1)

    def make_net():
        mx.random.seed(0)
        net = mx.gluon.nn.HybridSequential()
        net.add(mx.gluon.nn.Dense(16, activation="relu"),
                mx.gluon.nn.Dense(3))
        net.initialize()
        return net

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    results = {}
    for comp in (None, {"type": scheme, "threshold": 0.02}):
        net = make_net()
        step = FusedTrainStep(net, loss_fn,
                              mx.optimizer.SGD(learning_rate=0.2),
                              mesh=mesh, compression=comp)
        xs, ys = mx.nd.array(X), mx.nd.array(y)
        first = None
        for _ in range(80):
            l = step(xs, ys)
            if first is None:
                first = float(l.asscalar())
        results[scheme if comp else "fp32"] = (first,
                                               float(l.asscalar()))
    if scheme == "int8":
        # int8 is scale-adaptive: near-lossless, parity with fp32
        assert results[scheme][1] < results["fp32"][1] + 0.1, results
    # both schemes must actually train
    first, last = results[scheme]
    assert last < 0.5 * first, results


def test_fused_step_int8_tracks_fp32_step_for_step():
    """int8 is near-lossless, so the quantized-allreduce step must follow
    the fp32 GSPMD step loss for loss — not merely converge. Under the
    varying-axes checker the shard_map'd step once psum'd the gradient
    implicitly before its own collective summed it again: an ndp-fold
    gradient, which still "converged" (faster) and passed the test
    above."""
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep
    mesh = make_mesh([8], ["dp"])
    rs = np.random.RandomState(2)
    X = rs.rand(64, 10).astype(np.float32)
    y = rs.randint(0, 3, 64)
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    curves = []
    for comp in (None, {"type": "int8"}):
        mx.random.seed(0)
        net = mx.gluon.nn.HybridSequential()
        net.add(mx.gluon.nn.Dense(16, activation="relu"),
                mx.gluon.nn.Dense(3))
        net.initialize()
        step = FusedTrainStep(net, loss_fn,
                              mx.optimizer.SGD(learning_rate=0.2),
                              mesh=mesh, compression=comp)
        xs, ys = mx.nd.array(X), mx.nd.array(y)
        curves.append([float(step(xs, ys).asscalar())
                       for _ in range(6)])
    np.testing.assert_allclose(curves[1], curves[0], atol=2e-3)


def test_kvstore_eager_compression_2bit():
    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init(0, mx.nd.zeros((4,)))
    # two replicas push; values beyond the threshold survive, small
    # values are withheld into the residual...
    g1 = mx.nd.array(np.array([1.0, 0.2, -1.0, 0.0], np.float32))
    g2 = mx.nd.array(np.array([1.0, 0.2, -1.0, 0.0], np.float32))
    kv.push(0, [g1, g2])
    out = mx.nd.zeros((4,))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), [1.0, 0.0, -1.0, 0.0])
    # ...the small 0.2 entries accumulate in the residual; after enough
    # pushes (0.2 * 3 > 0.5) they cross the threshold and get sent
    kv.push(0, [g1, g2])
    kv.push(0, [g1, g2])
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), [1.0, 1.0, -1.0, 0.0])


def test_kvstore_rejects_unknown_compression():
    kv = mx.kv.create("device")
    with pytest.raises(ValueError):
        kv.set_gradient_compression({"type": "fp8"})


def test_kvstore_single_push_compresses():
    # Trainer._update pushes one NDArray per key (not a replica list);
    # compression must still apply
    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init(0, mx.nd.zeros((3,)))
    kv.push(0, mx.nd.array(np.array([1.0, 0.2, -1.0], np.float32)))
    out = mx.nd.zeros((3,))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), [0.5, 0.0, -0.5])


def test_compression_warns_when_meshless():
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep
    mx.random.seed(3)
    net = mx.gluon.nn.Dense(2, in_units=4)
    net.initialize()
    step = FusedTrainStep(net, mx.gluon.loss.L2Loss(),
                          mx.optimizer.SGD(learning_rate=0.1),
                          mesh=None, compression={"type": "int8"})
    with pytest.warns(RuntimeWarning, match="compression"):
        step(mx.nd.ones((2, 4)), mx.nd.ones((2, 2)))


def test_compressed_step_checkpoint_shardings_exist():
    # Checkpointer.restore reads _tr_sh/_st_sh off a built step
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep
    mesh = make_mesh([8], ["dp"])
    mx.random.seed(4)
    net = mx.gluon.nn.Dense(2, in_units=4)
    net.initialize()
    step = FusedTrainStep(net, mx.gluon.loss.L2Loss(),
                          mx.optimizer.SGD(learning_rate=0.1),
                          mesh=mesh, compression={"type": "int8"})
    step(mx.nd.ones((8, 4)), mx.nd.ones((8, 2)))
    assert step._tr_sh and step._st_sh is not None
    for n in step._tr_names:
        assert n in step._tr_sh


def test_dist_async_stale_updates_differ_from_sync():
    # async applies one momentum update per replica push (stale reads);
    # sync aggregates then updates once — different trajectories
    def run(kv_type):
        kv = mx.kv.create(kv_type)
        kv.init(0, mx.nd.array(np.ones(4, np.float32)))
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1,
                                          momentum=0.9))
        g1 = mx.nd.array(np.full(4, 1.0, np.float32))
        g2 = mx.nd.array(np.full(4, 2.0, np.float32))
        kv.push(0, [g1, g2])
        kv.push(0, [g1, g2])
        out = mx.nd.zeros((4,))
        kv.pull(0, out=out)
        return out.asnumpy()

    w_async = run("dist_async")
    w_sync = run("dist_sync")
    assert not np.allclose(w_async, w_sync), (w_async, w_sync)
    # both still descend
    assert (w_async < 1.0).all() and (w_sync < 1.0).all()


def test_pushpull_with_optimizer_compresses_once():
    # regression: pushpull used to quantize the replica list, then push
    # re-quantized the aggregate (halving every update)
    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init(0, mx.nd.array(np.zeros(3, np.float32)))
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0))
    g = mx.nd.array(np.array([0.6, 0.0, -0.6], np.float32))
    kv.pushpull(0, [g, g])
    out = mx.nd.zeros((3,))
    kv.pull(0, out=out)
    # each replica sends 0.5 -> aggregate 1.0 applied once with lr 1
    np.testing.assert_allclose(out.asnumpy(), [-1.0, 0.0, 1.0])


def test_compression_residuals_survive_replica_count_change():
    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init(0, mx.nd.zeros((2,)))
    g = mx.nd.array(np.array([1.0, 0.0], np.float32))
    kv.push(0, g)          # single push: one residual slot
    kv.push(0, [g, g])     # list push: must grow, not IndexError
    out = mx.nd.zeros((2,))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), [1.0, 0.0])
