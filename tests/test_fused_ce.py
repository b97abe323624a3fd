"""Fused Pallas softmax cross-entropy vs jnp reference (fwd + grads).
Kernels run under the Pallas interpreter on CPU — the same code the TPU
executes (reference analogue: src/operator/loss/softmax_cross_entropy.cc
+ the fork's vectorized softmax CUDA kernels)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.kernels import fused_ce
from mxnet_tpu.kernels.fused_ce import (_ce_pallas, fused_softmax_ce_raw,
                                        reference_softmax_ce)


def _data(n, v, seed=0, dtype=np.float32):
    rs = np.random.RandomState(seed)
    x = jnp.asarray((rs.randn(n, v) * 2).astype(dtype))
    lbl = jnp.asarray(rs.randint(0, v, n).astype(np.int32))
    return x, lbl


@pytest.mark.parametrize("n,v", [(16, 128), (5, 1000), (96, 2048)])
def test_forward_matches_reference(n, v):
    # n=5 exercises row padding; v=1000 exercises vocab padding
    x, lbl = _data(n, v)
    out = _ce_pallas(x, lbl, True)
    ref = reference_softmax_ce(x, lbl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_forward_bf16():
    x, lbl = _data(24, 512)
    xb = x.astype(jnp.bfloat16)
    out = _ce_pallas(xb, lbl, True)
    ref = reference_softmax_ce(xb, lbl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("n,v", [(16, 128), (5, 1000)])
def test_grads_match_reference(n, v):
    x, lbl = _data(n, v, seed=1)
    w = jnp.asarray(np.random.RandomState(2).rand(n).astype(np.float32))

    def lp(x_):
        return (_ce_pallas(x_, lbl, True) * w).sum()

    def lr(x_):
        return (reference_softmax_ce(x_, lbl) * w).sum()

    dp = jax.grad(lp)(x)
    dr = jax.grad(lr)(x)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dr),
                               rtol=1e-4, atol=1e-4)


def test_fallback_counts_and_returns_reference(monkeypatch):
    x, lbl = _data(8, 2048)
    monkeypatch.setenv("MXNET_TPU_CE_INTERPRET", "1")

    def boom(*a, **k):
        raise RuntimeError("forced kernel failure")

    monkeypatch.setattr(fused_ce, "_run_fwd", boom)
    before = fused_ce._fallback.count
    out = fused_softmax_ce_raw(x, lbl)
    assert fused_ce._fallback.count == before + 1
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(reference_softmax_ce(x, lbl)),
                               rtol=1e-5, atol=1e-5)


def test_strict_mode_raises(monkeypatch):
    x, lbl = _data(8, 2048)
    monkeypatch.setenv("MXNET_TPU_CE_INTERPRET", "1")
    monkeypatch.setenv("MXNET_TPU_STRICT_CE", "1")

    def boom(*a, **k):
        raise RuntimeError("forced kernel failure")

    monkeypatch.setattr(fused_ce, "_run_fwd", boom)
    with pytest.raises(RuntimeError, match="forced kernel failure"):
        fused_softmax_ce_raw(x, lbl)


def test_loss_block_rides_kernel(monkeypatch, request):
    """SoftmaxCrossEntropyLoss routes large-vocab sparse CE through the
    fused kernel (interpret mode here) and matches the jnp path —
    values AND gradients, eager and 3-D (B, T, V)."""
    monkeypatch.setenv("MXNET_TPU_CE_INTERPRET", "1")
    from mxnet_tpu.kernels import tuning
    tuning.set_runtime("fused_ce", "min_vocab", 64)
    request.addfinalizer(tuning.clear_runtime)
    import mxnet_tpu as mx

    rs = np.random.RandomState(0)
    B, T, V = 2, 6, 128
    pred = mx.nd.array(rs.randn(B, T, V).astype(np.float32))
    label = mx.nd.array(rs.randint(0, V, (B, T)).astype(np.float32))
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    pred.attach_grad()
    with mx.autograd.record():
        l_fused = loss_fn(pred, label).mean()
    l_fused.backward()
    g_fused = pred.grad.asnumpy()

    tuning.set_runtime("fused_ce", "min_vocab", 100000)  # force jnp
    pred2 = mx.nd.array(pred.asnumpy())
    pred2.attach_grad()
    with mx.autograd.record():
        l_ref = loss_fn(pred2, label).mean()
    l_ref.backward()
    np.testing.assert_allclose(float(l_fused.asscalar()),
                               float(l_ref.asscalar()), rtol=1e-5)
    np.testing.assert_allclose(g_fused, pred2.grad.asnumpy(),
                               rtol=1e-4, atol=1e-5)
