"""Pallas flash-attention kernel vs exact reference attention.

The kernel runs under the Pallas interpreter on CPU — same kernel code
the TPU executes, so online-softmax/tiling/GQA/causal-masking logic is
validated without a chip."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.kernels.flash_attention import (_pallas_forward,
                                               reference_attention)


def _qkv(B=2, T=256, H=4, K=2, d=16, seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, T, H, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(B, T, K, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(B, T, K, d).astype(np.float32) * 0.3)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_kernel_matches_reference(causal):
    q, k, v = _qkv()
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = reference_attention(q, k, v, causal=causal, scale=scale)
    out = _pallas_forward(q, k, v, causal=causal, scale=scale,
                          block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_pallas_kernel_gqa_grouping():
    # H=8 query heads sharing K=2 kv heads — grouping must map h//rep
    q, k, v = _qkv(B=1, T=128, H=8, K=2, d=8, seed=3)
    scale = 1.0 / np.sqrt(8)
    ref = reference_attention(q, k, v, causal=True, scale=scale)
    out = _pallas_forward(q, k, v, causal=True, scale=scale,
                          block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_backward_matches_reference_vjp(causal):
    from mxnet_tpu.kernels.flash_attention import (_pallas_backward,
                                                   _pallas_forward)
    q, k, v = _qkv(B=2, T=256, H=4, K=2, d=16, seed=7)
    scale = 1.0 / np.sqrt(q.shape[-1])
    g = jnp.asarray(np.random.RandomState(8)
                    .randn(*q.shape).astype(np.float32) * 0.2)

    ref, vjp = jax.vjp(lambda q_, k_, v_: reference_attention(
        q_, k_, v_, causal=causal, scale=scale), q, k, v)
    dq_ref, dk_ref, dv_ref = vjp(g)

    out, lse = _pallas_forward(q, k, v, causal=causal, scale=scale,
                               block_q=64, block_k=64, interpret=True,
                               return_lse=True)
    delta = jnp.sum(g * out, axis=-1).transpose(0, 2, 1)
    dq, dk, dv = _pallas_backward(q, k, v, lse, delta, g, causal, scale,
                                  block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_custom_vjp_interpret_end_to_end(monkeypatch):
    # the full dispatch path (flash_attention_raw under jax.grad) with
    # the Pallas kernels forced on via the interpret escape hatch
    from mxnet_tpu.kernels import flash_attention as fa
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    q, k, v = _qkv(B=1, T=128, H=4, K=4, d=8, seed=11)

    def loss_flash(q_, k_, v_):
        return (fa.flash_attention_raw(q_, k_, v_, causal=True) ** 2).sum()

    def loss_ref(q_, k_, v_):
        return (reference_attention(q_, k_, v_, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_pallas_backward_no_quadratic_buffer():
    # compile the backward for a tall T and assert no (T, T) temp is
    # allocated: peak temp memory must stay well under T*T*4 bytes
    from mxnet_tpu.kernels.flash_attention import _pallas_backward
    T = 2048
    q, k, v = _qkv(B=1, T=T, H=1, K=1, d=16, seed=13)
    scale = 0.25
    g = q
    lse = jnp.zeros((1, 1, T), jnp.float32)
    delta = jnp.zeros((1, 1, T), jnp.float32)

    fn = jax.jit(lambda *a: _pallas_backward(*a, True, scale,
                                             block_q=256, block_k=256,
                                             interpret=True))
    compiled = fn.lower(q, k, v, lse, delta, g).compile()
    mem = compiled.memory_analysis()
    if mem is None:
        pytest.skip("memory analysis unavailable on this backend")
    quadratic = T * T * 4
    assert mem.temp_size_in_bytes < quadratic // 4, \
        (mem.temp_size_in_bytes, quadratic)


def test_block_size_not_dividing_T(monkeypatch):
    # regression: T=384 is a multiple of 128 (passes the dispatch gate)
    # but not of the default 256 block — block picking must fall back
    # to a divisor instead of leaving tail rows unwritten (NaNs)
    from mxnet_tpu.kernels import flash_attention as fa
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    q, k, v = _qkv(B=1, T=384, H=2, K=2, d=8, seed=17)
    out = fa.flash_attention_raw(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    g = jax.grad(lambda q_: (fa.flash_attention_raw(
        q_, k, v, causal=True) ** 2).sum())(q)
    assert np.isfinite(np.asarray(g)).all()


def test_uneven_block_sweep():
    # T not a multiple of the default 256 blocks: smaller blocks chosen
    q, k, v = _qkv(B=1, T=128, H=2, K=2, d=8, seed=5)
    scale = 1.0 / np.sqrt(8)
    ref = reference_attention(q, k, v, causal=True, scale=scale)
    out = _pallas_forward(q, k, v, causal=True, scale=scale,
                          block_q=32, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_lengths_masking_matches_reference(causal):
    # BERT-style key padding: positions >= lengths[b] contribute nothing
    q, k, v = _qkv(B=3, T=256, seed=7)
    lengths = jnp.asarray([256, 100, 1], jnp.int32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = reference_attention(q, k, v, causal=causal, scale=scale,
                              lengths=lengths)
    out = _pallas_forward(q, k, v, causal=causal, scale=scale,
                          block_q=128, block_k=128, interpret=True,
                          lengths=lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    # padded-batch invariance: values beyond lengths must not leak
    k2 = k.at[1, 100:].set(99.0)
    v2 = v.at[1, 100:].set(-99.0)
    out2 = _pallas_forward(q, k2, v2, causal=causal, scale=scale,
                           block_q=128, block_k=128, interpret=True,
                           lengths=lengths)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                               rtol=1e-5, atol=1e-6)


def test_lengths_backward_matches_reference_vjp(monkeypatch):
    from mxnet_tpu.kernels.flash_attention import flash_attention_raw
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    q, k, v = _qkv(B=2, T=128, seed=8)
    lengths = jnp.asarray([128, 57], jnp.int32)

    def loss_kernel(q_, k_, v_):
        return (flash_attention_raw(q_, k_, v_, causal=False,
                                    lengths=lengths)
                .astype(jnp.float32) ** 2).sum()

    def loss_ref(q_, k_, v_):
        return (reference_attention(q_, k_, v_, causal=False,
                                    lengths=lengths)
                .astype(jnp.float32) ** 2).sum()

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


# the shapes the packed tiling has to serve: (H, K, d, causal, window,
# ragged lengths) -> heads a grid step
_PACKED = {
    "bert_12x64_two_heads_a_step": (12, 12, 64, False, None, True),
    "gqa_rep4_128_causal": (8, 2, 128, True, None, False),
    "gqa_rep4_128_causal_ragged": (8, 2, 128, True, None, True),
    "gqa_rep4_128_window": (8, 2, 128, True, 48, True),
    "odd_3x64_whole_width": (3, 3, 64, False, None, True),
}


@pytest.mark.parametrize("case", sorted(_PACKED))
def test_packed_heads_match_reference(case, monkeypatch):
    """Forward and custom_vjp gradients of the lane-dense kernels
    against reference_attention and its jax.vjp, through the dispatch
    seam (the windowed case too, since the dkv kernel takes one)."""
    from mxnet_tpu.kernels import flash_attention as fa
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    H, K, d, causal, window, ragged = _PACKED[case]
    G, Gk = fa._heads_per_step(H, K, d)
    assert (G * d) % 128 == 0 or G == H
    if case.startswith("bert"):
        assert (G, Gk) == (2, 2)
    q, k, v = _qkv(B=2, T=128, H=H, K=K, d=d, seed=21)
    lengths = jnp.asarray([128, 45], jnp.int32) if ragged else None
    kw = dict(causal=causal, lengths=lengths, window=window)
    before = fa._fallback.count
    out = fa.flash_attention_raw(q, k, v, **kw)
    ref = reference_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    g = jnp.asarray(np.random.RandomState(22)
                    .randn(*q.shape).astype(np.float32) * 0.2)
    gk = jax.grad(lambda *a: (fa.flash_attention_raw(*a, **kw)
                              * g).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (reference_attention(*a, **kw)
                              * g).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)
    assert fa._fallback.count == before, "the kernels fell back"


# the windowed backward: (window, ragged lengths) at T = 256 in blocks
# of 64 — a window inside one block, of exactly a block, of two and a
# half, and one no query reaches
_WINDOWED_BWD = [(17, False), (64, False), (64, True), (160, True),
                 (1000, False)]


@pytest.mark.parametrize("window,ragged", _WINDOWED_BWD)
def test_windowed_backward_matches_reference_vjp(window, ragged):
    """dQ, dK and dV of the dkv kernel with a sliding window against
    the VJP of reference_attention, 4 query heads on 2 kv heads; with
    blocks of 64 the sweep of a key block ends where its window does."""
    from mxnet_tpu.kernels.flash_attention import _pallas_backward
    q, k, v = _qkv(B=2, T=256, H=4, K=2, d=16, seed=31)
    scale = 1.0 / np.sqrt(q.shape[-1])
    lengths = jnp.asarray([256, 101], jnp.int32) if ragged else None
    g = jnp.asarray(np.random.RandomState(32)
                    .randn(*q.shape).astype(np.float32) * 0.2)
    kw = dict(causal=True, scale=scale, lengths=lengths, window=window)
    _, vjp = jax.vjp(lambda *a: reference_attention(*a, **kw), q, k, v)
    want = vjp(g)
    out, lse = _pallas_forward(q, k, v, block_q=64, block_k=64,
                               interpret=True, return_lse=True, **kw)
    delta = jnp.sum(g * out, axis=-1).transpose(0, 2, 1)
    got = _pallas_backward(q, k, v, lse, delta, g, block_q=64,
                           block_k=64, interpret=True, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    if window < 256:
        full = jax.vjp(lambda *a: reference_attention(
            *a, causal=True, scale=scale, lengths=lengths), q, k, v)[1](g)
        assert float(jnp.abs(full[0] - want[0]).max()) > 1e-3


def test_row_with_every_key_masked(monkeypatch):
    """lengths[b] == 0: every key of the row is masked. The output is
    0, lse is +inf, and nothing but zeros flows back — no NaN from
    exp(-inf - -inf)."""
    from mxnet_tpu.kernels import flash_attention as fa
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    q, k, v = _qkv(B=2, T=128, H=4, K=4, d=64, seed=23)
    lengths = jnp.asarray([0, 77], jnp.int32)
    out, lse = fa._pallas_forward(q, k, v, causal=False, scale=0.125,
                                  interpret=True, return_lse=True,
                                  lengths=lengths)
    assert lse.shape == (2, 4, 128)
    assert np.all(np.asarray(out[0]) == 0)
    assert np.all(np.isposinf(np.asarray(lse[0])))
    assert np.all(np.isfinite(np.asarray(lse[1])))
    grads = jax.grad(lambda *a: (fa.flash_attention_raw(
        *a, causal=False, lengths=lengths) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    live = jax.grad(lambda *a: (reference_attention(
        *a, causal=False, lengths=lengths[1:]) ** 2).sum(),
        argnums=(0, 1, 2))(q[1:], k[1:], v[1:])
    for a, b in zip(grads, live):
        assert np.all(np.asarray(a[0]) == 0)
        np.testing.assert_allclose(np.asarray(a[1:]), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_calls_read_the_models_layout(monkeypatch):
    """The three calls read q / k / v / do and write o / dq / dk / dv
    on (B, T, H * d): no transpose of an activation surrounds them, and
    no operand of a pallas_call has a minor dimension of 1 (the row
    statistics travel with T along the lanes)."""
    from mxnet_tpu.kernels import flash_attention as fa
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    q, k, v = _qkv(B=2, T=128, H=12, K=12, d=64, seed=25)
    lengths = jnp.asarray([128, 45], jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: (fa.flash_attention_raw(
            *a, causal=False, lengths=lengths) ** 2).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    calls = []
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "transpose":
            assert eqn.invars[0].aval.size < q.size, eqn
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn)
            for var in list(eqn.invars) + list(eqn.outvars):
                shape = var.aval.shape
                assert len(shape) < 2 or shape[-1] > 1, (eqn, shape)
                if len(shape) == 3:
                    assert shape == (2, 128, 12 * 64), shape
    names = sorted(e.params["name"] for e in calls)
    assert names == ["flash_attention_dkv", "flash_attention_fwd"], names


def test_bert_valid_length_flash_vs_mask(monkeypatch):
    """BERT's key-padding now rides the kernel's lengths support; the
    kernel-on and fallback paths must agree, and padding tokens must
    not influence the valid positions."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.bert import BERTModel

    mx.random.seed(0)
    net = BERTModel(vocab_size=64, units=32, hidden_size=64,
                    num_layers=1, num_heads=4, max_length=128,
                    dropout=0.0)
    net.initialize()
    rs = np.random.RandomState(9)
    ids = mx.nd.array(rs.randint(0, 64, (2, 128)), dtype="int32")
    vl = mx.nd.array(np.array([128, 40]), dtype="int32")
    seq_ref, pooled_ref = net(ids, valid_length=vl)
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    seq_k, pooled_k = net(ids, valid_length=vl)
    np.testing.assert_allclose(seq_k.asnumpy(), seq_ref.asnumpy(),
                               rtol=3e-4, atol=3e-4)
    # changing PAD tokens must not change valid positions' output
    ids2 = ids.asnumpy().copy()
    ids2[1, 40:] = 1
    seq_k2, _ = net(mx.nd.array(ids2, dtype="int32"), valid_length=vl)
    np.testing.assert_allclose(seq_k2.asnumpy()[1, :40],
                               seq_k.asnumpy()[1, :40],
                               rtol=3e-4, atol=3e-4)


def test_bert_valid_length_keeps_jit_cache():
    """lengths must ride POSITIONALLY through the layers: kwargs bypass
    the HybridBlock compiled-call path, silently de-hybridizing BERT."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.bert import BERTModel

    mx.random.seed(1)
    net = BERTModel(vocab_size=32, units=16, hidden_size=32,
                    num_layers=1, num_heads=2, max_length=32,
                    dropout=0.0)
    net.initialize()
    ids = mx.nd.array(np.random.RandomState(2).randint(0, 32, (2, 32)),
                      dtype="int32")
    vl = mx.nd.array(np.array([32, 9]), dtype="int32")
    eager, _ = net(ids, valid_length=vl)
    for layer in net.layers:
        layer.hybridize()
    hyb, _ = net(ids, valid_length=vl)
    np.testing.assert_allclose(hyb.asnumpy(), eager.asnumpy(),
                               rtol=2e-4, atol=2e-4)
    assert net.layers[0]._jit_cache, \
        "valid_length path must not bypass the compiled-call cache"


def test_cross_attention_lengths_fallback_masks():
    """T != S with lengths: the padding mask must be derived, never
    silently dropped."""
    from mxnet_tpu.models.transformer import MultiHeadAttention
    import mxnet_tpu as mx

    mx.random.seed(2)
    attn = MultiHeadAttention(16, 2, dropout=0.0)
    attn.initialize()
    rs = np.random.RandomState(3)
    q = mx.nd.array(rs.rand(2, 5, 16).astype(np.float32))
    mem = mx.nd.array(rs.rand(2, 8, 16).astype(np.float32))
    lens = mx.nd.array(np.array([8, 3]), dtype="int32")
    out = attn(q, mem, mem, None, lens)
    # batch row 1 must ignore memory positions >= 3
    mem2 = mem.asnumpy().copy()
    mem2[1, 3:] = 77.0
    out2 = attn(q, mx.nd.array(mem2), mx.nd.array(mem2), None, lens)
    np.testing.assert_allclose(out2.asnumpy()[1], out.asnumpy()[1],
                               rtol=1e-5, atol=1e-5)
