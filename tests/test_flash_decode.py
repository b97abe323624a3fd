"""Pallas flash-decode (single-token KV-cache attention) vs the exact
reference, incl. GQA and valid-length masking. Kernels run under the
Pallas interpreter on CPU — the same code the TPU executes (reference
analogue: the fork's fused decoder-attention inference kernels)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.kernels.flash_decode import (_flash_decode_pallas,
                                            flash_decode,
                                            reference_decode_attention)


def _data(B=2, S=256, H=8, K=2, d=16, seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, H, d).astype(np.float32))
    # cache-native (B, K, S, d) layout
    kc = jnp.asarray(rs.randn(B, K, S, d).astype(np.float32))
    vc = jnp.asarray(rs.randn(B, K, S, d).astype(np.float32))
    vl = jnp.asarray(rs.randint(1, S + 1, B).astype(np.int32))
    return q, kc, vc, vl


def test_decode_matches_reference_gqa():
    q, kc, vc, vl = _data()
    out = _flash_decode_pallas(q, kc, vc, vl, 0.25, interpret=True)
    ref = reference_decode_attention(q, kc, vc, vl, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_matches_reference_mha():
    q, kc, vc, vl = _data(H=4, K=4, seed=1)
    out = _flash_decode_pallas(q, kc, vc, vl, 0.25, interpret=True)
    ref = reference_decode_attention(q, kc, vc, vl, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("vl_val", [1, 128, 255, 256])
def test_decode_valid_len_edges(vl_val):
    q, kc, vc, _ = _data(B=1, seed=2)
    vl = jnp.asarray([vl_val], jnp.int32)
    out = _flash_decode_pallas(q, kc, vc, vl, 0.25, interpret=True)
    ref = reference_decode_attention(q, kc, vc, vl, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_bf16():
    q, kc, vc, vl = _data(seed=3)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, kc, vc))
    out = _flash_decode_pallas(qb, kb, vb, vl, 0.25, interpret=True)
    ref = reference_decode_attention(qb, kb, vb, vl, 0.25)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_dispatch_uses_kernel_when_forced(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    q, kc, vc, vl = _data(seed=4)
    out = flash_decode(q, kc, vc, vl)
    ref = reference_decode_attention(q, kc, vc, vl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_dispatch_falls_back_on_odd_cache_len():
    # S % 128 != 0 gates the kernel off; the no-repeat jnp path runs
    q, kc, vc, vl = _data(S=200, seed=5)
    out = flash_decode(q, kc, vc, vl)
    ref = reference_decode_attention(q, kc, vc, vl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6)


def test_vmem_gate_rejects_oversized_cache(monkeypatch):
    # a cache whose per-head K+V exceeds the VMEM budget must gate the
    # kernel OFF at trace time (a Mosaic compile failure inside the
    # caller's jit could not be caught by the fallback try/except)
    from mxnet_tpu.kernels import flash_decode as fd
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    small = jnp.zeros((1, 1, 256, 16), jnp.float32)
    assert fd._pallas_mode(small) == "interpret"

    class _Fake:
        shape = (1, 1, 16384, 128)
        dtype = np.dtype(np.float32)

    assert fd._pallas_mode(_Fake()) is None


@pytest.mark.slow
def test_llama_decode_step_parity(monkeypatch):
    """The llama_infer decode step must produce identical logits with
    the kernel forced on vs the jnp path."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from mxnet_tpu.models.llama_infer import build_decoder

    mx.random.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_layers=2, num_heads=4,
                      num_kv_heads=2, max_seq_len=128, dtype="float32")
    net = LlamaForCausalLM(cfg)
    net.initialize()
    params, prefill, step = build_decoder(net, max_len=128)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, 8)),
                      jnp.int32)
    vl = jnp.asarray([8, 5], jnp.int32)
    cache, _ = prefill(params, ids, vl)
    tok = jnp.asarray([3, 7], jnp.int32)
    _, logits_ref = step(params, cache, vl, tok)

    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    params2, prefill2, step2 = build_decoder(net, max_len=128)
    cache2, _ = prefill2(params2, ids, vl)
    _, logits_kernel = step2(params2, cache2, vl, tok)
    np.testing.assert_allclose(np.asarray(logits_kernel),
                               np.asarray(logits_ref),
                               rtol=2e-4, atol=2e-4)


# -- int8-quantized KV cache -------------------------------------------------

def test_quantize_kv_roundtrip():
    from mxnet_tpu.kernels.flash_decode import (dequantize_kv,
                                                quantize_kv)
    _, kc, vc, _ = _data(seed=3)
    k8, ks, v8, vs = quantize_kv(kc, vc)
    assert k8.dtype == jnp.int8 and ks.shape == kc.shape[:3] + (1,)
    back = dequantize_kv(k8, ks, jnp.float32)
    # per-token abs-max int8: max error <= scale/2 ~ amax/254
    err = np.abs(np.asarray(back) - np.asarray(kc))
    amax = np.abs(np.asarray(kc)).max(axis=-1, keepdims=True)
    assert (err <= amax / 254 + 1e-6).all()


def test_quantized_decode_matches_fp32_reference():
    from mxnet_tpu.kernels.flash_decode import (_flash_decode_pallas_q8,
                                                quantize_kv,
                                                reference_decode_attention)
    q, kc, vc, vl = _data(seed=4)
    k8, ks, v8, vs = quantize_kv(kc, vc)
    out8 = _flash_decode_pallas_q8(q, k8, ks, v8, vs, vl,
                                   1.0 / np.sqrt(q.shape[-1]),
                                   interpret=True)
    ref = reference_decode_attention(q, kc, vc, vl)
    # int8 cache: ~1% relative output error is the expected regime
    np.testing.assert_allclose(np.asarray(out8), np.asarray(ref),
                               rtol=0.05, atol=0.03)


def test_quantized_decode_jnp_fallback_matches_kernel():
    from mxnet_tpu.kernels.flash_decode import (
        dequantize_kv, flash_decode_quantized, quantize_kv,
        reference_decode_attention)
    q, kc, vc, vl = _data(seed=5)
    k8, ks, v8, vs = quantize_kv(kc, vc)
    # the twin by its name: dequantized exact softmax
    a = reference_decode_attention(
        q, dequantize_kv(k8, ks, jnp.float32),
        dequantize_kv(v8, vs, jnp.float32), vl).astype(q.dtype)
    # interpreter kernel path
    import os
    os.environ["MXNET_TPU_FLASH_INTERPRET"] = "1"
    try:
        b = flash_decode_quantized(q, k8, ks, v8, vs, vl)
    finally:
        del os.environ["MXNET_TPU_FLASH_INTERPRET"]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-4)


# -- in-kernel paged decode (scalar-prefetch block tables) -------------------

def _paged_data(B=2, S=128, H=8, K=2, d=16, bs=8, seed=7, vl=None):
    """Contiguous cache + the equivalent paged pool, stressing every
    table property the kernel must honor: OUT-OF-ORDER physical
    placement, garbage contents in never-written blocks (including
    scratch block 0), and table entries past valid_len left pointing
    at scratch — exactly what the serving allocator produces."""
    rs = np.random.RandomState(seed)
    nb = S // bs
    q = rs.randn(B, H, d).astype(np.float32)
    kc = rs.randn(B, K, S, d).astype(np.float32)
    vc = rs.randn(B, K, S, d).astype(np.float32)
    vl = (rs.randint(1, S + 1, B) if vl is None
          else np.asarray(vl)).astype(np.int32)
    N = B * nb + 1
    kp = rs.randn(N, K, bs, d).astype(np.float32)  # garbage everywhere
    vp = rs.randn(N, K, bs, d).astype(np.float32)
    perm = rs.permutation(np.arange(1, N))
    bt = np.zeros((B, nb), np.int32)
    idx = 0
    for b in range(B):
        for i in range(-(-int(vl[b]) // bs)):
            blk = int(perm[idx]); idx += 1
            bt[b, i] = blk
            kp[blk] = kc[b, :, i * bs:(i + 1) * bs]
            vp[blk] = vc[b, :, i * bs:(i + 1) * bs]
    return tuple(jnp.asarray(x) for x in (q, kc, vc, kp, vp, bt, vl))


def test_paged_inkernel_matches_reference_fp32():
    from mxnet_tpu.kernels.flash_decode import _flash_decode_paged_pallas
    q, kc, vc, kp, vp, bt, vl = _paged_data()
    out = _flash_decode_paged_pallas(q, kp, vp, bt, vl, 0.25,
                                     interpret=True)
    ref = reference_decode_attention(q, kc, vc, vl, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_inkernel_bf16():
    from mxnet_tpu.kernels.flash_decode import _flash_decode_paged_pallas
    q, kc, vc, kp, vp, bt, vl = _paged_data(seed=8)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    out = _flash_decode_paged_pallas(qb, kb, vb, bt, vl, 0.25,
                                     interpret=True)
    ref = reference_decode_attention(q.astype(jnp.bfloat16),
                                     kc.astype(jnp.bfloat16),
                                     vc.astype(jnp.bfloat16), vl, 0.25)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("vl_val", [1, 8, 77, 128])
def test_paged_inkernel_valid_len_edges(vl_val):
    # vl=1 leaves all but one table entry at scratch block 0; vl=8 is
    # an exact block boundary; 77 a ragged tail; 128 every block live
    from mxnet_tpu.kernels.flash_decode import _flash_decode_paged_pallas
    q, kc, vc, kp, vp, bt, vl = _paged_data(B=1, seed=9, vl=[vl_val])
    out = _flash_decode_paged_pallas(q, kp, vp, bt, vl, 0.25,
                                     interpret=True)
    ref = reference_decode_attention(q, kc, vc, vl, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.fixture
def pages_per_step():
    """Hold the sweep to P pages a step through the tuning table's
    VMEM budget (the one number the step chooser reads), so a small
    pool takes many steps; the table is restored afterwards."""
    from mxnet_tpu.kernels import flash_decode as fd, tuning

    def hold(P, pool):
        for budget in range(0, 64 << 20, 256):
            tuning.set_runtime("flash_decode_paged",
                               "vmem_budget_bytes", budget)
            if fd._paged_sweep_pages(pool.shape,
                                     pool.dtype.itemsize) == P:
                return
        raise AssertionError(f"no budget gives {P} pages a step")

    yield hold
    tuning.clear_runtime()


# what the many-pages-a-step schedule can get wrong, each against the
# reference on the GATHERED view: (P, data kwargs, valid lengths,
# rows whose table is all sink). S=128 at bs=8 is nb=16 pages.
_SWEEP_CASES = {
    # nb=16 is not a multiple of P=3: five full steps and a ragged one
    "nb_not_multiple_vl_1": (3, {}, [1], ()),
    "page_edge": (3, {}, [8], ()),
    "page_edge_plus_1": (3, {}, [9], ()),
    "step_edge": (3, {}, [24], ()),
    "step_edge_plus_1": (3, {}, [25], ()),
    "last_full_step_edge": (3, {}, [120], ()),
    "whole_table": (3, {}, [128], ()),
    "one_page_a_step": (1, {}, [77], ()),
    "table_in_one_step": (16, {}, [77], ()),
    # sequences of 1, 2, 3 and 6 steps take turns on the two halves
    "mixed_lengths": (3, {"B": 5}, [128, 1, 24, 61, 47], ()),
    "inactive_rows": (3, {"B": 4}, [90, 1, 1, 33], (1, 2)),
    "inactive_first_and_last": (2, {"B": 3}, [1, 100, 1], (0, 2)),
    "gqa_32_8_128_bs16": (3, {"B": 3, "S": 160, "H": 32, "K": 8,
                              "d": 128, "bs": 16}, [160, 49, 97], ()),
}


@pytest.mark.parametrize("case", sorted(_SWEEP_CASES))
def test_paged_sweep_schedule(case, pages_per_step):
    from mxnet_tpu.kernels.flash_decode import (
        _flash_decode_paged_pallas, gather_kv_pages)
    P, kw, vls, sink_rows = _SWEEP_CASES[case]
    q, _, _, kp, vp, bt, vl = _paged_data(seed=23, vl=vls,
                                          **{"B": 1, **kw})
    for row in sink_rows:      # an idle slot: length 1, table all sink
        bt = bt.at[row].set(0)
    pages_per_step(P, kp)
    out = _flash_decode_paged_pallas(q, kp, vp, bt, vl, 0.25,
                                     interpret=True)
    ref = reference_decode_attention(q, gather_kv_pages(kp, bt),
                                     gather_kv_pages(vp, bt), vl, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_sweep_bf16_splits_probabilities_exactly(pages_per_step):
    # bf16 q/k/v go to the MXU as stored and the fp32 probabilities as
    # three bf16 terms: the result must sit as close to the fp32
    # reference as the bf16 OUTPUT allows, not a bf16 product's 3e-2
    from mxnet_tpu.kernels.flash_decode import (
        _flash_decode_paged_pallas, gather_kv_pages)
    q, _, _, kp, vp, bt, vl = _paged_data(B=3, seed=24,
                                          vl=[128, 50, 9])
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    pages_per_step(3, kb)
    out = _flash_decode_paged_pallas(qb, kb, vb, bt, vl, 0.25,
                                     interpret=True)
    f32 = jnp.float32
    ref = reference_decode_attention(
        qb.astype(f32), gather_kv_pages(kb, bt).astype(f32),
        gather_kv_pages(vb, bt).astype(f32), vl, 0.25)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=8e-3, atol=8e-3)


# the schedule of PR 46 (starts dealt between the heads' products, each
# under its predicate; waits by the bits of the count; ONE step body,
# plain or windowed), against the reference on the GATHERED view:
# (P, data kwargs, valid lengths, window). S=128 at bs=8 is nb=16 pages,
# a step of P=3 pages is 24 tokens.
_DEALT_CASES = {
    "all_rows_empty": (3, {}, [0, 0, 0, 0, 0], None),
    "len_0_1_step_step_plus_1": (3, {}, [0, 1, 24, 25, 48], None),
    "ragged_last_page": (3, {}, [21, 77, 128, 3, 127], None),
    # the halves are handed over rows that take one step and fetch
    # nothing
    "empty_first_middle_last": (3, {}, [0, 61, 0, 47, 0], None),
    "empty_between_long_rows": (3, {}, [128, 0, 0, 128, 1], None),
    # nb = 2 < P = 4: a start past the table's end reads a clamped index
    "table_shorter_than_a_step": (4, {"S": 16}, [16, 0, 9, 1, 16], None),
    "one_step_holds_the_table": (16, {}, [128, 0, 77, 1, 24], None),
    # an idle slot is a row of ONE token: a successor that fits the
    # first share skips the branch round the lead's other shares
    "idle_rows_of_one_token": (8, {}, [1, 128, 1, 1, 90], None),
    "idle_rows_of_one_token_window": (8, {}, [1, 128, 1, 1, 90], 30),
    # first(row) > 0; 21 and 43 end inside a page, 40 on a page's edge
    "window_ends_inside_a_page": (3, {}, [100, 128, 5, 61, 0], 21),
    "window_on_a_page_edge": (3, {}, [100, 128, 5, 61, 0], 40),
    "window_over_two_steps": (3, {}, [128, 0, 44, 90, 43], 43),
    "window_short_table": (4, {"S": 16}, [16, 0, 9, 1, 13], 5),
    "rep_20_two_bf16_tiles": (3, {"H": 20, "K": 1}, [0, 128, 25, 1, 90],
                              None),
    "rep_20_window": (3, {"H": 20, "K": 1}, [0, 128, 25, 1, 90], 30),
    # one kv head: two shares of 12 pages a step, each in a loop
    "large_shares_loop": (24, {"S": 256, "H": 4, "K": 1},
                          [256, 0, 77, 1, 200], None),
    "large_shares_loop_window": (24, {"S": 256, "H": 4, "K": 1},
                                 [256, 0, 77, 1, 200], 50),
}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 8e-3)])
@pytest.mark.parametrize("case", sorted(_DEALT_CASES))
def test_paged_sweep_dealt_starts_and_bit_waits(case, dtype, tol):
    # bf16 at the tolerance of the exact three-term value product (the
    # test above), against fp32 arithmetic on the bf16 operands
    from mxnet_tpu.kernels.flash_decode import (_paged_sweep,
                                                gather_kv_pages)
    P, kw, vls, window = _DEALT_CASES[case]
    q, _, _, kp, vp, bt, vl = _paged_data(seed=29, vl=vls,
                                          **{"B": 5, **kw})
    bs = kp.shape[2]
    cut = np.array(bt)
    if window is not None:      # as the cache leaves a sliding layer's
        for b, n in enumerate(vls):
            cut[b, :max(n - window, 0) // bs] = 0
    dt = jnp.dtype(dtype)
    q, kp, vp = (x.astype(dt) for x in (q, kp, vp))
    out = _paged_sweep(q, kp, vp, jnp.asarray(cut), vl, scale=0.25,
                       pages=P, interpret=True, window=window)
    assert out.dtype == dt
    out = np.asarray(out, np.float32)
    f32 = jnp.float32
    ref = np.asarray(reference_decode_attention(
        q.astype(f32), gather_kv_pages(kp, bt).astype(f32),
        gather_kv_pages(vp, bt).astype(f32), vl, 0.25, window))
    held = np.asarray(vls) > 0
    np.testing.assert_allclose(out[held], ref[held], rtol=tol, atol=tol)
    assert (out[~held] == 0).all()       # a finite 0, not 0 / 0


class _Pool:
    """A pool's static face: all the step chooser and the gate read."""

    def __init__(self, shape, dtype):
        self.shape, self.dtype = shape, np.dtype(dtype)


@pytest.mark.parametrize("B,nb,N", [(32, 160, 5121), (20, 528, 5633)])
def test_paged_step_chooser_at_the_serving_cells(B, nb, N, monkeypatch):
    # pure Python: mistral_7b.chat / .reason's table shapes, bf16
    from mxnet_tpu.kernels import flash_decode as fd, tuning
    K, bs, d = 8, 16, 128
    pool = _Pool((N, K, bs, d), jnp.bfloat16)
    P = fd._paged_sweep_pages(pool.shape, 2, nb)
    page = K * bs * d * 2
    assert 1 <= P <= nb
    assert 2 * P * page >= 256 << 10            # k + v a step
    assert (P * bs) % 128 == 0                  # whole lanes of scores
    budget = tuning.get("flash_decode_paged", "vmem_budget_bytes")
    # two steps of k and of v, one head widened, q / o / m / l / acc
    assert 4 * P * page + 2 * P * bs * d * 4 \
        + 4 * K * 16 * d * 2 + 3 * K * 16 * d * 4 <= budget
    assert fd._paged_sweep_pages(pool.shape, 2, 5) == 5   # short table
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    assert fd.paged_kernel_mode(pool) == "interpret"      # not gather
    assert fd.paged_kernel_mode(_Pool((N, K, 12, d),
                                      jnp.bfloat16)) is None


def test_paged_inkernel_quantized_matches_gather():
    # quantize the POOL (per-token scales, same axis the serving cache
    # uses) and demand the in-kernel int8 path agree with the gathered
    # dequantize-exact fallback — the parity the dispatch gate promises
    from mxnet_tpu.kernels.flash_decode import (
        _flash_decode_paged_pallas_q8, dequantize_kv, gather_kv_pages,
        quantize_kv, reference_decode_attention)
    q, kc, vc, kp, vp, bt, vl = _paged_data(seed=10)
    k8, ks, v8, vs = quantize_kv(kp, vp)
    out = _flash_decode_paged_pallas_q8(q, k8, ks, v8, vs, bt, vl,
                                        0.25, interpret=True)
    ref = reference_decode_attention(
        q, *(dequantize_kv(gather_kv_pages(p8, bt),
                           gather_kv_pages(ps, bt), jnp.float32)
             for p8, ps in ((k8, ks), (v8, vs))), vl, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_paged_dispatch_interpret_matches_gather(monkeypatch):
    from mxnet_tpu.kernels import flash_decode as fd
    q, kc, vc, kp, vp, bt, vl = _paged_data(seed=11)
    before = fd._paged_fallback.count
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    assert fd.paged_kernel_mode(kp) == "interpret"
    a = fd.flash_decode_paged(q, kp, vp, bt, vl)
    b = fd.reference_decode_attention(
        q, fd.gather_kv_pages(kp, bt), fd.gather_kv_pages(vp, bt), vl)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
    assert fd._paged_fallback.count == before  # kernel path, no note()


def test_paged_gate_and_fallback_registration(monkeypatch):
    from mxnet_tpu.kernels import dispatch
    from mxnet_tpu.kernels import flash_decode as fd
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    ok = jnp.zeros((5, 2, 8, 16), jnp.float32)
    assert fd.paged_kernel_mode(ok) == "interpret"
    # Mosaic sublane constraint: block_size not a multiple of 8
    odd = jnp.zeros((5, 2, 4, 16), jnp.float32)
    assert fd.paged_kernel_mode(odd) is None

    class _Fake:  # per-cell working set far beyond the VMEM budget
        shape = (8, 1, 512, 4096)
        dtype = np.dtype(np.float32)

    assert fd.paged_kernel_mode(_Fake()) is None
    # gather fallbacks are telemetry-visible under their own label
    assert "flash-decode-paged" in dispatch.fallback_counts()
    assert fd._paged_fallback.kernel_name == "flash-decode-paged"


def test_paged_gather_bytes_accounting():
    from mxnet_tpu.kernels.flash_decode import paged_gather_bytes
    # (N, K, bs, d) pool, (B, nb) tables: k+v contiguous views
    assert paged_gather_bytes((33, 4, 16, 32), (4, 8), 4) \
        == 2 * 4 * 4 * 8 * 16 * 32 * 4
    # int8 adds the two fp32 per-token scale views
    assert paged_gather_bytes((33, 4, 16, 32), (4, 8), 1,
                              quantized=True) \
        == 2 * 4 * 4 * 8 * 16 * 32 * 1 + 2 * 4 * 4 * 8 * 16 * 4


# -- windowed paged attention (chunked prefill / speculative verify) --------

def _window_data(B=2, W=4, S=128, H=8, K=2, d=16, bs=8, seed=13,
                 vls=None):
    """Paged pool filled to each sequence's max window position, plus
    a (B, W) per-row valid-length matrix: row j of the window attends
    its own prefix, exactly the contract chunked prefill and verify
    hand the kernel."""
    rs = np.random.RandomState(seed)
    if vls is None:
        base = rs.randint(1, S - W, B)
        vls = base[:, None] + np.arange(W)[None, :]  # consecutive rows
    vls = np.asarray(vls, np.int32).reshape(B, W)
    q, kc, vc, kp, vp, bt, _ = _paged_data(
        B=B, S=S, H=H, K=K, d=d, bs=bs, seed=seed,
        vl=vls.max(axis=1))
    qw = jnp.asarray(rs.randn(B, W, H, d).astype(np.float32))
    return qw, kc, vc, kp, vp, bt, jnp.asarray(vls)


def test_window_reference_matches_single_position_stack():
    # the window reference must be W independent single-position
    # references stacked — this is the identity speculative greedy
    # parity rests on
    from mxnet_tpu.kernels.flash_decode import \
        reference_paged_window_attention
    qw, kc, vc, _, _, _, vls = _window_data(seed=21)
    out = reference_paged_window_attention(qw, kc, vc, vls, 0.25)
    for j in range(qw.shape[1]):
        ref = reference_decode_attention(qw[:, j], kc, vc, vls[:, j],
                                         0.25)
        np.testing.assert_allclose(np.asarray(out[:, j]),
                                   np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_paged_window_inkernel_matches_reference():
    from mxnet_tpu.kernels.flash_decode import \
        _flash_decode_paged_window_pallas
    qw, kc, vc, kp, vp, bt, vls = _window_data(seed=14)
    out = _flash_decode_paged_window_pallas(qw, kp, vp, bt, vls, 0.25,
                                            interpret=True)
    from mxnet_tpu.kernels.flash_decode import \
        reference_paged_window_attention
    ref = reference_paged_window_attention(qw, kc, vc, vls, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("vls", [[[1, 2, 3, 4]], [[8, 9, 10, 11]],
                                 [[125, 126, 127, 128]],
                                 [[1, 1, 1, 1]]])
def test_paged_window_valid_len_edges(vls):
    # window crossing a block boundary, hugging the end of the pool,
    # and degenerate all-rows-see-one-token (verify with every draft
    # at position 0 masked)
    from mxnet_tpu.kernels.flash_decode import (
        _flash_decode_paged_window_pallas,
        reference_paged_window_attention)
    qw, kc, vc, kp, vp, bt, v = _window_data(B=1, seed=15, vls=vls)
    out = _flash_decode_paged_window_pallas(qw, kp, vp, bt, v, 0.25,
                                            interpret=True)
    ref = reference_paged_window_attention(qw, kc, vc, v, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_window_dispatch_and_gate(monkeypatch):
    from mxnet_tpu.kernels import flash_decode as fd
    qw, kc, vc, kp, vp, bt, vls = _window_data(seed=16)
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    assert fd.paged_window_mode(kp, 4) == "interpret"
    # int8 pools always take the gathered dequant reference
    assert fd.paged_window_mode(kp, 4, quantized=True) is None
    # Mosaic sublane constraint carries over from the decode gate
    odd = jnp.zeros((5, 2, 4, 16), jnp.float32)
    assert fd.paged_window_mode(odd, 4) is None
    before = fd._paged_fallback.count
    a = fd.flash_decode_paged_window(qw, kp, vp, bt, vls)
    b = fd.reference_paged_window_attention(
        qw, fd.gather_kv_pages(kp, bt), fd.gather_kv_pages(vp, bt), vls)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
    assert fd._paged_fallback.count == before


def test_paged_window_quantized_matches_fp32_loosely():
    from mxnet_tpu.kernels.flash_decode import (
        flash_decode_paged_window_quantized, quantize_kv,
        reference_paged_window_attention)
    qw, kc, vc, kp, vp, bt, vls = _window_data(seed=17)
    k8, ks, v8, vs = quantize_kv(kp, vp)
    out = flash_decode_paged_window_quantized(qw, k8, ks, v8, vs, bt,
                                              vls, scale=0.25)
    ref = reference_paged_window_attention(qw, kc, vc, vls, 0.25)
    assert out.dtype == qw.dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0.08, atol=0.08)


# -- 20 query heads on ONE kv head (jamba2_3b): a group that is no
# multiple of 8 sublanes, a page of one kv head -----------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 3e-2)])
@pytest.mark.parametrize("vl", [None, [1, 16, 77]])
def test_paged_sweep_at_a_group_of_twenty_on_one_kv_head(dtype, tol, vl):
    from mxnet_tpu.kernels.flash_decode import _flash_decode_paged_pallas
    q, kc, vc, kp, vp, bt, vl = _paged_data(B=3, H=20, K=1, S=128, d=128,
                                            bs=16, seed=21, vl=vl)
    dt = jnp.dtype(dtype)
    out = _flash_decode_paged_pallas(
        q.astype(dt), kp.astype(dt), vp.astype(dt), bt, vl, 128 ** -0.5,
        interpret=True)
    ref = reference_decode_attention(q.astype(dt), kc.astype(dt),
                                     vc.astype(dt), vl, 128 ** -0.5)
    assert out.shape == (3, 20, 128)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("lengths", [None, [256, 100]])
def test_flash_attention_at_a_group_of_twenty_on_one_kv_head(
        lengths, monkeypatch):
    from mxnet_tpu.kernels.flash_attention import (flash_attention_raw,
                                                   reference_attention)
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    rs = np.random.RandomState(4)
    q = jnp.asarray(rs.randn(2, 256, 20, 128), jnp.float32)
    k = jnp.asarray(rs.randn(2, 256, 1, 128), jnp.float32)
    v = jnp.asarray(rs.randn(2, 256, 1, 128), jnp.float32)
    n = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    out = flash_attention_raw(q, k, v, causal=True, lengths=n)
    ref = reference_attention(q, k, v, causal=True, lengths=n)
    rows = slice(None) if lengths is None else slice(0, 100)
    np.testing.assert_allclose(np.asarray(out)[:, rows],
                               np.asarray(ref)[:, rows],
                               rtol=2e-4, atol=2e-4)


# -- the latent (MLA) sweep: one pool, every row read once, used twice ------

from mxnet_tpu.kernels import flash_decode as fd  # noqa: E402

def _latent_pool(dtype, vl=None, H=8, R=256, bs=8, N=80, seed=0):
    """Ragged lengths by default: an idle slot on the scratch block
    (length 1, table all 0, as every length of 1 here), a sequence
    shorter than one page, one that ends on a page's edge, two ragged
    ones. The table is as wide as the longest needs."""
    rng = np.random.default_rng(seed)
    vl = vl or (1, 5, 17, 48, 33)
    B, nb = len(vl), max(6, -(-max(vl) // bs))
    pool = jnp.asarray(rng.normal(0, 1, (N, 1, bs, R)), dtype)
    q = jnp.asarray(rng.normal(0, 1, (B, H, R)), dtype)
    vl = np.array(vl, np.int32)
    bt = np.zeros((B, nb), np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    for b in range(B):
        for j in range(-(-int(vl[b]) // bs) if vl[b] > 1 else 0):
            bt[b, j] = free.pop()
    return q, pool, jnp.asarray(bt), jnp.asarray(vl)


# (pages a step, keys a sub-chunk, lengths): blocks of 8 positions
_LATENT_CASES = [
    # over the counts of pages a step, the step ONE sub-chunk: one (a
    # step a block), a last step part full (sequences of 1 to 6
    # blocks), the table's own 6 and more than it holds
    *[(pages, None, None) for pages in (1, 2, 3, 4, 6, 8)],
    # steps of 32 in sub-chunks of 16: a length that ends on a
    # sub-chunk's edge, on a step's edge, one position into a new
    # sub-chunk, two whole steps, one position into a new step, idle
    (4, 16, (16, 32, 17, 64, 33, 1)),
    # the halves' hand-over under the single wait: a many-step sequence,
    # then a one-step one, an idle slot, and many steps again
    (4, 16, (90, 5, 1, 70, 96)),
    # sub-chunks that do not divide the step: 4 + 2 pages, 2 + 1,
    # 3 + 3 + 2
    (6, 32, (32, 48, 33, 49, 96, 1)),
    (3, 16, (16, 24, 17, 25, 72)),
    (8, 24, (24, 48, 25, 64, 65, 90)),
    # a page a sub-chunk, and one step a sequence in 8 + 4 pages
    (2, 8, (8, 16, 9, 90, 3, 1)),
    (12, 64, (64, 96, 65, 1, 95)),
]


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("pages,chunk,vl", _LATENT_CASES)
def test_flash_decode_paged_latent_matches_its_twin(dtype, tol, pages,
                                                    chunk, vl,
                                                    monkeypatch):
    from mxnet_tpu.kernels import tuning

    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    q, pool, bt, vl = _latent_pool(jnp.dtype(dtype), vl)
    tuning.set_runtime("flash_decode_paged_latent", "pages", pages)
    if chunk is not None:
        tuning.set_runtime("flash_decode_paged_latent", "chunk", chunk)
    before = fd._paged_fallback.count
    try:
        assert fd.paged_latent_mode(pool, 128) == "interpret"
        got = fd.flash_decode_paged_latent(q, pool, bt, vl, latent=128,
                                           scale=0.2)
    finally:
        tuning.clear_runtime()
    want = fd.reference_paged_latent_attention(q, pool, bt, vl, 128, 0.2)
    assert got.shape == (len(vl), 8, 128) and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    assert fd._paged_fallback.count == before  # kernel path, no note()


def test_the_latent_twin_reads_keys_and_values_off_one_row():
    """By hand on one sequence: scores over the whole row, values its
    first `latent` entries, positions past the length masked."""
    q, pool, bt, vl = _latent_pool(jnp.float32)
    b, n = 2, int(vl[2])
    rows = np.concatenate([np.asarray(pool[p, 0]) for p in
                           np.asarray(bt[b])])[:n]
    s = np.asarray(q[b]) @ rows.T * 0.2
    p = np.exp(s - s.max(-1, keepdims=True))
    want = (p / p.sum(-1, keepdims=True)) @ rows[:, :128]
    got = fd.reference_paged_latent_attention(q, pool, bt, vl, 128, 0.2)
    np.testing.assert_allclose(got[b], want, atol=1e-5)


def test_the_latent_gate_answers_from_static_shapes(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_FLASH_INTERPRET", raising=False)
    pool = jnp.zeros((4, 1, 16, 640), jnp.bfloat16)
    assert fd.paged_latent_mode(pool, 512) is None          # the CPU
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    assert fd.paged_latent_mode(pool, 512) == "interpret"
    assert fd.paged_latent_mode(jnp.zeros((4, 2, 16, 640)), 512) is None
    assert fd.paged_latent_mode(jnp.zeros((4, 1, 12, 640)), 512) is None
    assert fd.paged_latent_mode(pool, 768) is None
