"""Device-less TPU lowering of every Pallas kernel family.

jax.export(platforms=['tpu']) runs the full Mosaic lowering pipeline
(incl. the block-shape tiling validation) WITHOUT a TPU — these tests
are the proof that the 'compiled' kernel paths are actually viable on
hardware, which interpret-mode tests cannot give (the interpreter
ignores tiling constraints; round 2 shipped kernels that passed
interpret tests but could never have compiled on-chip)."""
import functools
import re
import types

import numpy as np
import pytest

import jax
import jax.export  # noqa: F401  (jax.export is not an auto-imported attr)
import jax.numpy as jnp


def _lowers(fn, *args):
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    n = exp.mlir_module().count("tpu_custom_call")
    assert n > 0, "no Pallas custom call in the lowered TPU module"
    return n


def test_fused_rmsnorm_lowers_fwd_and_grad():
    from mxnet_tpu.kernels.fused_norm import _rms
    x = jax.ShapeDtypeStruct((96, 64), jnp.float32)
    g = jax.ShapeDtypeStruct((64,), jnp.float32)
    _lowers(lambda a, b: _rms(a, b, 1e-6, False), x, g)
    _lowers(lambda a, b: jax.grad(
        lambda p, q: (_rms(p, q, 1e-6, False) ** 2).sum(),
        argnums=(0, 1))(a, b)[0], x, g)


def test_fused_layernorm_lowers_fwd_and_grad():
    from mxnet_tpu.kernels.fused_norm import _ln
    x = jax.ShapeDtypeStruct((130, 256), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((256,), jnp.float32)
    b = jax.ShapeDtypeStruct((256,), jnp.float32)
    _lowers(lambda a, c, e: _ln(a, c, e, 1e-5, False), x, g, b)
    _lowers(lambda a, c, e: jax.grad(
        lambda p, q, r: (_ln(p, q, r, 1e-5, False)
                         .astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2))(a, c, e)[0], x, g, b)


def test_flash_attention_lowers_fwd_and_grad_gqa():
    from mxnet_tpu.kernels.flash_attention import _flash_pallas
    q = jax.ShapeDtypeStruct((2, 512, 8, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 512, 2, 64), jnp.bfloat16)
    L = jnp.full((2,), 512, jnp.int32)
    _lowers(lambda a, b, c: _flash_pallas(a, b, c, L, True, 0.125,
                                          False), q, k, k)
    _lowers(lambda a, b, c: jax.grad(
        lambda p, s, t: _flash_pallas(p, s, t, L, True, 0.125, False)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2))(a, b, c)[0],
        q, k, k)


def test_flash_attention_with_lengths_lowers():
    from mxnet_tpu.kernels.flash_attention import _flash_pallas
    q = jax.ShapeDtypeStruct((2, 256, 4, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 256, 2, 64), jnp.bfloat16)
    lens = jax.ShapeDtypeStruct((2,), jnp.int32)
    _lowers(lambda a, b, c, L: _flash_pallas(
        a, b, c, L, False, 0.125, False), q, k, k, lens)
    _lowers(lambda a, b, c, L: jax.grad(
        lambda p, s, t: _flash_pallas(p, s, t, L, False, 0.125, False)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2))(a, b, c)[0],
        q, k, k, lens)


def test_flash_decode_lowers():
    from mxnet_tpu.kernels.flash_decode import _flash_decode_pallas
    q = jax.ShapeDtypeStruct((2, 8, 128), jnp.bfloat16)
    kc = jax.ShapeDtypeStruct((2, 2, 1024, 128), jnp.bfloat16)
    vl = jax.ShapeDtypeStruct((2,), jnp.int32)
    _lowers(lambda a, b, c, d: _flash_decode_pallas(
        a, b, c, d, 0.0884, False), q, kc, kc, vl)


def _paged_args(B, nb, N, K=8, bs=16, d=128, H=32, sharding=None):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=sharding)
    return (sds((B, H, d), jnp.bfloat16),
            sds((N, K, bs, d), jnp.bfloat16),
            sds((N, K, bs, d), jnp.bfloat16),
            sds((B, nb), jnp.int32), sds((B,), jnp.int32))


def _paged(q, kp, vp, bt, vl):
    from mxnet_tpu.kernels.flash_decode import _flash_decode_paged_pallas
    return _flash_decode_paged_pallas(q, kp, vp, bt, vl,
                                      q.shape[-1] ** -0.5, False)


def test_flash_decode_paged_lowers_at_the_serving_cells():
    # mistral_7b.chat / .reason's table shapes (BENCHMARK.json)
    _lowers(_paged, *_paged_args(32, 160, 5121))
    _lowers(_paged, *_paged_args(20, 528, 5633))


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: libtpu's own compiler, so
    Mosaic's VMEM limit and slice alignment apply. Only inside a
    fixture: one process at a time may load libtpu."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("B,nb,N", [(32, 160, 5121), (20, 528, 5633)])
def test_flash_decode_paged_compiles_for_v5e(B, nb, N, one_chip):
    text = jax.jit(_paged).lower(
        *_paged_args(B, nb, N, sharding=one_chip)).compile().as_text()
    assert "flash_decode_paged" in text


# -- the latent sweep at its cell's shapes (sarvam_105b.longctx64: 64
# slots of max_len 26,624, 64 heads on one cached row of 640 = 512
# latent + 64 rotated key + 64 zeros, a pool of 57,501 blocks) ----------

def _latent_args(B=64, nb=1664, N=57501, sharding=None):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=sharding)
    return (sds((B, 64, 640), jnp.bfloat16),
            sds((N, 1, 16, 640), jnp.bfloat16), sds((B, nb), jnp.int32),
            sds((B,), jnp.int32))


def _latent(q, pool, bt, vl, pages=128, chunk=1024):
    from mxnet_tpu.kernels.flash_decode import _paged_latent_sweep
    return _paged_latent_sweep(q, pool, bt, vl, latent=512, scale=0.1352,
                               pages=pages, chunk=chunk, interpret=False)


def test_flash_decode_paged_latent_lowers_at_the_cells_shapes():
    _lowers(_latent, *_latent_args())


def _custom_calls(text):
    """Names of the Mosaic custom calls of a compiled program."""
    return re.findall(
        r"%(\S+) = [^\n]*custom-call\([^\n]*tpu_custom_call", text)


def test_flash_decode_paged_latent_compiles_for_v5e(one_chip, monkeypatch):
    """Through the dispatch, so the gate's answer for this pool is part
    of what is held: compiled, never the gathered twin, and ONE kernel
    under the name the benchmark's readers look for. A pool of rows
    576 wide is what the gate turns away (Mosaic slices the values out
    of the row at a 128-lane tile's edge)."""
    from mxnet_tpu.kernels import flash_decode as fd

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("MXNET_TPU_FLASH_INTERPRET", raising=False)
    args = _latent_args(sharding=one_chip)
    assert fd.paged_latent_mode(args[1], 512) == "compiled"
    assert fd.paged_latent_mode(jax.ShapeDtypeStruct(
        (9, 1, 16, 576), jnp.bfloat16, sharding=one_chip), 512) is None
    text = jax.jit(lambda *a: fd.flash_decode_paged_latent(
        *a, latent=512, scale=0.1352)).lower(*args).compile().as_text()
    calls = _custom_calls(text)
    assert len(calls) == 1 and calls[0].startswith(
        "flash_decode_paged_latent"), calls
    assert "gather" not in text


@pytest.mark.parametrize("pages,chunk", [
    (128, 512), (128, 2048), (64, 512), (192, 1536), (256, 2048)])
def test_the_latent_sweeps_swept_sizes_fit_the_chip(pages, chunk,
                                                    one_chip):
    """Every (pages a step, keys a sub-chunk) `tuned.json` may take (its
    note's sweep; 128 / 1,024, the values taken, compile above): the
    two halves of the scratch and the unrolled step fit the VMEM."""
    text = jax.jit(functools.partial(_latent, pages=pages, chunk=chunk)) \
        .lower(*_latent_args(sharding=one_chip)).compile().as_text()
    assert len(_custom_calls(text)) == 1


# -- the afmoe cell's kernels at its shapes (trinity_large.longctx:
# 48 slots of max_len 22,528, window 4,096, 48 / 8 heads of 128, 32 held
# experts of 3,072 x 3,072, prompts up to 14,336) -------------------------

def _paged_windowed(q, kp, vp, bt, vl):
    from mxnet_tpu.kernels.flash_decode import _flash_decode_paged_pallas
    return _flash_decode_paged_pallas(q, kp, vp, bt, vl,
                                      q.shape[-1] ** -0.5, False, 4096)


def _grouped(fused, tm):
    from mxnet_tpu.kernels.grouped_matmul import _grouped_matmul_pallas

    def f(x, a, b, tg, nt):
        return _grouped_matmul_pallas(x, a, b if fused else None, tg, nt,
                                      tm=tm, interpret=False)
    return f


def _grouped_args(M, tm, sharding=None):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=sharding)
    w = sds((32, 3072, 3072), jnp.bfloat16)
    return (sds((M, 3072), jnp.bfloat16), w, w,
            sds((M // tm,), jnp.int32), sds((), jnp.int32))


def _windowed_prefill(q, k, v, n):
    from mxnet_tpu.kernels.flash_attention import _flash_pallas
    return _flash_pallas(q, k, v, n, True, 128 ** -0.5, False, 4096)


def test_afmoe_kernels_lower_at_the_cells_shapes():
    _lowers(_paged_windowed, *_paged_args(48, 1408, 12337, H=48))
    _lowers(_grouped(True, 16), *_grouped_args(704, 16))
    _lowers(_grouped(False, 128), *_grouped_args(12288, 128))


@pytest.mark.parametrize("what", ["paged sweep, window", "paged sweep, "
                                  "full layer", "grouped matmul, decode",
                                  "grouped matmul, prefill chunk",
                                  "flash forward, window"])
def test_afmoe_kernels_compile_for_v5e(what, one_chip):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    if what.startswith("paged sweep"):
        # the full layer's table is 48 x 1,408 int32 = 270 KB of scalar
        # prefetch: it has to fit SMEM beside the sweep's scratch
        fn = _paged_windowed if "window" in what else _paged
        N = 12337 if "window" in what else 32001
        args, name = _paged_args(48, 1408, N, H=48, sharding=one_chip), \
            "flash_decode_paged"
    elif what.startswith("grouped"):
        M, tm = (704, 16) if "decode" in what else (12288, 128)
        fn, args, name = _grouped("decode" in what, tm), \
            _grouped_args(M, tm, one_chip), "moe_grouped_matmul"
    else:
        fn, name = _windowed_prefill, "flash_attention_fwd"
        args = (sds((1, 14336, 48, 128), jnp.bfloat16),
                sds((1, 14336, 8, 128), jnp.bfloat16),
                sds((1, 14336, 8, 128), jnp.bfloat16),
                sds((1,), jnp.int32))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert name in text


# -- the Mellum training cell's kernels at its shapes
# (mellum2_12b.pretrain8k: 4 x 8,192 positions, 32 / 4 heads of 128,
# window 1,024; 16 held experts of 2,304 x 896, a chunk of 8,192 tokens
# x top-8 laid out in row tiles of 512) ------------------------------------

_MELLUM = ["grouped swiglu, pre-activations kept", "grouped down",
           "grouped dgrad, transposed sum of two", "grouped wgrad up",
           "grouped wgrad down", "flash backward, window",
           "flash backward, full"]


@pytest.mark.parametrize("what", _MELLUM)
def test_mellum_training_kernels_compile_for_v5e(what, one_chip):
    from mxnet_tpu.kernels import flash_attention as fa
    from mxnet_tpu.kernels import grouped_matmul as gm
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    tm = gm.row_tile(8192 * 8)
    assert tm == 512
    M, D, I, n = 8192 * 8 + 16 * tm, 2304, 896, 16
    kw = dict(tm=tm, interpret=False)
    tg, nt = sds((M // tm,), jnp.int32), sds((), jnp.int32)
    name = "moe_grouped_matmul"
    if what.startswith("grouped swiglu"):
        fn = lambda x, a, b, tg, nt: gm._grouped_matmul_pallas(  # noqa: E731
            x, a, b, tg, nt, save_pre=True, **kw)
        args = (sds((M, D)), sds((n, D, I)), sds((n, D, I)), tg, nt)
    elif what == "grouped down":
        fn = lambda x, a, tg, nt: gm._grouped_matmul_pallas(  # noqa: E731
            x, a, None, tg, nt, **kw)
        args = (sds((M, I)), sds((n, I, D)), tg, nt)
    elif what.startswith("grouped dgrad"):
        fn = lambda x, a, y, b, tg, nt: gm._grouped_matmul_pallas(  # noqa: E731
            x, a, b, tg, nt, lhs2=y, transpose_rhs=True, **kw)
        args = (sds((M, I)), sds((n, D, I)), sds((M, I)), sds((n, D, I)),
                tg, nt)
    elif what.startswith("grouped wgrad"):
        K, N = (D, I) if what.endswith("up") else (I, D)
        fn = lambda x, dy, tg, nt: gm._grouped_wgrad_pallas(  # noqa: E731
            x, dy, tg, nt, n, **kw)
        args, name = (sds((M, K)), sds((M, N)), tg, nt), \
            "moe_grouped_matmul_wgrad"
    else:
        window = 1024 if "window" in what else None
        B, T, H, Kh, d = 4, 8192, 32, 4, 128
        fn = lambda q, k, v, lse, dl, do: fa._pallas_backward(  # noqa: E731
            q, k, v, lse, dl, do, True, d ** -0.5, window=window)
        args = (sds((B, T, H, d)), sds((B, T, Kh, d)), sds((B, T, Kh, d)),
                sds((B, H, T), jnp.float32), sds((B, H, T), jnp.float32),
                sds((B, T, H, d)))
        name = "flash_attention_dkv"
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert name in text


# -- the Jamba cell's kernels at its shapes (jamba2_3b.reason256: 256
# slots of max_len 10,240, 20 query heads on ONE kv head of 128, d_inner
# 5,120, d_state 16, prompts up to 2,048) ---------------------------------

def _scan(x, dt, a, b, c, h):
    from mxnet_tpu.kernels.selective_scan import selective_scan_fwd
    return selective_scan_fwd(x, dt, a, b, c, h, chunk=256,
                              interpret=False)


def _state_step(h, tail, xz, live, w):
    from mxnet_tpu.kernels import tuning
    from mxnet_tpu.kernels.selective_scan import _state_update
    return _state_update(
        h, tail, xz, live, w, eps=1e-6, interpret=False,
        rows_per_step=tuning.get("ssm_state_update", "rows", "tpu"))


def _jamba_args(what, sharding=None):
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=sharding)
    if what == "scan":
        return (sds((1, 2048, 5120)), sds((1, 2048, 5120)),
                sds((16, 5120)), sds((1, 2048, 16)), sds((1, 2048, 16)),
                sds((1, 16, 40, 128)))
    if what == "state update":
        # both pools, xz as in_proj leaves it, a layer's small weights
        bf = jnp.bfloat16
        return (sds((256, 16, 40, 128)), sds((256, 3 * 5120), bf),
                sds((256, 10240), bf), sds((256,), jnp.bool_),
                {"conv_w": sds((4, 5120)), "conv_b": sds((5120,)),
                 "x_proj": sds((192, 5120), bf), "dt_norm": sds((160,), bf),
                 "b_norm": sds((16,), bf), "c_norm": sds((16,), bf),
                 "dt_proj": sds((5120, 160), bf), "dt_bias": sds((5120,)),
                 "A_log": sds((16, 5120)), "D": sds((5120,))})
    if what == "paged sweep":
        return _paged_args(256, 640, 65537, K=1, H=20, sharding=sharding)
    bf = jnp.bfloat16
    return (sds((1, 2048, 20, 128), bf), sds((1, 2048, 1, 128), bf),
            sds((1, 2048, 1, 128), bf), sds((1,), jnp.int32))


def _group20_prefill(q, k, v, n):
    from mxnet_tpu.kernels.flash_attention import _pallas_forward
    return _pallas_forward(q, k, v, True, 128 ** -0.5, lengths=n)


_JAMBA_KERNELS = {
    "scan": (_scan, "selective_scan_fwd"),
    "state update": (_state_step, "ssm_state_update"),
    "paged sweep": (_paged, "flash_decode_paged"),
    "flash forward": (_group20_prefill, "flash_attention_fwd")}


@pytest.mark.parametrize("what", list(_JAMBA_KERNELS))
def test_jamba_kernels_lower_at_the_cells_shapes(what):
    _lowers(_JAMBA_KERNELS[what][0], *_jamba_args(what))


@pytest.mark.parametrize("what", list(_JAMBA_KERNELS))
def test_jamba_kernels_compile_for_v5e(what, one_chip):
    """Mosaic's VMEM limit and tiling at the real sizes: the state
    (16 x 8 x 128 float32 a channel block) across 8 time chunks, a
    block of rows of state and tail in and out a step with both pools
    aliased and a layer's small weights resident, a group of 20 query
    heads (no multiple of 8 sublanes) on 4 KB pages with a 655 KB
    block table in SMEM."""
    fn, name = _JAMBA_KERNELS[what]
    donate = (0, 1) if what == "state update" else ()
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        *_jamba_args(what, one_chip)).compile()
    text = compiled.as_text()
    assert name in text
    if what == "state update":
        # both pools are updated in place: no copy of either, no second
        # one (the tail is a pool of (3 x 5120,) rows, tap after tap;
        # held (3, 5120) a row it was re-laid out twice a layer)
        state, tail = 256 * 16 * 5120 * 4, 256 * 3 * 5120 * 2
        assert compiled.memory_analysis().alias_size_in_bytes \
            == state + tail
        assert not re.search(r"(f32\[256,16,40,128\]|bf16\[256,3,5120\]"
                             r"|bf16\[256,15360\])\S* copy\(", text)
        # x, dt and y no longer travel between XLA and the call as
        # float32: it takes xz and gives g, both in the model's dtype
        # (the line names its results and, under
        # operand_layout_constraints, its operands)
        call = re.search(r"%ssm_state_update[\w.]* = [^\n]* custom-call\("
                         r"[^\n]*", text).group(0)
        assert "bf16[256,10240]" in call and "f32[256,5120]" not in call


# -- the Brumby cell's kernels at its shapes (brumby_14b.longctx20: 20
# slots, 40 query heads on 8 kv heads of 128, a state of 65 offsets x
# 128 x 128 float32 a kv head, prompts padded to 12,288) -------------------

def _retention_step(S, z, q, k, v, g, live):
    from mxnet_tpu.kernels.power_retention import EPS, _step
    return _step(S, z, q, k, v, g, live, eps=EPS, interpret=False)


def _retention_chunked(q, k, v, g, chunk=256):
    from mxnet_tpu.kernels.power_retention import \
        EPS, power_retention_chunked_fwd
    return power_retention_chunked_fwd(q, k, v, g, chunk=chunk, eps=EPS,
                                       interpret=False)


def _retention_args(what, sharding=None, rows=20):
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=sharding)
    bf = jnp.bfloat16
    if what == "step":
        return (sds((rows, 8, 65, 128, 128)), sds((rows, 8, 72, 128)),
                sds((rows, 40, 128), bf), sds((rows, 8, 128), bf),
                sds((rows, 8, 128), bf), sds((rows, 8)),
                sds((rows,), jnp.bool_))
    return (sds((1, 12288, 40, 128), bf), sds((1, 12288, 8, 128), bf),
            sds((1, 12288, 8, 128), bf), sds((1, 12288, 8)))


_RETENTION_KERNELS = {
    "step": (_retention_step, "power_retention_step"),
    "chunked": (_retention_chunked, "power_retention_chunked")}


@pytest.mark.parametrize("what", list(_RETENTION_KERNELS))
def test_retention_kernels_lower_at_the_cells_shapes(what):
    assert _lowers(_RETENTION_KERNELS[what][0],
                   *_retention_args(what)) == 1


@pytest.mark.parametrize("what,size", [
    ("step", 20), ("step", 16), ("step", 1),
    ("chunked", 128), ("chunked", 256), ("chunked", 512)])
def test_retention_kernels_compile_for_v5e(what, size, one_chip):
    """Mosaic's VMEM limit and tiling at the real sizes: a kv head's
    whole state (4.3 MB) in and out a step with the pool aliased, at
    the cell's 20 rows, at 16 and at 1; a head's state resident across
    96 / 48 / 24 chunks of a padded prompt beside five (heads x chunk,
    128) scratches, at every value of the swept chunk."""
    fn, name = _RETENTION_KERNELS[what]
    if what == "step":
        args = _retention_args(what, one_chip, rows=size)
    else:
        fn, args = functools.partial(fn, chunk=size), \
            _retention_args(what, one_chip)
    compiled = jax.jit(
        fn, donate_argnums=(0, 1) if what == "step" else ()).lower(
        *args).compile()
    text = compiled.as_text()
    assert len(re.findall(rf"%{name}[\w.]* = [^\n]* custom-call\(",
                          text)) == 1
    if what == "step":
        # the pool is updated in place: no copy of it, no second one
        state = size * 8 * (65 * 128 * 128 + 72 * 128) * 4
        assert compiled.memory_analysis().alias_size_in_bytes == state
        assert not re.search(
            rf"f32\[{size},8,(65,128,128|72,128)\]\S* copy\(", text)


def test_the_sweeps_vmem_reckoning_takes_the_real_group():
    from mxnet_tpu.kernels.flash_decode import _paged_sweep_pages
    mistral, trinity, jamba = (5633, 8, 16, 128), (32001, 8, 16, 128), \
        (65537, 1, 16, 128)
    # groups up to 16 (one bf16 tile of rows) reckon as they always did
    assert _paged_sweep_pages(mistral, 2, 528, 4) \
        == _paged_sweep_pages(mistral, 2, 528) == 48
    assert _paged_sweep_pages(trinity, 2, 1408, 6) \
        == _paged_sweep_pages(trinity, 2, 1408, 16)
    # a group of 20 takes two tiles: fewer bytes left for pages
    free = lambda g: _paged_sweep_pages(jamba, 2, None, g)  # noqa: E731
    assert free(20) == free(32) <= free(16)
    assert _paged_sweep_pages(jamba, 2, 640, 20) >= 128


@pytest.mark.parametrize("what", ["train cell, forward",
                                  "train cell, backward",
                                  "prefill of 2,048", "prefill of 6,144"])
def test_flash_attention_compiles_for_v5e_at_the_cells_shapes(
        what, one_chip):
    """The lane-dense kernels at the shapes the benchmark's cells run,
    with the blocks tuned.json's tpu section gives: heads of 64 two a
    step on (64, 512, 768), heads of 128 with GQA on a prefill."""
    from mxnet_tpu.kernels import flash_attention as fa
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    if what.startswith("train"):
        B, T, H, K, d, causal = 64, 512, 12, 12, 64, False
    else:
        B, T, H, K, d, causal = 1, 2048 if "2,048" in what else 6144, \
            32, 8, 128, True
    q, kv, n = sds((B, T, H, d)), sds((B, T, K, d)), sds((B,), jnp.int32)
    if what.endswith("backward"):
        row = sds((B, H, T), jnp.float32)
        fn, args, name = (lambda q, k, v, lse, dl, g, n: fa._pallas_backward(
            q, k, v, lse, dl, g, causal, d ** -0.5, lengths=n)), \
            (q, kv, kv, row, row, q, n), "flash_attention_dkv"
    else:
        fn, args, name = (lambda q, k, v, n: fa._pallas_forward(
            q, k, v, causal, d ** -0.5, return_lse=True, lengths=n)), \
            (q, kv, kv, n), "flash_attention_fwd"
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert name in text
    # the row statistics leave and enter with T along the lanes
    assert re.search(r"f32\[%d,%d,%d,%d\]" % (
        (B, H // 2, 2, T) if d == 64 else (B, H, 1, T)), text), what


def _gates_as_on_the_chip(monkeypatch):
    """The one gate (kernels/dispatch.py) answers as on the chip: it
    asks jax.default_backend() (cpu in tests) and the environment,
    whatever another test module of this process set there
    (perfbench/rehearse.py turns the interpreter on when imported)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for family in ("FLASH", "NORM", "CE", "MOE", "SCAN"):
        monkeypatch.delenv(f"MXNET_TPU_{family}_INTERPRET", raising=False)


@pytest.fixture
def chip_gates(monkeypatch):
    _gates_as_on_the_chip(monkeypatch)


def test_full_llama_step_lowers_with_kernels(chip_gates):
    """The flagship model's jitted forward lowers for TPU with the
    fused-norm kernels actually inside (the _ops_nn dispatch routes
    trailing-axis norms to Pallas when the backend is not cpu — the
    export targets TPU, so patch the backend the gate asks for the way
    the TPU runtime would see it)."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    mx.random.seed(0)
    cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                      intermediate_size=256, num_layers=1, num_heads=4,
                      num_kv_heads=2, max_seq_len=256, dtype="float32")
    net = LlamaForCausalLM(cfg)
    net.initialize()
    ids = mx.nd.array(np.zeros((2, 256), np.int32))
    ent = net.trace_entry([ids], training=False)
    tr = {n: net.collect_params()[n].data()._data for n in ent.tr_names}
    aux = {n: net.collect_params()[n].data()._data
           for n in ent.aux_names}
    key = jax.random.PRNGKey(0)

    def fwd(ids_):
        flat, _ = ent.raw_fn(tr, aux, key, ids_)
        return flat[0]

    n = _lowers(fwd, jax.ShapeDtypeStruct((2, 256), jnp.int32))
    assert n >= 2  # at least the norm kernels appear in the program


def test_fused_ce_lowers_fwd_and_grad():
    from mxnet_tpu.kernels.fused_ce import _ce_pallas
    # BERT-base vocab (30522: exercises the 128-lane padding) at a
    # realistic (B*T) row count
    x = jax.ShapeDtypeStruct((256, 30522), jnp.bfloat16)
    lbl = jax.ShapeDtypeStruct((256,), jnp.int32)
    _lowers(lambda a, b: _ce_pallas(a, b, False), x, lbl)
    _lowers(lambda a, b: jax.grad(
        lambda p: _ce_pallas(p, b, False).sum())(a), x, lbl)


def test_flash_decode_quantized_lowers():
    from mxnet_tpu.kernels.flash_decode import _flash_decode_pallas_q8
    B, K, S, d, rep = 2, 2, 1024, 128, 4
    q = jax.ShapeDtypeStruct((B, K * rep, d), jnp.bfloat16)
    k8 = jax.ShapeDtypeStruct((B, K, S, d), jnp.int8)
    ks = jax.ShapeDtypeStruct((B, K, S, 1), jnp.float32)
    vl = jax.ShapeDtypeStruct((B,), jnp.int32)
    _lowers(lambda q_, k_, ks_, v_, vs_, vl_: _flash_decode_pallas_q8(
        q_, k_, ks_, v_, vs_, vl_, 0.088, False), q, k8, ks, k8, ks, vl)


def test_bert_forward_with_flash_lengths_lowers(chip_gates):
    """The benchmark's BERT cells feed ragged valid_length so the
    flash kernel's key-padding path engages — prove THAT exact forward
    lowers for TPU before chip time is spent on it."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.bert import BERTForPretraining

    mx.random.seed(0)
    net = BERTForPretraining(vocab_size=512, units=128,
                             hidden_size=256, num_layers=1,
                             num_heads=4, max_length=128)
    net.initialize(init=mx.init.Normal(0.02))
    ids = mx.nd.array(np.zeros((2, 128), np.int32))
    tok = mx.nd.zeros((2, 128), dtype="int32")
    vlen = mx.nd.array(np.array([100, 128], np.int32))
    ent = net.trace_entry([ids, tok, vlen], training=False)
    tr = {n: net.collect_params()[n].data()._data for n in ent.tr_names}
    aux = {n: net.collect_params()[n].data()._data
           for n in ent.aux_names}
    key = jax.random.PRNGKey(0)

    def fwd(ids_, tok_, vlen_):
        flat, _ = ent.raw_fn(tr, aux, key, ids_, tok_, vlen_)
        return flat[0]

    n = _lowers(fwd, jax.ShapeDtypeStruct((2, 128), jnp.int32),
                jax.ShapeDtypeStruct((2, 128), jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.int32))
    assert n >= 2  # flash attention AND the fused norms engaged


@pytest.mark.slow
def test_resnet_fused_train_step_lowers():
    """The headline bench workload — fused fwd+bwd+momentum-SGD on a
    bf16 NHWC ResNet — exports for the TPU platform (round 3 verified
    this interactively; this commits the proof so a lowering
    regression turns the suite red, not the driver's one on-chip
    bench window). ResNet-18 at 32px keeps the export fast; the op
    mix (convs, BN, pooling, dense, momentum update, donated buffers)
    is the same as the bench's ResNet-50."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.models.resnet import resnet18_v1
    from mxnet_tpu.parallel.data_parallel import FusedTrainStep

    mx.random.seed(0)
    saved_amp = dict(amp._STATE)  # amp.init is process-wide: restore
    try:                          # even when an earlier stage raises
        net = resnet18_v1(classes=10, layout="NHWC")
        net.initialize(init=mx.init.Xavier())
        amp.init("bfloat16")
        amp.convert_block(net)
        loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                               multi_precision=True)
        step = FusedTrainStep(net, loss_fn, opt, mesh=None)
        x = mx.nd.array(np.zeros((2, 32, 32, 3), np.float32),
                        dtype="bfloat16")
        y = mx.nd.array(np.zeros((2,), np.int32))
        float(step(x, y).asscalar())  # build + one CPU step

        sds = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        hyper = {"lr": jax.ShapeDtypeStruct((), jnp.float32),
                 "wd": jax.ShapeDtypeStruct((), jnp.float32),
                 "t": jax.ShapeDtypeStruct((), jnp.int32),
                 "rescale": jax.ShapeDtypeStruct((), jnp.float32)}
        import mxnet_tpu.random as _random
        key_sd = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            _random.next_key())
        exp = jax.export.export(step._compiled, platforms=["tpu"])(
            sds(step._tr), sds(step._aux), sds(step._states), hyper,
            key_sd,
            jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.bfloat16),
            jax.ShapeDtypeStruct((2,), jnp.int32))
        assert exp.mlir_module()  # lowered for TPU without error
    finally:
        amp._STATE.update(saved_amp)


def test_a_descriptions_decode_options_reach_the_tpu_compiler(
        one_chip, monkeypatch):
    """A recurrent net's tick is compiled without XLA's prefetches of
    its operands into VMEM (1,094 asynchronous pairs a tick at the
    cell's sizes, which a profiler trace pays for one by one). The
    option has to be one libtpu knows — an unknown name fails the
    compile — and the descriptions without recurrent layers give
    none, so their programs are compiled as they were."""
    from mxnet_tpu.models.afmoe import AfmoeDecoder
    from mxnet_tpu.models.jamba import JambaDecoder
    from mxnet_tpu.models.llama_infer import LlamaDecoder
    from mxnet_tpu.serving.executables import Program

    assert LlamaDecoder.decode_compiler_options is None
    assert AfmoeDecoder.decode_compiler_options is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def tick(ws, x):
        for w in ws:
            x = jnp.tanh(x @ w)
        return x

    bf16 = jnp.bfloat16
    args = ([jax.ShapeDtypeStruct((2560, 2560), bf16, sharding=one_chip)
             for _ in range(8)],
            jax.ShapeDtypeStruct((256, 2560), bf16, sharding=one_chip))

    def starts(options):
        text = Program("probe", tick, compiler_options=options) \
            ._jit.lower(*args).compile().as_text()
        return len(re.findall(r"(?:copy|slice)-start\(", text))

    assert starts(JambaDecoder.decode_compiler_options) < starts(None)
    with pytest.raises(Exception, match="xla_no_such_option"):
        starts({"xla_no_such_option": 1})


# -- the serving programs whole: the paged cache's row write ---------------

def _described_net(name, sharding, **sizes):
    """A net of the cell's widths whose weights are shapes on the
    described chip: nothing is allocated, `params_tree` reads them as
    it reads arrays."""
    import mxnet_tpu as mx
    net = mx.models.get_model(name, **sizes)
    for p in net.collect_params().values():
        p._data = types.SimpleNamespace(_data=jax.ShapeDtypeStruct(
            tuple(p.shape), jnp.dtype(p.dtype), sharding=sharding))
        p._deferred = None
    return net


_COMPILED = {}      # a cell's program compiles once for every test below


def _compiled_serving_program(what, one_chip, monkeypatch):
    """The real `decode` / `prefill` of `paged_programs` at two layers
    of a cell's widths, with the cell's slots, blocks and `max_len`,
    compiled for the described v5e. Returns the compiled text, the
    pools' shapes as a `copy`'s result prints them, and the number of
    pool arrays."""
    from mxnet_tpu.serving.executables import paged_programs

    _gates_as_on_the_chip(monkeypatch)
    if what in _COMPILED:
        return _COMPILED[what]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.dtype(dt), sharding=one_chip)
    cell, program = what.split(",")[0].split()
    q8 = what.endswith("int8")
    if cell == "mistral_7b.reason":
        net = _described_net("llama_3_8b", one_chip, num_layers=2,
                             vocab_size=32000, rope_base=1e6)
        slots, max_len, max_prompt, blocks = 20, 8448, 6144, [5633] * 2
    elif cell == "sarvam_105b.longctx64":   # the dense layer, one sparse
        net = _described_net("sarvam_mla", one_chip, num_layers=2,
                             vocab_size=32768, held_experts=(0, 16),
                             max_seq_len=26624)
        slots, max_len, max_prompt, blocks = 64, 26624, 14336, [57501] * 2
    elif cell == "brumby_14b.longctx20":    # two retention layers, no pool
        net = _described_net("brumby", one_chip, num_layers=2,
                             max_seq_len=20480)
        slots, max_len, max_prompt, blocks = 20, 20480, 12288, [0, 0]
    elif cell == "jamba2_3b.reason256":     # one Mamba layer, one attention
        net = _described_net("jamba", one_chip, num_layers=2,
                             attn_layer_period=2, attn_layer_offset=1)
        slots, max_len, max_prompt, blocks = 256, 10240, 2048, [0, 65537]
    else:       # one sliding layer (dense) and one full (sparse)
        net = _described_net(
            "afmoe", one_chip, vocab_size=25024, num_layers=2,
            num_dense_layers=1, layer_types=["sliding", "full"],
            held_experts=(0, 32), max_seq_len=22528)
        slots, max_len, max_prompt = 48, 22528, 14336
        blocks = [12337, 32001]
    dec = net.decoder()
    cfg, bs = dec.cfg, 16
    nb = max_len // bs
    programs = paged_programs(
        net, batch_slots=slots, max_blocks_per_seq=nb, block_size=bs,
        max_prompt_len=max_prompt,
        kv_cache_dtype="int8" if q8 else "model")
    kv = (cfg.num_kv_heads, bs, cfg.head_dim)
    pages = [{f: sds((n,) + kv, "int8" if q8 else cfg.dtype)
              for f in (("k",) if dec.latent else ("k", "v"))} if n else
             {name: sds((slots,) + tuple(shape), dt)
              for name, (shape, dt) in dec.state_shapes().items()}
             for n in blocks]
    if q8:
        for pg, n in zip(pages, blocks):
            pg.update({f: sds((n,) + kv[:2] + (1,), "float32")
                       for f in ("ks", "vs")})
    params = dec.params_tree(net)
    table, row = sds((slots, nb), "int32"), sds((nb,), "int32")
    if dec.mixed:
        table, row = (table, table), (row, row)
    if not any(blocks):             # no pool: no table goes in
        table = row = ()
    if program == "prefill":
        args = (params, pages, row, sds((1, max_prompt), "int32"),
                sds((1,), "int32"), sds((1,), "int32")) \
            + ((sds((1,), "int32"),) if dec.recurrent else ())
    else:
        args = (params, pages, table, sds((slots,), "int32"),
                sds((slots, cfg.vocab_size), cfg.dtype),
                sds((slots, 2), "uint32"), sds((slots,), "float32"),
                sds((slots,), "int32"), sds((slots,), "float32"),
                sds((slots,), "bool"))
    text = programs[program]._jit.lower(*args).compile().as_text()
    pools = {",".join(map(str, (n,) + kv)) for n in blocks if n}
    _COMPILED[what] = text, pools, len(jax.tree_util.tree_leaves(pages))
    return _COMPILED[what]


@pytest.mark.parametrize("what", [
    "mistral_7b.reason decode", "mistral_7b.reason prefill",
    "trinity_large.longctx decode", "trinity_large.longctx prefill",
    "mistral_7b.reason decode, int8",
    "sarvam_105b.longctx64 decode", "sarvam_105b.longctx64 prefill"])
def test_the_paged_row_write_re_lays_no_pool_out(what, one_chip,
                                                 monkeypatch):
    """No `copy` in the compiled program has a result of a pool's
    shape. The indexed write
    `pool.at[blk, :, offs, :].set(rows)` held FOUR a layer (k and v,
    each re-laid out with the scattered dims major for the scatter
    and back for the kernel: 64 copies of 185 MB a Mistral tick, two
    thirds of it); `write_rows` scatters on the pool's (N, K*bs, d)
    view, in place. The int8 twin's scale pools (N, K, bs, 1) keep
    the indexed write and are not held to this. A LATENT layer's one
    pool (N, 1, bs, 640) is written the same way and read by the
    latent sweep (PR 41)."""
    text, pools, n_pools = _compiled_serving_program(what, one_chip,
                                                     monkeypatch)
    program = what.split(",")[0].split()[1]
    copied = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
    assert copied, "the pattern finds no copy at all in this program"
    whole = [c for c in copied if c in pools]
    assert not whole, f"{len(whole)} whole-pool copies"
    kernel = "flash_attention_fwd" if program == "prefill" \
        else "flash_decode_paged_latent" if what.startswith("sarvam") \
        else "flash_decode_paged"
    assert re.search(rf"%{kernel}[\w.]* = [^\n]* custom-call\(", text), \
        f"no Mosaic call {kernel} in the compiled {program}"
    # in place: every pool comes back in the buffer it came in
    assert text.count("may-alias") + text.count("must-alias") >= n_pools


@pytest.mark.parametrize("what,calls", [
    ("mistral_7b.reason decode", 2),        # two full layers: plain
    ("trinity_large.longctx decode", 2),    # one sliding (window), one full
    ("jamba2_3b.reason256 decode", 1)])     # one attention layer of two
def test_the_dealt_sweep_is_one_call_a_layer_round_no_pool_copy(
        what, calls, one_chip, monkeypatch):
    """PR 46's schedule (a step's starts unrolled under predicates, one
    step body) still compiles, in the cells' real decode programs, to
    ONE `flash_decode_paged` Mosaic call an attention layer, plain and
    windowed, and XLA lays no pool out anew to feed it (PR 38)."""
    text, pools, _ = _compiled_serving_program(what, one_chip,
                                               monkeypatch)
    found = re.findall(
        r"%flash_decode_paged(?:\.\d+)? = [^\n]* custom-call\(", text)
    assert len(found) == calls, found
    copied = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
    assert not [c for c in copied if c in pools]


def test_the_recurrent_step_is_one_call_a_layer_both_pools_in_place(
        one_chip, monkeypatch):
    """Jamba's whole `decode` at the cell's sizes (one recurrent layer,
    one attention layer): ONE `ssm_state_update` Mosaic call for
    everything between in_proj and out_proj, no `copy` whose result has
    the shape of the state pool or of the tail pool (held (3, 5120) a
    row the tail was copied on its way in and out, PR 35's finding),
    nothing float32 of a row's width between XLA and the call, and
    every pool back in the buffer it came in."""
    text, _, n_pools = _compiled_serving_program(
        "jamba2_3b.reason256 decode", one_chip, monkeypatch)
    assert n_pools == 4                             # h, tail, k, v
    calls = re.findall(r"%ssm_state_update[\w.]* = [^\n]* custom-call\(",
                       text)
    assert len(calls) == 1 and "f32[256,5120]" not in calls[0]
    assert "bf16[256,15360]" in calls[0] and "bf16[256,5120]" in calls[0]
    copied = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
    assert copied, "the pattern finds no copy at all in this program"
    whole = [c for c in copied
             if c in ("256,16,40,128", "256,15360", "256,3,5120")]
    assert not whole, f"{len(whole)} whole-pool copies"
    assert text.count("may-alias") + text.count("must-alias") >= n_pools


@pytest.mark.parametrize("program,kernel", [
    ("decode", "power_retention_step"),
    ("prefill", "power_retention_chunked")])
def test_the_retention_programs_hold_the_state_pool_in_place(
        program, kernel, one_chip, monkeypatch):
    """The whole `decode` / `prefill` of a net with no attention layer
    at the cell's sizes: one Mosaic call a layer, no block pool and no
    table among the operands, and no `copy` whose result has the shape
    of the state pool (PR 38's lesson: a pool re-laid out round its
    kernel costs more than the kernel). In the tick every pool comes
    back in the buffer it came in."""
    text, pools, n_pools = _compiled_serving_program(
        f"brumby_14b.longctx20 {program}", one_chip, monkeypatch)
    assert pools == set() and n_pools == 4          # S and z, two layers
    assert len(re.findall(rf"%{kernel}[\w.]* = [^\n]* custom-call\(",
                          text)) == 2
    assert "flash_decode_paged" not in text
    copied = re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
    assert copied, "the pattern finds no copy at all in this program"
    whole = [c for c in copied
             if c in ("20,8,65,128,128", "20,8,72,128")]
    assert not whole, f"{len(whole)} whole-pool copies"
    assert text.count("may-alias") + text.count("must-alias") >= n_pools


# -- the sampler: thresholds by selection, no sort (PR 40) -----------------

def _sampler_args(B, V, sharding=None):
    """`sample_tokens`' operands as shapes: bf16 logits, row keys,
    temperature, top_k, top_p."""
    return [jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=sharding)
            for shape, dt in (((B, V), "bfloat16"), ((B, 2), "uint32"),
                              ((B,), "float32"), ((B,), "int32"),
                              ((B,), "float32"))]


def _sorts(text):
    """[(operand dims, ...)] of every `sort` of a compiled program."""
    return [tuple(tuple(map(int, dims.split(",")))
                  for dims in re.findall(r"\w+\[([\d,]+)\]", operands))
            for operands in re.findall(r" sort\(([^)]*)\)", text)]


@pytest.mark.parametrize("what", ["jamba2_3b.reason256 decode",
                                  "mistral_7b.reason decode",
                                  "trinity_large.longctx decode"])
def test_the_decode_program_sorts_no_logits(what, one_chip, monkeypatch):
    """Until PR 40 every tick sorted its (slots, vocabulary) logits
    twice (61% of jamba's tick). The compiled decode programs of the
    Jamba and Mistral descriptions hold no `sort` at all; afmoe's
    holds the expert layer's alone, over slots x top-k expert ids."""
    text, _, _ = _compiled_serving_program(what, one_chip, monkeypatch)
    assert " fusion(" in text and "reduce(" in text   # a compiled text
    sorts = _sorts(text)
    if what.startswith("trinity_large"):
        assert sorts, "the expert layer's argsort has left the program"
        assert all(dims == (48 * 4,) for op in sorts for dims in op), sorts
    else:
        assert not sorts, sorts


def test_the_sampler_lowers_to_no_sort():
    """Without a topology: the lowered text of `sample_tokens` holds
    no sort (the parent's held two), whatever the backend makes of
    the rest."""
    from mxnet_tpu.serving.sampling import sample_tokens
    text = jax.jit(sample_tokens).lower(*_sampler_args(8, 4099)).as_text()
    assert "reduce" in text and "sort" not in text


def test_a_selection_round_is_one_fusion_over_the_row(one_chip):
    """What the tick pays for the two thresholds, counted from the
    sampler compiled for the described v5e at jamba's 256 x 65,536:
    each of the 32 rounds is ONE multi-output fusion over the bf16
    logits (its 3 reductions are siblings) and ONE (256,) fusion that
    settles the digit — no float32 copy of the row is kept, and the
    device operations are a dozen more than the 66 the sorted sampler
    compiled to (a profiler trace pays 36-46 us for every one, every
    tick: PERF.md)."""
    from mxnet_tpu.serving.sampling import sample_tokens
    B, V = 256, 65536
    compiled = jax.jit(sample_tokens).lower(
        *_sampler_args(B, V, one_chip)).compile()
    entry = compiled.as_text()
    entry = entry[entry.index("\nENTRY"):]
    ops = re.findall(r"^\s+(?:ROOT )?%[\w.\-]+ = (?:\(.*?\)|\S+) "
                     r"([\w\-]+)\(", entry, re.M)
    events = [op for op in ops if op not in (
        "parameter", "get-tuple-element", "bitcast", "tuple", "constant",
        "copy-start", "slice-start")]
    assert "sort" not in events
    assert 64 <= events.count("fusion") and len(events) <= 84, \
        (len(events), sorted(set(events)))
    assert compiled.memory_analysis().temp_size_in_bytes < B * V * 4
