"""chip_smoke.py's logic under tier-1: its phase functions at a tiny
size on the CPU (the same Pallas kernels, under the interpreter), and
the rules the chip run rests on — the script refuses anything but a
TPU, the compile cache can be placed from outside, the paged kernels
pass the installed Pallas's compiler params, `mx.tpu()` does not
quietly mean the host, a kernel failure on a TPU backend raises, and a
Pallas call under a multi-device jit runs per shard."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402

#: the decoder at toy widths; every other field keeps the relations the
#: full size has (seq a multiple of 128 so the flash gate admits it,
#: vocab2 off the lane multiple, batch divisible by four chips, GQA with
#: more query than KV heads) and is otherwise as small as it goes — the
#: kernels run under the interpreter here
TINY = chip_smoke.Size(
    vocab=1152, hidden=64, ffn=128, heads=2, kv_heads=1, layers=1,
    dtype="bfloat16",
    batch=4, seq=128, steps=3,
    bert_heads=2, bert_head_dim=32, bert_seq=128, ln_width=96,
    vocab2=1100, decode_len=128, block=16, window=2,
    serve_table=(3, 12, 30, 20, 150),
    sliding_window=48, serve_window=64, experts=(16, 4, 32, 2),
    slots=4, max_len=128, max_prompt=64,
    waves=(((40, 2, 0.0), (5, 4, 0.0), (64, 3, 0.8), (17, 5, 0.0),
            (33, 3, 0.7), (60, 2, 0.0)),
           ((9, 4, 0.0), (50, 3, 0.8), (63, 2, 0.0), (21, 5, 0.6),
            (48, 3, 0.0), (3, 5, 0.0))),
    compiled=False)


@pytest.fixture
def interpret(monkeypatch):
    """The kernels' CPU switch: trace the real Pallas kernels and run
    them under the interpreter."""
    for fam in ("FLASH", "NORM", "CE", "MOE", "SCAN"):
        monkeypatch.setenv(f"MXNET_TPU_{fam}_INTERPRET", "1")


@pytest.fixture
def meter():
    return chip_smoke.CompileMeter()


def test_kernels_phase_tiny(interpret):
    chip_smoke.phase_kernels(TINY)


def test_train_serve_multichip_phases_tiny(interpret, meter):
    """train -> serve on the trained net -> the same step on four
    (virtual) devices reproducing the one-device first loss."""
    chip_smoke.phase_device(TINY)
    net, first_loss = chip_smoke.phase_train(TINY, meter)
    chip_smoke.phase_serve(TINY, net, meter)
    assert jax.device_count() >= 4      # conftest's virtual mesh
    chip_smoke.phase_multichip(TINY, first_loss, meter)


def test_script_refuses_a_cpu():
    """`python chip_smoke.py` with JAX pinned to the CPU: non-zero exit
    in the device phase, the missing chip named, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable,
                          os.path.join(_REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
    assert "[kernels]" not in out.stdout


def test_failed_check_is_fatal():
    with pytest.raises(AssertionError, match="tolerance"):
        chip_smoke.check(False, "error above tolerance")


_CACHE_PROBE = ("import sys; sys.path.insert(0, %r); "
                "from mxnet_tpu import tracing; "
                "print(tracing.enable_compile_cache())" % _REPO)


def test_compile_cache_can_be_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins; unset, it is one fixed path under
    the checkout, the same from two processes started in different
    directories."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    placed = dict(env, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    procs = [subprocess.Popen([sys.executable, "-c", _CACHE_PROBE],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=e,
                              cwd=cwd)
             for e, cwd in ((placed, _REPO), (env, _REPO),
                            (env, str(tmp_path)))]
    dirs = []
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, err[-2000:]
        dirs.append(out.strip().splitlines()[-1])
    assert dirs == [str(tmp_path / "cc"),
                    os.path.join(_REPO, ".jax_cache"),
                    os.path.join(_REPO, ".jax_cache")]


def test_paged_kernels_pass_installed_compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    from mxnet_tpu.kernels import flash_decode

    kw = flash_decode._paged_compiler_params(pltpu, interpret=False)
    params = kw["compiler_params"]
    assert isinstance(params, pltpu.CompilerParams)
    assert tuple(params.dimension_semantics) == (
        "parallel", "parallel", "arbitrary")
    assert flash_decode._paged_compiler_params(pltpu, True) == {}


def test_tpu_context_unpinned_without_a_tpu_raises():
    """`mx.tpu()` means a host device only in a process that pinned JAX
    to the CPU; otherwise a missing chip is an error, not a default."""
    assert mx.tpu().jax_device.platform == "cpu"        # pinned: legal
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(RuntimeError, match="no TPU device found"):
            mx.tpu().jax_device
        with pytest.raises(RuntimeError, match="no TPU device found"):
            mx.context._default_context()
    finally:
        jax.config.update("jax_platforms", "cpu")
    assert mx.context.current_context() == mx.cpu()


def test_kernel_failure_raises_on_a_tpu_backend(monkeypatch):
    from mxnet_tpu.kernels import dispatch

    monkeypatch.setattr(dispatch, "_REGISTRY", dict(dispatch._REGISTRY))
    fb = dispatch.KernelFallback("test-family", "FLASH")
    with pytest.warns(RuntimeWarning, match="falling back"):
        fb.note(ValueError("interpreter quirk"))        # off the chip
    assert fb.count == 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="mosaic"):
        fb.note(ValueError("mosaic said no"))
    assert fb.count == 1


@pytest.mark.parametrize("tp,H,K,local_heads", [
    (2, 4, 2, 2),       # heads split over tp
    (4, 8, 2, 8),       # tp divides q heads but not kv heads: stay whole
])
def test_pallas_under_multi_device_jit_runs_per_shard(interpret, tp, H, K,
                                                      local_heads):
    """Under a dp x tp mesh the kernel seam wraps the call in shard_map:
    each device sees its slice of the batch and — when tp divides query
    AND kv heads, so GQA still pairs the right ones — of the heads, and
    the result equals the unsharded one."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu.kernels import flash_attention as fa
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.mesh import use_mesh

    mesh = make_mesh([2, tp], ["dp", "tp"])
    B, T, d = 4, 128, 32
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, T, H, d), jnp.float32)
    k = jnp.asarray(rs.randn(B, T, K, d), jnp.float32)
    v = jnp.asarray(rs.randn(B, T, K, d), jnp.float32)
    seen = []
    real = fa._pallas_forward

    def spy(q, *a, **kw):
        seen.append(q.shape)
        return real(q, *a, **kw)

    fa._pallas_forward = spy
    try:
        want = fa.flash_attention_raw(q, k, v)          # one device
        sh = NamedSharding(mesh, P("dp"))
        with use_mesh(mesh):
            got = jax.jit(fa.flash_attention_raw,
                          in_shardings=(sh, sh, sh))(q, k, v)
    finally:
        fa._pallas_forward = real
    assert seen == [(B, T, H, d), (B // 2, T, local_heads, d)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
