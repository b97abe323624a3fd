"""The choice of kernel is made in one place (kernels/dispatch.py): one
gate, `kernel_mode`, for the five families' switches, and one guarded
call, `KernelFallback.run`, at all ten fallback sites (one counter +
warn-once + strict escape hatch); the profiler surfaces the counts.
Reference analogue: the fork's fused-kernel env toggles
(MXNET_USE_FUSION-style) with visible fallback logging."""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.kernels import (dispatch, flash_attention, flash_decode,
                               fused_ce, fused_norm, grouped_matmul,
                               power_retention, selective_scan)

FAMILIES = ("FLASH", "NORM", "CE", "MOE", "SCAN")


def _f(seed, *shape, by=1.0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape) * by,
                       jnp.float32)


def _attention():
    q = _f(0, 1, 128, 2, 8)
    return (lambda: flash_attention.flash_attention_raw(q, q, q),
            lambda: flash_attention.reference_attention(q, q, q))


def _decode():
    q, kc, vl = _f(1, 2, 4, 16), _f(2, 2, 2, 128, 16), jnp.asarray([70, 128])
    return (lambda: flash_decode.flash_decode(q, kc, kc, vl),
            lambda: flash_decode.reference_decode_attention(q, kc, kc, vl))


def _decode_paged():
    # 3 pages of 8 a sequence: the gathered view is no 128-row tile, so
    # the twin's contiguous sweep is the jnp reference too
    q, kp = _f(3, 2, 4, 16), _f(4, 7, 2, 8, 16)
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    vl = jnp.asarray([20, 9])
    return (lambda: flash_decode.flash_decode_paged(q, kp, kp, bt, vl),
            lambda: flash_decode.reference_decode_attention(
                q, flash_decode.gather_kv_pages(kp, bt),
                flash_decode.gather_kv_pages(kp, bt), vl))


def _ce():
    x = _f(5, 8, 2048)
    lbl = jnp.asarray(np.random.default_rng(5).integers(0, 2048, 8))
    return (lambda: fused_ce.fused_softmax_ce_raw(x, lbl),
            lambda: fused_ce.reference_softmax_ce(x, lbl))


def _norm():
    x, g = _f(6, 4, 8), 1 + _f(7, 8, by=0.1)
    return (lambda: fused_norm.fused_rmsnorm(x, g),
            lambda: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                      + 1e-6) * g)


def _grouped():
    lhs, rhs = _f(8, 16, 8), _f(9, 2, 8, 8)
    tg, nt = jnp.asarray([0, 1], jnp.int32), jnp.asarray(2, jnp.int32)
    return (lambda: grouped_matmul.grouped_matmul(lhs, rhs, tg, nt, 8),
            lambda: grouped_matmul.reference_grouped_matmul(
                lhs, rhs, tg, nt, 8))


def _scan():
    B, T, Dn, N = 1, 8, 128, 4
    x, b, c = _f(10, B, T, Dn), _f(11, B, T, N), _f(12, B, T, N)
    dt = jnp.abs(_f(13, B, T, Dn, by=0.1))
    a_log = jnp.zeros((N, Dn), jnp.float32)
    h0 = _f(14, B, *selective_scan.state_shape(N, Dn))
    return (lambda: selective_scan.selective_scan(x, dt, a_log, b, c, h0),
            lambda: selective_scan.selective_scan_ref(x, dt, a_log, b, c,
                                                      h0))


def _ssm_step():
    R, Dn, N, K, RK = 2, 128, 4, 4, 8
    w = {"conv_w": _f(15, K, Dn, by=0.5), "conv_b": _f(16, Dn, by=0.1),
         "x_proj": _f(17, RK + 2 * N, Dn, by=Dn ** -0.5),
         "dt_norm": 1 + _f(18, RK, by=0.1), "b_norm": 1 + _f(19, N, by=0.1),
         "c_norm": 1 + _f(20, N, by=0.1),
         "dt_proj": _f(21, Dn, RK, by=RK ** -0.5),
         "dt_bias": _f(22, Dn) - 3.0,
         "A_log": jnp.zeros((N, Dn), jnp.float32), "D": _f(23, Dn)}
    assert set(w) == set(selective_scan.STEP_WEIGHTS)
    h = _f(24, R, *selective_scan.state_shape(N, Dn))
    tail = _f(25, R, *selective_scan.tail_shape(K, Dn))
    xz, live = _f(26, R, 2 * Dn), jnp.asarray([True, False])
    return (lambda: selective_scan.ssm_state_update(h, tail, xz, live, w,
                                                    1e-6),
            lambda: selective_scan.ssm_state_update_ref(h, tail, xz, live,
                                                        w, 1e-6))


def _retention_draws(T):
    q, k = _f(27, 2, T, 2, 8) + 0.7, _f(28, 2, T, 1, 8) + 0.7
    log_g = jnp.log(jnp.asarray(
        np.random.default_rng(29).uniform(0.7, 0.999, (2, T, 1)),
        jnp.float32))
    return q, k, _f(30, 2, T, 1, 8), log_g


def _retention_chunked():
    q, k, v, log_g = _retention_draws(12)
    return (lambda: power_retention.power_retention_chunked(q, k, v, log_g),
            lambda: power_retention.power_retention_chunked_ref(q, k, v,
                                                                log_g))


def _retention_step():
    _, st = power_retention.power_retention_chunked_ref(*_retention_draws(5))
    q, k, v, log_g = (a[:, 0] for a in _retention_draws(1))
    live = jnp.asarray([True, False])
    return (lambda: power_retention.power_retention_step(
                st["S"], st["z"], q, k, v, log_g, live),
            lambda: power_retention.power_retention_step_ref(
                st["S"], st["z"], q, k, v, log_g, live))


#: fallback name -> (family, module, the kernel's entry inside the
#: guarded call, a builder of (the public call, its jnp twin))
SITES = {
    "flash-attention": ("FLASH", flash_attention, "_flash_pallas",
                        _attention),
    "flash-decode": ("FLASH", flash_decode, "_flash_decode_pallas", _decode),
    "flash-decode-paged": ("FLASH", flash_decode,
                           "_flash_decode_paged_pallas", _decode_paged),
    "fused-ce": ("CE", fused_ce, "_run_fwd", _ce),
    "fused-norm": ("NORM", fused_norm, "_rms_pallas_fwd", _norm),
    "moe-grouped-matmul": ("MOE", grouped_matmul, "_grouped_matmul_pallas",
                           _grouped),
    "selective-scan": ("SCAN", selective_scan, "selective_scan_fwd", _scan),
    "ssm-state-update": ("SCAN", selective_scan, "_state_update",
                         _ssm_step),
    "power-retention-chunked": ("SCAN", power_retention,
                                "power_retention_chunked_fwd",
                                _retention_chunked),
    "power-retention-step": ("SCAN", power_retention, "_step",
                             _retention_step),
}


def _boom(*a, **k):
    raise RuntimeError("forced kernel failure")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """No switch set on the way in; the counters and the warn-once
    flags as they were on the way out (other files in this worker
    assert that nothing fell back)."""
    for fam in FAMILIES:
        monkeypatch.delenv(f"MXNET_TPU_{fam}_INTERPRET", raising=False)
        monkeypatch.delenv(f"MXNET_TPU_STRICT_{fam}", raising=False)
    monkeypatch.delenv("MXNET_TPU_STRICT_KERNELS", raising=False)
    kept = {n: (fb.count, fb._warned)
            for n, fb in dispatch._REGISTRY.items()}
    yield
    for n, (count, warned) in kept.items():
        dispatch._REGISTRY[n].count = count
        dispatch._REGISTRY[n]._warned = warned


def _broken(name, monkeypatch):
    """The site's public call and twin, its kernel interpreted and made
    to fail."""
    family, module, kernel, build = SITES[name]
    monkeypatch.setenv(f"MXNET_TPU_{family}_INTERPRET", "1")
    monkeypatch.setattr(module, kernel, _boom)
    return family, build()


def test_the_ten_sites_and_five_families_are_the_registrys():
    assert set(dispatch.fallback_counts()) == set(SITES)
    for name, (family, *_rest) in SITES.items():
        assert dispatch._REGISTRY[name].family == family
    assert {s[0] for s in SITES.values()} == set(FAMILIES)


@pytest.mark.parametrize("family", FAMILIES)
def test_off_the_chip_the_gate_says_none_and_its_switch_interpret(
        family, monkeypatch):
    x = jnp.ones((8, 128), jnp.float32)
    assert jax.default_backend() == "cpu"
    assert dispatch.kernel_mode(family, x) is None
    assert dispatch.kernel_mode(family) is None
    for other in FAMILIES:          # another family's switch is not its
        if other != family:
            monkeypatch.setenv(f"MXNET_TPU_{other}_INTERPRET", "1")
    assert dispatch.kernel_mode(family, x) is None
    monkeypatch.setenv(f"MXNET_TPU_{family}_INTERPRET", "1")
    assert dispatch.kernel_mode(family, x) == "interpret"
    # a kernel's own precondition wins over the switch; one that only
    # Mosaic has does not
    assert dispatch.kernel_mode(family, x, ok=False) is None
    assert dispatch.kernel_mode(family, x, ok_compiled=False) == "interpret"


@pytest.mark.parametrize("family", FAMILIES)
def test_an_operand_committed_to_the_cpu_never_gets_compiled(
        family, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    traced = jax.ShapeDtypeStruct((8, 128), jnp.float32)   # no devices
    assert dispatch.kernel_mode(family) == "compiled"
    assert dispatch.kernel_mode(family, traced) == "compiled"
    assert dispatch.kernel_mode(family, traced, ok_compiled=False) is None
    assert dispatch.kernel_mode(family, traced, ok=False) is None
    on_cpu = jnp.ones((8, 128), jnp.float32)
    assert dispatch.operand_on_cpu(on_cpu)
    assert dispatch.kernel_mode(family, on_cpu) is None
    monkeypatch.setenv(f"MXNET_TPU_{family}_INTERPRET", "1")
    assert dispatch.kernel_mode(family, on_cpu) == "interpret"


@pytest.mark.parametrize("name", sorted(SITES))
def test_with_its_switch_a_site_runs_its_kernel_and_counts_nothing(
        name, monkeypatch):
    family, _module, _kernel, build = SITES[name]
    call, twin = build()
    fb = dispatch._REGISTRY[name]
    before = fb.count
    monkeypatch.setenv(f"MXNET_TPU_{family}_INTERPRET", "1")
    monkeypatch.setenv(f"MXNET_TPU_STRICT_{family}", "1")   # would raise
    for got, want in zip(jax.tree.leaves(call()), jax.tree.leaves(twin())):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-4, atol=2e-4)
    assert fb.count == before


@pytest.mark.parametrize("name", sorted(SITES))
def test_a_failed_kernel_is_counted_warns_once_and_the_twin_answers(
        name, monkeypatch):
    _, (call, twin) = _broken(name, monkeypatch)
    fb = dispatch._REGISTRY[name]
    before, fb._warned = fb.count, False

    def op():                       # the op wrapper a model calls
        return call()

    def model():
        return op()

    with pytest.warns(RuntimeWarning, match=f"Pallas {name} kernel") as rec:
        out = model()
    assert len([r for r in rec if "falling back" in str(r.message)]) == 1
    # the warning names the model's line, not the kernel file's
    assert rec[0].filename == __file__
    assert fb.count == before + 1
    for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(twin())):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), rtol=1e-5,
                                   atol=1e-5)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # the second is silent
        call()
    assert fb.count == before + 2
    assert dispatch.fallback_counts()[name] == fb.count


@pytest.mark.parametrize("strict", ["family", "MXNET_TPU_STRICT_KERNELS"])
@pytest.mark.parametrize("name", sorted(SITES))
def test_a_strict_switch_makes_the_failure_fatal(name, strict, monkeypatch):
    family, (call, _) = _broken(name, monkeypatch)
    fb = dispatch._REGISTRY[name]
    before = fb.count
    for other in FAMILIES:          # another family's is not this one's
        if other != family:
            monkeypatch.setenv(f"MXNET_TPU_STRICT_{other}", "1")
    fb._warned = True
    call()
    assert fb.count == before + 1
    monkeypatch.setenv(f"MXNET_TPU_STRICT_{family}" if strict == "family"
                       else strict, "1")
    with pytest.raises(RuntimeError, match="forced kernel failure"):
        call()
    assert fb.count == before + 1


def test_on_a_tpu_backend_a_failure_raises_without_a_switch(monkeypatch):
    _, (call, _) = _broken("fused-norm", monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="forced kernel failure"):
        call()


def test_layernorm_and_the_attention_backward_are_guarded_too(monkeypatch):
    """The two sites that share a KernelFallback with another entry:
    fused_layernorm ("fused-norm") and the flash backward
    ("flash-attention")."""
    monkeypatch.setenv("MXNET_TPU_NORM_INTERPRET", "1")
    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    monkeypatch.setattr(fused_norm, "_ln_pallas_fwd", _boom)
    monkeypatch.setattr(flash_attention, "_pallas_backward", _boom)
    fused_norm._fallback._warned = flash_attention._fallback._warned = True
    x, g, b = _f(31, 4, 8), jnp.ones((8,)), jnp.zeros((8,))
    before = fused_norm._fallback.count
    out = fused_norm.fused_layernorm(x, g, b)
    assert fused_norm._fallback.count == before + 1
    np.testing.assert_allclose(
        out, (x - x.mean(-1, keepdims=True))
        * jax.lax.rsqrt(x.var(-1, keepdims=True) + 1e-5), rtol=1e-5,
        atol=1e-5)
    q = _f(32, 1, 128, 2, 8)
    before = flash_attention._fallback.count
    got = jax.grad(lambda a: flash_attention.flash_attention_raw(
        a, q, q).sum())(q)
    want = jax.grad(lambda a: flash_attention.reference_attention(
        a, q, q).sum())(q)
    assert flash_attention._fallback.count == before + 1
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    monkeypatch.setenv("MXNET_TPU_STRICT_FLASH", "1")
    with pytest.raises(RuntimeError, match="forced kernel failure"):
        jax.grad(lambda a: flash_attention.flash_attention_raw(
            a, q, q).sum())(q)


def test_registry_and_profiler_surface_counts():
    counts = dispatch.fallback_counts()
    assert "fused-norm" in counts and "flash-attention" in counts
    from mxnet_tpu import profiler
    s = profiler.summary()
    assert "kernel fallbacks:" in s and "fused-norm=" in s
