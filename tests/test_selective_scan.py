"""The state-space kernels (kernels/selective_scan.py) at small sizes
on the CPU: the Pallas scan and the one-step state update, interpreted,
against their jnp twins; the scan over T against T single steps; an
initial state, right padding, a chunk boundary; the masked update."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from mxnet_tpu.kernels import dispatch  # noqa: E402
from mxnet_tpu.kernels import selective_scan as ss  # noqa: E402

N = 4


def inputs(B, T, Dn, seed=0, zero_state=False):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    a_log = jnp.log(jnp.broadcast_to(
        jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, Dn)))
    dt = jnp.asarray(rng.uniform(1e-3, 0.3, (B, T, Dn)), jnp.float32)
    h0 = jnp.zeros((B,) + ss.state_shape(N, Dn), jnp.float32) \
        if zero_state else f(B, *ss.state_shape(N, Dn))
    return f(B, T, Dn), dt, a_log, f(B, T, N), f(B, T, N), h0


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_SCAN_INTERPRET", "1")


@pytest.mark.parametrize("B,T,Dn,chunk", [
    (1, 8, 128, 256),        # one chunk, one lane row
    (2, 37, 256, 256),       # T no multiple of 8: padded with dt = 0
    (1, 40, 1024, 16),       # three chunks: the state crosses them
    (2, 33, 2048, 8),        # two channel blocks of 8 lane rows
])
def test_the_pallas_scan_equals_its_twin(B, T, Dn, chunk, interpret):
    x, dt, a_log, b, c, h0 = inputs(B, T, Dn, seed=T)
    y, h = ss.selective_scan_fwd(x, dt, a_log, b, c, h0, chunk=chunk,
                                 interpret=True)
    yr, hr = ss.selective_scan_ref(x, dt, a_log, b, c, h0)
    np.testing.assert_allclose(y, yr, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(h, hr, atol=2e-5, rtol=2e-5)
    assert h.shape == h0.shape and y.shape == x.shape


K, RK, EPS = 4, 8, 1e-6


def layer(Dn, seed=0, dtype=jnp.float32):
    """A recurrent layer's STEP_WEIGHTS (jamba_math's `lp` without its
    three big matrices)."""
    rng = np.random.default_rng(seed)
    f = lambda *s, by=1.0: jnp.asarray(  # noqa: E731
        rng.normal(size=s) * by, jnp.float32)
    w = {"conv_w": f(K, Dn, by=0.5), "conv_b": f(Dn, by=0.1),
         "x_proj": f(RK + 2 * N, Dn, by=Dn ** -0.5).astype(dtype),
         "dt_norm": (1 + f(RK, by=0.1)).astype(dtype),
         "b_norm": (1 + f(N, by=0.1)).astype(dtype),
         "c_norm": (1 + f(N, by=0.1)).astype(dtype),
         "dt_proj": f(Dn, RK, by=RK ** -0.5).astype(dtype),
         "dt_bias": f(Dn) - 3.0,
         "A_log": jnp.log(jnp.broadcast_to(
             jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, Dn))),
         "D": f(Dn)}
    assert set(w) == set(ss.STEP_WEIGHTS)
    return w


def rows_of(R, Dn, seed, dtype=jnp.float32, T=None):
    """h, tail and xz of R rows (xz over T positions where T is
    given)."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    xz = f(R, 2 * Dn) if T is None else f(R, T, 2 * Dn)
    return f(R, *ss.state_shape(N, Dn)), \
        f(R, *ss.tail_shape(K, Dn)).astype(dtype), xz.astype(dtype)


def prompt_form(w, h0, tail0, xz):
    """What `jamba_math.mixer` does between in_proj and out_proj over
    (B, T): the convolution from the carried tail, `ssm_inputs`, the
    scan, the gate. Returns g (B, T, Dn), the final state and tail."""
    B, T, Dn = xz.shape[0], xz.shape[1], xz.shape[2] // 2
    xr, z = xz[..., :Dn], xz[..., Dn:]
    xp = jnp.concatenate([tail0.reshape(B, K - 1, Dn), xr], axis=1)
    acc = w["conv_b"]
    for j in range(K):
        acc = acc + xp[:, j:j + T].astype(jnp.float32) * w["conv_w"][j]
    xc = jax.nn.silu(acc).astype(xz.dtype)
    dt, b, c = ss.ssm_inputs(w, xc, EPS)
    y, h = ss.selective_scan(xc, dt, w["A_log"], b, c, h0)
    return ss.gate(w, y, xc, z), h, xp[:, T:].reshape(B, -1)


@pytest.mark.parametrize("mode", ["twin", "interpreted"])
@pytest.mark.parametrize("zero_state", [True, False])
def test_a_scan_over_t_is_t_single_steps(mode, zero_state, monkeypatch):
    """Prefill and decode compute one layer: the prompt form's g, final
    state and tail equal T calls of the one-call step, from a zero and
    from a nonzero initial state."""
    if mode == "interpreted":
        monkeypatch.setenv("MXNET_TPU_SCAN_INTERPRET", "1")
    B, T, Dn = 3, 11, 256
    w = layer(Dn, 3)
    h0, tail0, xz = rows_of(B, Dn, 3, T=T)
    if zero_state:
        h0, tail0 = jnp.zeros_like(h0), jnp.zeros_like(tail0)
    g, h, tail = prompt_form(w, h0, tail0, xz)
    hs, ts, live = h0, tail0, jnp.ones((B,), bool)
    for t in range(T):
        gt, hs, ts = ss.ssm_state_update(hs, ts, xz[:, t], live, w, EPS)
        np.testing.assert_allclose(gt, g[:, t], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(hs, h, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(ts, tail)
    assert sum(dispatch.fallback_counts()[k] for k in
               ("selective-scan", "ssm-state-update")) == 0


@pytest.mark.parametrize("valid", [1, 5, 16, 21])
def test_right_padding_does_not_advance_the_state(valid, interpret):
    """dt = 0 past a row's length: the final state is the one at
    `valid`, whatever the padding holds."""
    x, dt, a_log, b, c, h0 = inputs(1, 21, 256, 9)
    dt_masked = jnp.where(jnp.arange(21)[None, :, None] < valid, dt, 0.0)
    _, h = ss.selective_scan(x, dt_masked, a_log, b, c, h0)
    _, h_cut = ss.selective_scan(x[:, :valid], dt[:, :valid], a_log,
                                 b[:, :valid], c[:, :valid], h0)
    np.testing.assert_allclose(h, h_cut, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("R,want,active", [
    (6, 8, [1, 0, 1, 1, 0, 1]),      # 6 rows a step
    (8, 4, [0, 0, 0, 0, 1, 1, 1, 1]),  # two steps, one all idle
    (5, 8, [1, 1, 1, 1, 1]),
    (3, 2, [0, 0, 0]),               # a prime: one row a step
    (32, 16, [1, 1, 0, 1] * 8),      # groups of 16 rows, two steps
])
def test_the_masked_state_update_equals_its_twin(R, want, active, dtype,
                                                 interpret):
    """The one-call step against its twin for g, h' and tail': an
    inactive row's state and tail come back bit for bit; an active
    row's equal the twin's; the rows a step divide the pools. In
    bfloat16 the call keeps float32 where the twin rounds x_proj's and
    dt_proj's products, B and C to the model's dtype."""
    rb = ss._rows_per_step(R, want)
    assert R % rb == 0 and rb <= want
    w = layer(384, R, dtype)
    h, tail, xz = rows_of(R, 384, R, dtype)
    act = jnp.asarray(active, bool)
    g, hn, tn = ss._state_update(h, tail, xz, act, w, eps=EPS,
                                 rows_per_step=rb, interpret=True)
    gr, hr, tr = ss.ssm_state_update_ref(h, tail, xz, act, w, EPS)
    assert g.dtype == dtype and tn.dtype == dtype \
        and hn.dtype == jnp.float32
    assert g.shape == (R, 384) and tn.shape == tail.shape \
        and hn.shape == h.shape
    tol = 2e-5 if dtype == jnp.float32 else 0.08
    np.testing.assert_allclose(hn, hr, atol=tol / 2, rtol=tol)
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(gr, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_array_equal(np.asarray(tn, np.float32),
                                  np.asarray(tr, np.float32))
    idle = ~np.asarray(act)
    assert np.array_equal(np.asarray(hn)[idle], np.asarray(h)[idle])
    assert np.array_equal(np.asarray(tn, np.float32)[idle],
                          np.asarray(tail, np.float32)[idle])
    # a live row's tail moved up by one tap and took the new input
    Dn = 384
    live = np.asarray(act)
    assert np.array_equal(np.asarray(tn, np.float32)[live, :2 * Dn],
                          np.asarray(tail, np.float32)[live, Dn:])
    assert np.array_equal(np.asarray(tn, np.float32)[live, 2 * Dn:],
                          np.asarray(xz, np.float32)[live, :Dn])


def test_idle_rows_keep_state_and_tail_over_steps(interpret):
    """Through the public call, with every row idle but one: the idle
    rows' `h` and `tail` are the bytes that went in, T steps on."""
    R, Dn = 4, 256
    w = layer(Dn, 1)
    h, tail, xz = rows_of(R, Dn, 5, T=3)
    act = jnp.asarray([0, 1, 0, 0], bool)
    hs, ts = h, tail
    for t in range(3):
        _, hs, ts = ss.ssm_state_update(hs, ts, xz[:, t], act, w, EPS)
    for new, old in ((hs, h), (ts, tail)):
        new, old = np.asarray(new), np.asarray(old)
        assert np.array_equal(new[[0, 2, 3]], old[[0, 2, 3]])
        assert not np.array_equal(new[1], old[1])
    assert dispatch.fallback_counts()["ssm-state-update"] == 0


def test_a_width_that_is_no_lane_row_takes_the_twin():
    with pytest.raises(ValueError, match="128-lane"):
        ss.state_shape(N, 100)
    assert ss.tail_shape(4, 5120) == (15360,)
    assert ss._channel_rows(100) == 0 and ss._channel_rows(5120) == 8 \
        and ss._channel_rows(384) == 3


def test_both_kernels_are_counted_among_the_fallbacks():
    counts = dispatch.fallback_counts()
    assert {"selective-scan", "ssm-state-update"} <= set(counts)
