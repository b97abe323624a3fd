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


@pytest.mark.parametrize("mode", ["twin", "interpreted"])
@pytest.mark.parametrize("zero_state", [True, False])
def test_a_scan_over_t_is_t_single_steps(mode, zero_state, monkeypatch):
    """Prefill and decode compute one recurrence: the scan's y and
    final state equal T calls of the one-step update, from a zero and
    from a nonzero initial state."""
    if mode == "interpreted":
        monkeypatch.setenv("MXNET_TPU_SCAN_INTERPRET", "1")
    B, T, Dn = 3, 11, 256
    x, dt, a_log, b, c, h0 = inputs(B, T, Dn, 3, zero_state)
    y, h = ss.selective_scan(x, dt, a_log, b, c, h0)
    hs, live = h0, jnp.ones((B,), bool)
    for t in range(T):
        hs, yt = ss.ssm_state_update(hs, x[:, t], dt[:, t], a_log,
                                     b[:, t], c[:, t], live)
        np.testing.assert_allclose(yt, y[:, t], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(hs, h, atol=2e-5, rtol=2e-5)
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


@pytest.mark.parametrize("R,want,active", [
    (6, 8, [1, 0, 1, 1, 0, 1]),      # 6 rows a step
    (8, 4, [0, 0, 0, 0, 1, 1, 1, 1]),  # two steps, one all idle
    (5, 8, [1, 1, 1, 1, 1]),
    (3, 2, [0, 0, 0]),               # a prime: one row a step
])
def test_the_masked_state_update_equals_its_twin(R, want, active,
                                                 interpret):
    """An inactive row's state comes back bit for bit; an active row's
    equals the twin's; the rows a step divide the pool."""
    rb = ss._rows_per_step(R, want)
    assert R % rb == 0 and rb <= want
    x, dt, a_log, b, c, h = inputs(R, 1, 384, R)
    act = jnp.asarray(active, bool)
    hn, y = ss._state_update(h, x[:, 0], dt[:, 0], a_log, b[:, 0],
                             c[:, 0], act, rows_per_step=rb,
                             interpret=True)
    hr, yr = ss.ssm_state_update_ref(h, x[:, 0], dt[:, 0], a_log,
                                     b[:, 0], c[:, 0], act)
    np.testing.assert_allclose(hn, hr, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(y, yr, atol=2e-5, rtol=2e-5)
    idle = ~np.asarray(act)
    assert np.array_equal(np.asarray(hn)[idle], np.asarray(h)[idle])
    assert not np.asarray(y)[idle].any()


def test_a_width_that_is_no_lane_row_takes_the_twin():
    with pytest.raises(ValueError, match="128-lane"):
        ss.state_shape(N, 100)
    assert ss._channel_rows(100) == 0 and ss._channel_rows(5120) == 8 \
        and ss._channel_rows(384) == 3


def test_both_kernels_are_counted_among_the_fallbacks():
    counts = dispatch.fallback_counts()
    assert {"selective-scan", "ssm-state-update"} <= set(counts)
