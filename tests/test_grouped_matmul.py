"""The grouped matmul of the expert layer and its gradients: the Pallas
kernels (interpreted) against `ragged_dot` against a per-expert loop,
and the whole held-expert layer against a dense per-pair definition,
forward and `jax.grad`."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.kernels import grouped_matmul as gm
from mxnet_tpu.kernels import tuning
from mxnet_tpu.parallel import moe

TM = 8


def _layout(counts, tm=TM, spare_tiles=2):
    """Rows sorted by group, each group's padded to whole tiles, as
    `held_expert_ffn` lays them out: (tile_group, n_tiles, row -> is it
    a real row, M)."""
    padded = [-(-c // tm) * tm for c in counts]
    tiles = sum(padded) // tm + spare_tiles
    tg, real = [], []
    for g, (c, p) in enumerate(zip(counts, padded)):
        tg += [g] * (p // tm)
        real += [True] * c + [False] * (p - c)
    n_tiles = len(tg)
    tg += [len(counts) - 1] * (tiles - n_tiles)
    real += [False] * (tiles * tm - len(real))
    return (jnp.asarray(tg, jnp.int32), jnp.asarray(n_tiles, jnp.int32),
            np.asarray(real), tiles * tm)


def _loop(lhs, rhs, rhs2, counts, tm=TM):
    """Each group's rows against its own matrix, one group at a time."""
    out, r0 = [], 0
    for g, c in enumerate(counts):
        p = -(-c // tm) * tm
        a = lhs[r0:r0 + p] @ rhs[g]
        if rhs2 is not None:
            a = jax.nn.silu(a) * (lhs[r0:r0 + p] @ rhs2[g])
        out.append(a)
        r0 += p
    out.append(jnp.zeros((lhs.shape[0] - r0, rhs.shape[2]), lhs.dtype))
    return jnp.concatenate(out)


# (rows of each group): whole tiles; ragged ends; a group with no row
# in the middle and at the end; one row in all
_CASES = {"whole_tiles": [8, 16, 8], "ragged": [3, 13, 9, 1],
          "empty_groups": [5, 0, 11, 0], "one_row": [0, 1, 0]}


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "swiglu"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_grad_through_grouped_matmul(case, fused, monkeypatch):
    """Forward, d lhs and d matrices: Pallas (interpreted) == ragged_dot
    == the per-expert loop. Rows no pair owns carry a zero cotangent,
    as the layer's combine gives them; the rows of dead tiles come back
    unwritten and are not compared."""
    monkeypatch.setenv("MXNET_TPU_MOE_INTERPRET", "1")
    counts = _CASES[case]
    G, K, N = len(counts), 16, 24
    tg, nt, real, M = _layout(counts)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    lhs = jax.random.normal(ks[0], (M, K))
    rhs = jax.random.normal(ks[1], (G, K, N)) * 0.3
    rhs2 = jax.random.normal(ks[2], (G, K, N)) * 0.3 if fused else None
    ct = jax.random.normal(ks[3], (M, N)) * real[:, None]
    live = np.arange(M) < int(nt) * TM

    def loss(fn):
        def f(lhs, rhs, rhs2):
            return jnp.sum(jnp.where(live[:, None], fn(lhs, rhs, rhs2),
                                     0.0) * ct)
        return f

    paths = {
        "pallas": lambda a, b, c: gm.grouped_matmul(a, b, tg, nt, TM,
                                                    rhs2=c),
        "ragged_dot": lambda a, b, c: gm.grouped_matmul(
            a, b, tg, nt, TM, rhs2=c, use_kernel=False),
        "loop": lambda a, b, c: _loop(a, b, c, counts)}
    before = gm._fallback.count
    got = {}
    for name, fn in paths.items():
        args = (0, 1, 2) if fused else (0, 1)
        out = np.where(live[:, None], fn(lhs, rhs, rhs2), 0.0)
        got[name] = (out,) + jax.grad(loss(fn), argnums=args)(
            lhs, rhs, rhs2)
    assert gm._fallback.count == before, "the kernels fell back"
    for name in ("pallas", "ragged_dot"):
        for i, (a, b) in enumerate(zip(got[name], got["loop"])):
            if i == 1:      # d lhs: dead tiles' rows are unwritten
                a, b = a[live], b[live]
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5,
                                       err_msg=f"{name} output {i}")
    for g, c in enumerate(counts):
        if c == 0:      # zero for a group no row reached
            assert not np.asarray(got["pallas"][2][g]).any()


def test_wgrad_tiles_of_one_group_accumulate(monkeypatch):
    """Three row tiles of one group sum into one (K, N) block; blocks
    narrower than the matrices (several grid steps either way)."""
    tuning.set_runtime("moe_grouped_matmul", "block_large", 128)
    try:
        tg, nt, real, M = _layout([20, 4])
        ks = jax.random.split(jax.random.PRNGKey(5), 2)
        lhs = jax.random.normal(ks[0], (M, 256))
        dy = jax.random.normal(ks[1], (M, 384)) * real[:, None]
        got = gm._grouped_wgrad_pallas(lhs, dy, tg, nt, 2, tm=TM,
                                       interpret=True)
    finally:
        tuning.clear_runtime()
    want = jnp.stack([lhs[:24].T @ dy[:24], lhs[24:32].T @ dy[24:32]])
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)


# -- the layer ---------------------------------------------------------------

def _experts(T=40, D=32, I=16, E=12, n=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (T, D)),
            jax.random.normal(ks[1], (E, D)) * 0.3,
            jax.random.normal(ks[2], (E,)) * 0.1,
            jax.random.normal(ks[3], (n, D, I)) * 0.2,
            jax.random.normal(ks[4], (n, D, I)) * 0.2,
            jax.random.normal(ks[5], (n, I, D)) * 0.2)


def _dense_layer(route, lo, k, scale):
    """Every held expert on every token, weighted by what the router
    gave it: the layer's definition, differentiable as written."""
    def f(x, rw, b, eg, eu, ed):
        sel, w = route(x, rw, b, k, scale)
        n = eg.shape[0]
        y = jnp.einsum("tni,nid->tnd",
                       jax.nn.silu(jnp.einsum("td,ndi->tni", x, eg))
                       * jnp.einsum("td,ndi->tni", x, eu), ed)
        on = jnp.sum(jnp.where(
            sel[:, :, None] == lo + jnp.arange(n), w[:, :, None], 0.0),
            axis=1)
        return jnp.einsum("tn,tnd->td", on, y)
    return f


@pytest.mark.parametrize("rule", ["sigmoid_bias", "softmax_normalised"])
@pytest.mark.parametrize("how", ["ragged_dot", "pallas", "pallas_chunked"])
def test_held_expert_layer_differentiates(rule, how, monkeypatch):
    """jax.grad of the held-expert layer against the dense definition,
    with respect to the tokens, the router and the three expert
    matrices, under both routing rules; through ragged_dot, through the
    Pallas kernels, and a chunk at a time with the chunk rebuilt in the
    backward (what a training step does)."""
    if how != "ragged_dot":
        monkeypatch.setenv("MXNET_TPU_MOE_INTERPRET", "1")
    route = moe.route_top_k if rule == "sigmoid_bias" \
        else moe.route_softmax_top_k
    x, rw, b, eg, eu, ed = _experts()
    b = b if rule == "sigmoid_bias" else None
    lo, k, scale = 4, 3, 1.5
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    kw = {}
    if how == "pallas_chunked":
        kw = dict(remat=True)
        monkeypatch.setattr(tuning, "_runtime", {
            ("moe_grouped_matmul", "chunk_rows_remat"): 8 * k})

    def layer(x, rw, eg, eu, ed):
        return moe.held_expert_ffn(x, rw, b, eg, eu, ed, lo=lo, top_k=k,
                                   route=route, route_scale=scale,
                                   **kw)[0]

    def dense(x, rw, eg, eu, ed):
        return _dense_layer(route, lo, k, scale)(x, rw, b, eg, eu, ed)

    args = (x, rw, eg, eu, ed)
    np.testing.assert_allclose(layer(*args), dense(*args), atol=2e-5)
    before = gm._fallback.count
    got = jax.grad(lambda *a: jnp.sum(layer(*a) * ct),
                   argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * ct),
                    argnums=range(5))(*args)
    assert gm._fallback.count == before
    for name, a, w_ in zip(("x", "router", "gate", "up", "down"), got,
                           want):
        np.testing.assert_allclose(a, w_, atol=3e-5, rtol=1e-4,
                                   err_msg=name)
    assert float(jnp.abs(want[1]).max()) > 1e-3     # the router learns


def test_counts_of_the_layer():
    x, rw, b, eg, eu, ed = _experts()
    sel, _ = moe.route_softmax_top_k(x, rw, None, 3, 1.0)
    _, pairs, touched, fullest = moe.held_expert_ffn(
        x, rw, None, eg, eu, ed, lo=4, top_k=3,
        route=moe.route_softmax_top_k)
    per = [int((np.asarray(sel) == e).sum()) for e in range(4, 8)]
    assert (int(pairs), int(touched), int(fullest)) == (
        sum(per), sum(c > 0 for c in per), max(per))
