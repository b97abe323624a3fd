"""Brumby (power retention: every layer a gated linear attention with a
degree-2 feature map) through the normal serving path, against the
plain float32 reference (perfbench/reference/retention_decoder.py: the
QUADRATIC form, no state), at tiny sizes on the CPU (head_dim 16: 136
distinct products, 144 as stored): the feature map, the recurrence
against the quadratic form, both kernels against their twins, the
net's forward, served LOGITS over staggered admissions, a freed and
reused slot, a preemption, an idle row, the cache with no block pool,
what the server refuses, the reference's controls, and the tiny
rehearsal of the benchmark's cell."""
import copy
import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.kernels import dispatch, tuning  # noqa: E402
from mxnet_tpu.kernels import power_retention as pr  # noqa: E402
from mxnet_tpu.models import retention_math  # noqa: E402
from mxnet_tpu.serving import InferenceServer  # noqa: E402
from mxnet_tpu.serving.kv_cache import PagedKVCache  # noqa: E402
from perfbench import control_check, harness, rehearse  # noqa: E402
from perfbench.reference import retention_decoder as ref  # noqa: E402

KERNELS = ("SCAN",)
CELL = "brumby_14b.longctx20"


def tiny_cfg(**over):
    """The benchmark's configuration file under its tiny preset: three
    layers, 4 heads on 2 kv heads of 16."""
    cfg = rehearse.merge(
        harness.load_json(harness.HERE, "configs", "brumby_14b.json"),
        harness.load_json(harness.HERE, "rehearsal.brumby.json")["config"])
    cfg.update(over)
    return cfg


@pytest.fixture
def interpret(monkeypatch):
    for k in KERNELS:
        monkeypatch.setenv(f"MXNET_TPU_{k}_INTERPRET", "1")


def build_server(cfg, seed, **spec):
    from perfbench.families import retention_decoder as family

    spec = dict({"batch_slots": 4, "max_len": 96, "max_prompt_len": 48,
                 "kv_cache_dtype": "model"}, **spec)
    return family.build(cfg, spec, seed, jax.devices()[:1])


SEED = 11


@pytest.fixture(scope="module")
def shared():
    """One server for the tests that serve (a build compiles prefill
    and decode: ~10 s): three slots, seed 11, the retention kernels
    interpreted. Every test leaves it drained, so the next starts from
    empty slots; a prefill overwrites a slot's state whole."""
    os.environ["MXNET_TPU_SCAN_INTERPRET"] = "1"
    try:
        served = build_server(tiny_cfg(), SEED, batch_slots=3, max_len=128,
                              max_prompt_len=64)
        served.submit(np.arange(5), 3)      # trace both programs now
        served.server.run()
    finally:
        del os.environ["MXNET_TPU_SCAN_INTERPRET"]
    assert sum(dispatch.fallback_counts().values()) == 0
    return served


def reference_logits(cfg, seed, ids, control=None):
    with jax.default_matmul_precision("highest"):
        xs, watch = ref.forward(cfg, seed, ids, q_block=64,
                                control=control)
        ends = ref.Weights(cfg, seed).ends()
        return [np.asarray(ref._rms(x.astype(jnp.float32),
                                    ends["norm"].astype(jnp.float32),
                                    cfg["rms_norm_eps"])
                           @ ends["head"].astype(jnp.float32).T)
                for x in xs], watch


def draws(rng, B, T, H, K, d, dtype=jnp.float32):
    """q and k lean one way, so that no (q . k)^2 is the near-zero
    difference of the map's large products: what is compared is the
    arithmetic, not the conditioning of a ratio."""
    q = jnp.asarray(rng.normal(size=(B, T, H, d)) + 0.7, dtype)
    k = jnp.asarray(rng.normal(size=(B, T, K, d)) + 0.7, dtype)
    v = jnp.asarray(rng.normal(size=(B, T, K, d)), dtype)
    log_g = jnp.asarray(np.log(rng.uniform(0.7, 0.999, (B, T, K))),
                        jnp.float32)
    return q, k, v, log_g


def quadratic(q, k, v, log_g, eps=pr.EPS):
    """a_{t,s} = (q_t . k_s)^2 prod_{r=s+1..t} g_r as written: no
    state, no feature map."""
    B, T, H, d = q.shape
    G = H // k.shape[2]
    kk, vv = (jnp.repeat(a.astype(jnp.float32), G, axis=2) for a in (k, v))
    cum = jnp.cumsum(jnp.repeat(log_g, G, axis=2), axis=1)   # (B, T, H)
    sc = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), kk) ** 2
    cum = jnp.moveaxis(cum, 2, 1)
    a = jnp.where(jnp.tril(jnp.ones((T, T), bool)),
                  sc * jnp.exp(cum[..., :, None] - cum[..., None, :]), 0.0)
    num = jnp.einsum("bhts,bshd->bthd", a, vv)
    return num / (jnp.moveaxis(a.sum(-1), 1, 2)[..., None] + eps)


# -- (1) the feature map and the recurrence ------------------------------------

@pytest.mark.parametrize("d", [16, 128])
def test_the_feature_map_squares_the_inner_product(d):
    rng = np.random.default_rng(d)
    u, w = (jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
            for _ in range(2))
    pu, pw = pr.feature_map(u), pr.feature_map(w)
    O = d // 2 + 1
    assert pu.shape == (5, O, d)
    # d (d + 1) / 2 distinct products in O x d entries: offset d / 2
    # holds its d / 2 pairs twice
    assert O * d - d // 2 == d * (d + 1) // 2
    # (u . w)^2 cancels among d^2 products of size |u|^2 |w|^2 / d
    size = float(jnp.max(jnp.sum(u * u, 1) * jnp.sum(w * w, 1)))
    np.testing.assert_allclose(jnp.sum(pu * pw, axis=(1, 2)),
                               jnp.sum(u * w, axis=1) ** 2,
                               atol=1e-6 * size)
    assert pr.state_shapes(8, 128) == {
        "S": ((8, 65, 128, 128), jnp.float32),
        "z": ((8, 72, 128), jnp.float32)}
    with pytest.raises(ValueError, match="odd"):
        pr.state_shapes(2, 15)


def test_the_recurrence_equals_the_quadratic_form():
    rng = np.random.default_rng(0)
    q, k, v, log_g = draws(rng, 2, 37, 4, 2, 16)
    y, state = pr.power_retention_chunked_ref(q, k, v, log_g)
    np.testing.assert_allclose(y, quadratic(q, k, v, log_g), atol=1e-5,
                               rtol=1e-4)
    # the state is the sum the recurrence says: the last position's
    # answer from it is the quadratic form's last row
    again = pr._answer(pr.feature_map(q[:, -1].reshape(2, 2, 2, 16)),
                       state["S"], state["z"], pr.EPS).reshape(2, 4, 16)
    np.testing.assert_allclose(again, y[:, -1], atol=1e-6, rtol=1e-5)
    # the rows of z past the map's own stay 0
    assert not np.asarray(state["z"][:, :, 9:]).any()


# -- (2) the kernels against their twins ---------------------------------------

@pytest.mark.parametrize("T,chunk", [(1, 8), (8, 8), (21, 8), (37, 16),
                                     (40, 256)])
def test_the_chunked_kernel_equals_its_twin(T, chunk, interpret):
    """float32 in, float32 products: the same sums in another order
    (1e-5), over chunk edges, a ragged last chunk, a length of 1 and a
    prompt shorter than one chunk."""
    rng = np.random.default_rng(T)
    q, k, v, log_g = draws(rng, 2, T, 4, 2, 16)
    eps = 0.25 if T == 21 else pr.EPS
    tuning.set_runtime("power_retention_chunked", "chunk", chunk)
    try:
        y, state = pr.power_retention_chunked(q, k, v, log_g, eps=eps)
    finally:
        tuning.clear_runtime()
    want, wstate = pr.power_retention_chunked_ref(q, k, v, log_g, eps)
    if eps != pr.EPS:
        assert float(jnp.abs(want - pr.power_retention_chunked_ref(
            q, k, v, log_g)[0]).max()) > 1e-4
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(y, want, atol=1e-5 * max(scale, 1.0),
                               rtol=5e-5)
    for name in ("S", "z"):
        big = float(jnp.abs(wstate[name]).max())
        np.testing.assert_allclose(state[name], wstate[name],
                                   atol=1e-5 * big, rtol=1e-5)
    assert sum(dispatch.fallback_counts().values()) == 0


def test_the_chunked_kernel_in_bfloat16(interpret):
    """bfloat16 in: q k^T, phi, the decayed scores and the state enter
    their products rounded to 8 bits of mantissa (the twin keeps them
    float32), so y agrees to a few parts in a hundred of its size and
    the state, a sum of some forty such terms, to about one."""
    rng = np.random.default_rng(9)
    q, k, v, log_g = draws(rng, 1, 40, 4, 2, 16, jnp.bfloat16)
    tuning.set_runtime("power_retention_chunked", "chunk", 16)
    try:
        y, state = pr.power_retention_chunked(q, k, v, log_g)
    finally:
        tuning.clear_runtime()
    assert y.dtype == jnp.bfloat16 and state["S"].dtype == jnp.float32
    want, wstate = pr.power_retention_chunked_ref(q, k, v, log_g)
    np.testing.assert_allclose(y.astype(jnp.float32), want, atol=5e-2,
                               rtol=5e-2)
    big = float(jnp.abs(wstate["S"]).max())
    np.testing.assert_allclose(state["S"], wstate["S"], atol=2e-2 * big)


def test_padding_with_no_key_and_no_gate_holds_the_state(interpret):
    """Right padding: a zero key and a log-gate of 0 leave the state as
    the valid positions left it (what retention_layer does with
    `lengths`)."""
    rng = np.random.default_rng(4)
    q, k, v, log_g = draws(rng, 1, 24, 4, 2, 16)
    n = 13
    kp = k.at[:, n:].set(0.0)
    gp = log_g.at[:, n:].set(0.0)
    _, padded = pr.power_retention_chunked(q, kp, v, gp)
    _, exact = pr.power_retention_chunked_ref(q[:, :n], k[:, :n],
                                              v[:, :n], log_g[:, :n])
    for name in ("S", "z"):
        np.testing.assert_allclose(padded[name], exact[name], atol=1e-5,
                                   rtol=1e-5)


@pytest.mark.parametrize("heads,kv_heads,d,dtype,tol", [
    (4, 2, 16, jnp.float32, 1e-5), (5, 1, 16, jnp.float32, 1e-5),
    (6, 3, 8, jnp.float32, 1e-5),       # (this one at another eps)
    # bfloat16 q, k, v: both sides widen them first and work in
    # float32, so only y's own rounding to bfloat16 is left
    (4, 2, 16, jnp.bfloat16, 1e-2)])
def test_the_step_kernel_equals_its_twin(heads, kv_heads, d, dtype, tol,
                                         interpret):
    rng = np.random.default_rng(heads * d)
    q, k, v, log_g = draws(rng, 3, 12, heads, kv_heads, d)
    _, state = pr.power_retention_chunked_ref(q, k, v, log_g)
    q1, k1, v1, lg1 = (a[:, 0] for a in draws(rng, 3, 1, heads, kv_heads,
                                              d, dtype))
    active = jnp.asarray([True, False, True])
    eps = 0.25 if d == 8 else pr.EPS
    S, z, y = pr.power_retention_step(state["S"], state["z"], q1, k1, v1,
                                      lg1, active, eps=eps)
    wS, wz, wy = pr.power_retention_step_ref(state["S"], state["z"], q1,
                                             k1, v1, lg1, active, eps)
    if eps != pr.EPS:
        assert float(jnp.abs(wy - pr.power_retention_step_ref(
            state["S"], state["z"], q1, k1, v1, lg1, active)[2]).max()) \
            > 1e-4
    big = float(jnp.abs(wS).max())
    np.testing.assert_allclose(S, wS, atol=1e-5 * big, rtol=1e-5)
    np.testing.assert_allclose(z, wz, atol=1e-5 * big, rtol=1e-5)
    np.testing.assert_allclose(y.astype(jnp.float32), wy, atol=tol,
                               rtol=tol)
    # the idle row: bit for bit, and it answers 0
    assert np.array_equal(S[1], state["S"][1])
    assert np.array_equal(z[1], state["z"][1])
    assert not np.asarray(y[1]).any()
    assert not np.array_equal(S[0], state["S"][0])
    assert sum(dispatch.fallback_counts().values()) == 0


@pytest.fixture(scope="module")
def tiny_layer():
    """(configuration, first layer's parameters) of one `brumby_tiny`
    for the tests of the layer's mathematics."""
    net = mx.models.get_model("brumby_tiny")
    net.initialize(init=mx.init.Normal(0.2))
    return net.model.cfg, net.decoder().params_tree(net)["layers"][0]


def test_the_step_and_the_prompt_form_are_one_layer(tiny_layer, interpret):
    """retention_math: a layer over T positions equals T single-token
    steps from a zero state at the same positions, and ends in the same
    state; without the positions it does not."""
    cfg, lp = tiny_layer
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 6, 64)), jnp.float32)
    whole, st = jax.jit(lambda a: retention_math.retention_layer(
        lp, a, cfg, jnp.arange(6)))(x)
    live = jnp.ones((2,), bool)
    step = jax.jit(lambda xt, p, s: retention_math.retention_layer_step(
        lp, xt, cfg, p, s, live))
    state = retention_math.zero_state(cfg, 2)
    for t in range(6):
        y, state = step(x[:, t:t + 1], jnp.full((2,), t), state)
        # (a normaliser of these random heads can be a small
        # difference of large products: 1e-3 of outputs of size ~3)
        np.testing.assert_allclose(y[:, 0], whole[:, t], atol=2e-3,
                                   rtol=2e-3)
    for name in st:
        big = float(jnp.abs(st[name]).max())
        np.testing.assert_allclose(state[name], st[name],
                                   atol=2e-5 * big, rtol=2e-5)
    y0, _ = step(x[:, 5:6], jnp.zeros((2,), jnp.int32), state)
    assert float(jnp.abs(y0[:, 0] - whole[:, 5]).max()) > 5e-2


def test_the_configurations_eps_reaches_both_forms(tiny_layer):
    """`retention_eps` is the configuration's, not a constant of the
    kernels: a layer under another eps equals the quadratic form under
    that eps, whole and step by step, and not the one under 1e-6 (the
    twins here; the kernels' tests above take an eps of their own)."""
    cfg, lp = tiny_layer
    cfg = copy.copy(cfg)
    cfg.retention_eps = 0.5
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1, 3, 64)), jnp.float32)
    whole, _ = jax.jit(lambda a: retention_math.retention_layer(
        lp, a, cfg, jnp.arange(3)))(x)
    step = jax.jit(lambda xt, p, s: retention_math.retention_layer_step(
        lp, xt, cfg, p, s, jnp.ones((1,), bool)))
    state = retention_math.zero_state(cfg, 1)
    for t in range(3):
        y, state = step(x[:, t:t + 1], jnp.full((1,), t), state)
        np.testing.assert_allclose(y[:, 0], whole[:, t], atol=1e-4,
                                   rtol=1e-4)

    @functools.partial(jax.jit, static_argnums=0)
    def layer_out(eps):
        u = retention_math.rms(x, lp["ln_in"], cfg.rms_eps)
        q, k, v, log_g = retention_math.retention_inputs(
            lp, u, cfg, jnp.arange(3))
        y = quadratic(q, k, v, log_g, eps).reshape(1, 3, -1)
        return retention_math._feed_forward(lp, x + y @ lp["wo"].T, cfg)

    np.testing.assert_allclose(whole, layer_out(0.5), atol=1e-4,
                               rtol=1e-4)
    assert float(jnp.abs(whole - layer_out(pr.EPS)).max()) > 1e-2
    assert sum(dispatch.fallback_counts().values()) == 0


def test_a_degree_other_than_2_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="retention_degree 3"):
        mx.models.get_model("brumby_tiny", retention_degree=3)


def test_the_family_hands_the_configurations_eps_and_degree_over():
    """The benchmark's family builds the net from the configuration
    file's `retention_eps` and `retention_degree`: editing the data file
    changes the program as it changes the reference."""
    served = build_server(tiny_cfg(retention_eps=0.125), SEED)
    got = served.server.net.model.cfg
    assert got.retention_eps == 0.125 and got.retention_degree == 2
    with pytest.raises(NotImplementedError, match="retention_degree"):
        build_server(tiny_cfg(retention_degree=1), SEED)


def test_the_bf16_rounding_survives_a_jit():
    """`bf16_round` inside `jax.jit` and `lax.scan` rounds for real: a
    sum of ones kept that way stops at 256 (257 is no bfloat16 and ties
    go to the even mantissa), where a float32 carry reads the count. A
    cast there and back may be dropped by XLA as excess precision."""
    def count(r):
        return jax.jit(lambda: jax.lax.scan(
            lambda z, _: (r(z + 1.0), None), jnp.float32(0), None,
            length=600)[0])()

    assert float(count(ref.bf16_round)) == 256.0
    assert float(count(lambda a: a)) == 600.0
    assert float(jax.jit(ref.bf16_round)(jnp.float32(1 + 2 ** -9))) == 1.0
    assert float(jax.jit(ref.bf16_round)(jnp.float32(3.0e38))) \
        == float(jnp.float32(3.0e38).astype(jnp.bfloat16))


# -- (3) the net and the published sizes ----------------------------------------

def test_the_net_forward_equals_the_reference(shared):
    """float32: the recurrence's sums against the quadratic form's, on
    logits of size ~0.3."""
    cfg = tiny_cfg()
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    got = shared.server.net(mx.nd.array(ids, dtype="int32")).asnumpy()
    want, watch = reference_logits(cfg, SEED, list(ids))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g.astype(np.float32), w[:24],
                                   atol=2e-4, rtol=2e-4)
    assert 1e-3 < watch["stream_rms"] < 1e2


def test_the_published_sizes_and_the_description():
    net = mx.models.get_model("brumby")         # the published sizes
    cfg = net.model.cfg
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim) == (40, 5120, 40, 8, 128)
    shapes = {n: p.shape for n, p in net.collect_params().items()}
    assert shapes["model.layers.0.wq"] == (5120, 5120)
    assert shapes["model.layers.0.wk"] == (1024, 5120)
    assert shapes["model.layers.0.wg"] == (8, 5120)
    assert shapes["model.layers.39.bg"] == (8,)
    assert shapes["lm_head.weight"] == (151936, 5120)
    layer = sum(int(np.prod(s)) for n, s in shapes.items()
                if n.startswith("model.layers.0."))
    assert layer == 330_352_904                 # 330.35M: 660.7 MB
    n_params = sum(int(np.prod(s)) for s in shapes.values())
    assert abs(n_params - 14.77e9) < 0.01e9
    dec = net.decoder()
    assert dec.layer_kinds == ("recurrent",) * 40
    assert dec.recurrent and not dec.mixed \
        and not dec.latent and dec.supports == frozenset()
    st = dec.state_shapes()
    assert st["S"] == ((8, 65, 128, 128), jnp.float32)
    assert st["z"] == ((8, 72, 128), jnp.float32)
    stored = sum(int(np.prod(s)) * 4 for s, _ in st.values())
    assert stored == 34_373_632                 # 34.08 MB needed: +0.9%
    assert "brumby_tiny" in mx.models.list_models()


# -- (4) served through the state pool, no block pool ---------------------------

def test_served_logits_match_the_reference(shared):
    """Prefill, then decode through the state pool, against the
    reference's one full forward of the QUADRATIC form: every greedy
    token is the reference's first at its position and every sampled
    one among its top 20; staggered admissions, greedy and sampled rows
    in one batch, more requests than slots (a slot is freed and
    reused); the kernels interpreted (the jnp twins serve the tests
    that set nothing)."""
    cfg, served = tiny_cfg(), shared
    srv = served.server
    rng = np.random.default_rng(5)
    sampling = {"temperature": 0.7, "top_k": 20, "top_p": 0.9}
    mix = [(6, 12), (17, 20), (40, 9), (23, 14), (9, 16)]
    reqs = []
    for i, (n, new) in enumerate(mix):
        reqs.append(served.submit(
            rng.integers(0, cfg["vocab_size"], n), new,
            sampling if i % 2 else None, seed=i))
        srv.step()
        srv.cache.check()
    while served.busy():
        srv.step()
        srv.cache.check()
    assert all(served.ok(r) for r in reqs)
    assert srv.compile_stats()["prefill_compiles"] == 1
    assert srv.compile_stats()["decode_compiles"] == 1
    assert srv.cache.state_slots_used == 0
    assert srv.cache.num_used_blocks == 0
    assert sum(dispatch.fallback_counts().values()) == 0
    gaps = ref.served_token_gaps(
        cfg, SEED, [served.tokens(r) for r in reqs if r.temperature == 0],
        q_block=64)
    assert sum(len(g) for g in gaps) == 12 + 9 + 16
    assert max(float(g.max()) for g in gaps) < 1e-4
    # a sampled request's tokens: every one is among the reference's
    # top-20 at its position
    sampled = [r for r in reqs if r.temperature > 0]
    ids = [np.concatenate([r.prompt, r.output_tokens])[:-1]
           for r in sampled]
    logits, _ = reference_logits(cfg, SEED, ids)
    for r, lg in zip(sampled, logits):
        n = len(r.prompt)
        for j, tok in enumerate(r.output_tokens):
            assert tok in np.argsort(lg[n - 1 + j])[-20:]


def test_served_logits_to_a_tolerance_the_bf16_state_fails(shared):
    """LOGITS, not tokens: the decode program's rows (`_last_logits`,
    read when nothing is in flight) against the reference's, to 2e-4 of
    logits of size ~0.3; the reference's `state_bf16` control, held to
    the same comparison, misses it by an order of magnitude."""
    cfg, srv = tiny_cfg(), shared.server
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg["vocab_size"], 48)
    req = srv.submit(prompt, max_new_tokens=16)
    rows = []
    while req.status is None:
        before = len(req.output_tokens)
        srv.step()
        if srv._slot_req[0] is req and len(req.output_tokens) > before:
            rows.append(np.asarray(srv._last_logits[0], np.float32))
    ids = np.concatenate([prompt, req.output_tokens])[:-1]
    want, _ = reference_logits(cfg, SEED, [ids])
    rounded, _ = reference_logits(cfg, SEED, [ids], control="state_bf16")
    n = len(prompt)
    # when token j is handed over, the tick behind it is in flight: it
    # sampled token j + 1 from the row at n + j - 1 and computed the
    # row at n + j; the last two launches are one and the same
    got = np.stack(rows[:14])
    exact = want[0][n + 1:n + 1 + len(got)]
    np.testing.assert_allclose(got, exact, atol=2e-4, rtol=2e-4)
    miss = np.abs(rounded[0][n + 1:n + 1 + len(got)] - exact).max()
    assert miss > 2e-3, miss


def test_a_freed_slot_starts_from_a_zero_state(shared):
    """One slot, three requests one after another (each alone on the
    server, so each takes slot 0): the second and the
    third get the state of nobody (the prefill overwrites the row
    whole; a stale state would move their logits off the reference's,
    which starts every sequence at 0)."""
    cfg = tiny_cfg()
    rng = np.random.default_rng(8)
    served, reqs = shared, []
    for n in (30, 7, 19):
        reqs.append(served.submit(
            rng.integers(0, cfg["vocab_size"], n), 10))
        served.server.step()
        assert served.server._slot_req[0] is reqs[-1]
        served.server.run()
    assert all(served.ok(r) for r in reqs)
    gaps = ref.served_token_gaps(cfg, SEED,
                                 [served.tokens(r) for r in reqs],
                                 q_block=64)
    assert max(float(g.max()) for g in gaps) < 1e-4
    # and the yardstick sees a stale state: the second request's tokens
    # scored behind the first's prompt read a gap
    stale = ref.served_token_gaps(
        cfg, SEED, [(np.concatenate([reqs[0].prompt, reqs[1].prompt]),
                  reqs[1].output_tokens)], q_block=64)
    assert float(stale[0].max()) > 1e-3


def test_a_preemption_reruns_the_prefill(shared):
    """Nothing runs out in a cache with no pool, so nothing preempts by
    itself; a preemption asked for (what a deadline policy or a router
    would do) frees the slot's row, requeues the request at the head,
    and the rerun prefills the state anew: greedy tokens equal an
    undisturbed run's."""
    cfg = tiny_cfg()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg["vocab_size"], n) for n in (10, 12, 9)]

    served, srv = shared, shared.server
    before = srv.preemptions

    def run(preempt_at):
        reqs = [served.submit(p, 30, seed=i)
                for i, p in enumerate(prompts)]
        ticks = 0
        while served.busy():
            served.step()
            srv.cache.check()
            ticks += 1
            if ticks == preempt_at:
                assert srv._preempt_youngest(asker=0)
                srv.cache.check()
                assert srv.cache.state_slots_used == 2
        assert srv.cache.state_slots_used == 0
        return [list(r.output_tokens) for r in reqs], reqs, \
            srv.compile_stats()["prefill_calls"]

    calls = srv.compile_stats()["prefill_calls"]
    calm, _, calls0 = run(None)
    assert srv.preemptions == before
    shaken, reqs, calls1 = run(7)
    assert srv.preemptions == before + 1
    assert [r.preemptions for r in reqs] == [0, 0, 1]
    assert (calls0 - calls, calls1 - calls0) == (3, 4)  # one more
    assert [r.status for r in reqs] == ["ok"] * 3
    assert shaken == calm


def test_an_idle_row_keeps_its_state(shared):
    """The decode program's masked update: rows not in `active` hand
    their state back bit for bit, active rows move."""
    cfg, srv = tiny_cfg(), shared.server
    rng = np.random.default_rng(0)
    srv.submit(rng.integers(0, cfg["vocab_size"], 9), 6)
    srv.submit(rng.integers(0, cfg["vocab_size"], 14), 6)
    srv.step()                          # two prefills, ticks 1 and 2
    before = [{k: np.asarray(v) for k, v in pg.items()}
              for pg in srv.cache.pages]
    srv.step()                          # tick 3: rows 0, 1; row 2 idle
    after = [{k: np.asarray(v) for k, v in pg.items()}
             for pg in srv.cache.pages]
    assert srv.decoder.layer_kinds == ("recurrent",) * 3
    for b, a in zip(before, after):
        assert set(b) == {"S", "z"}
        for name in b:
            assert np.array_equal(b[name][2], a[name][2]), name
            assert not np.array_equal(b[name][:2], a[name][:2]), name
    srv.run()


def test_an_admission_while_a_tick_is_queued_ahead(shared):
    """A request admitted while another's tick is already queued on the
    device is an inactive row of that tick; its freshly prefilled state
    must come through it untouched (after 1, 2 and 5 ticks of the
    first)."""
    cfg = tiny_cfg()
    rng = np.random.default_rng(12)
    served, srv = shared, shared.server
    pairs = []
    for steps_before in (1, 2, 5):
        a = served.submit(rng.integers(0, cfg["vocab_size"], 11), 16)
        for _ in range(steps_before):
            srv.step()
        assert srv._flights                  # a tick is queued ahead
        b = served.submit(rng.integers(0, cfg["vocab_size"], 21), 12)
        while served.busy():
            srv.step()
            srv.cache.check()
        assert served.ok(a) and served.ok(b)
        pairs += [served.tokens(a), served.tokens(b)]
    assert srv.stats()["ticks_ahead"] > 0
    gaps = ref.served_token_gaps(cfg, SEED, pairs, q_block=64)
    assert max(float(g.max()) for g in gaps) < 1e-4


# -- (5) the cache with no block pool --------------------------------------------

def make_cache(**kw):
    return PagedKVCache(**dict(dict(
        num_layers=3, num_kv_heads=2, head_dim=16, num_blocks=12,
        block_size=8, batch_slots=3, max_blocks_per_seq=6,
        layer_kinds=("recurrent",) * 3,
        state_shapes=pr.state_shapes(2, 16)), **kw))


def test_an_all_recurrent_cache_holds_no_pool_and_no_table():
    kv = make_cache()
    assert not kv.paged
    assert [sorted(pg) for pg in kv.pages] == [["S", "z"]] * 3
    assert kv.pages[0]["S"].shape == (3, 2, 9, 16, 16)
    assert kv.pages[0]["z"].shape == (3, 2, 16, 16)
    assert kv.num_blocks == 1 and kv.num_free_blocks == 0
    assert kv.block_tables.shape == (3, 0)
    assert kv.state_pool_bytes == 3 * 3 * 2 * (9 * 16 * 16 + 16 * 16) * 4
    assert kv.stats()["state_pool_bytes"] == kv.state_pool_bytes
    assert kv.stats()["utilization"] == 0 and kv.fragmentation() == 0.0
    # whatever num_blocks says, it is ignored
    assert not make_cache(num_blocks=0).paged
    # a sequence costs its slot's row at any length, and nothing a token
    assert kv.blocks_for(10 ** 6) == 0 and kv.can_alloc(10 ** 6)
    assert kv.alloc(1, 10 ** 6)
    assert all(kv.ensure(1, p) for p in (10 ** 6, 10 ** 7))
    assert kv.alloc_count == 0 and kv.slot_blocks(1) == []
    kv.check()


def test_alloc_free_and_check_hold_the_slots_alone():
    kv = make_cache()
    rng = np.random.default_rng(0)
    held = set()
    for _ in range(200):
        slot = int(rng.integers(0, 3))
        if slot in held:
            if rng.random() < 0.5:
                assert kv.ensure(slot, kv.slot_len(slot) + 7)
            else:
                kv.free_slot(slot)
                held.discard(slot)
        else:
            assert kv.alloc(slot, int(rng.integers(1, 5000)))
            held.add(slot)
        kv.check()
        assert kv.state_slots_used == len(held)
        assert kv.num_used_blocks == 0
    kv.free_slot(0)
    assert kv.alloc(0, 5)
    with pytest.raises(ValueError, match="already holds"):
        kv.alloc(0, 5)


@pytest.mark.parametrize("fault", ["a state missing", "a row too few",
                                   "a block pool", "a table"])
def test_check_finds_an_all_recurrent_cache_out_of_shape(fault):
    kv = make_cache()
    assert kv.alloc(1, 10)
    kv.check()
    if fault == "a state missing":
        del kv.pages[2]["z"]
    elif fault == "a row too few":
        kv.pages[0]["S"] = kv.pages[0]["S"][:2]
    elif fault == "a block pool":
        kv.pages[1]["k"] = jnp.zeros((2, 2, 8, 16))
    else:
        kv.block_tables = np.zeros((3, 6), np.int32)
    with pytest.raises(AssertionError,
                       match="recurrent layer's pool|all-recurrent"):
        kv.check()


def test_the_server_does_no_block_work_for_it(monkeypatch):
    """Admission by slot alone, no table uploaded, `_ensure_blocks`
    never called, nothing fed to the pool's forecaster; a request far
    longer than any block budget would allow is served."""
    net = mx.models.get_model("brumby_tiny")
    net.initialize()
    srv = InferenceServer(net, batch_slots=2, max_len=4096,
                          max_prompt_len=32, num_blocks=2)
    assert not srv.cache.paged and srv._tables() == ()
    assert srv._tables(slot=1) == ()
    monkeypatch.setattr(
        srv, "_ensure_blocks",
        lambda send: pytest.fail("block bookkeeping on a cache with "
                                 "no pool"))
    fed = []
    monkeypatch.setattr(srv._forecaster, "add",
                        lambda *a: fed.append(a))
    reqs = [srv.submit(np.arange(5 + i), max_new_tokens=40)
            for i in range(3)]
    assert srv.step() >= 0 and srv.cache.state_slots_used == 2
    assert len(srv.queue) == 1          # the third waits for a SLOT
    srv.run()
    srv.cache.check()
    assert [r.status for r in reqs] == ["ok"] * 3 and not fed
    st = srv.stats()
    assert st["preemptions"] == 0 and st["kv_num_blocks"] == 0
    assert st["state_pool_bytes"] == srv.cache.state_pool_bytes > 0
    assert not srv._kernel_paged and srv._gather_bytes_per_tick == 0
    assert srv.health_detail()["ok"]


# -- (6) what the server refuses ---------------------------------------------------

@pytest.mark.parametrize("feature,kw", [
    ("prefill_chunk", {"prefill_chunk_tokens": 8}),
    ("speculative", {"speculative": 2}),
    ("lora", {"lora": True}),
    ("int8", {"kv_cache_dtype": "int8"}),
    ("prefix_cache", {"prefix_cache": True}),
    ("kv_tier", {"kv_tiering": True}),
])
def test_unsupported_features_raise_by_name(feature, kw):
    net = mx.models.get_model("brumby_tiny")
    net.initialize()
    with pytest.raises(NotImplementedError) as e:
        InferenceServer(net, batch_slots=2, max_len=64, **kw)
    assert feature in str(e.value) and "BrumbyDecoder" in str(e.value)
    assert "recurrent" in str(e.value)


def test_jamba_is_handed_the_positions_and_ignores_them():
    """The recurrent forms' new operand reaches every description; a
    state-space layer's program does not read it: traced with the
    positions as an operand, no equation of the jaxpr takes it."""
    from mxnet_tpu.models import jamba_math

    net = mx.models.get_model("jamba_tiny")
    net.initialize()
    dec = net.decoder()
    lp = dec.params_tree(net)["layers"][0]
    x = jnp.zeros((1, 5, 64), jnp.float32)
    st = jamba_math.zero_state(net.model.cfg, 1)
    live = jnp.ones((1,), bool)
    for fn, pos in (
            (lambda p: dec.prefill_recurrent(0, lp, x, p,
                                             jnp.asarray([5]))[0],
             jnp.arange(5)),
            (lambda p: dec.decode_recurrent(0, lp, x[:, :1], p, st,
                                            live)[0],
             jnp.zeros((1,), jnp.int32))):
        jaxpr = jax.make_jaxpr(fn)(pos).jaxpr
        used = {v for eqn in jaxpr.eqns for v in eqn.invars
                if not hasattr(v, "val")}
        assert jaxpr.invars[0] not in used


# -- (7) the reference's controls -----------------------------------------------------

@pytest.fixture(scope="module")
def exact_streams():
    """The reference's own forward of the two lengths the controls are
    held against, once for the module."""
    cfg = tiny_cfg()
    out = {}
    for n in (40, 600):
        rng = np.random.default_rng(2)
        ids = [rng.integers(0, 256, n + 23)]
        with jax.default_matmul_precision("highest"):
            out[n] = (ids, ref.forward(cfg, 3, ids, q_block=64)[0][0])
    return cfg, out


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_each_control_changes_the_answer(control, exact_streams):
    cfg, streams = exact_streams
    # short_memory cuts the sums to 512 positions: a sequence past them
    n = 600 if control == "short_memory" else 40
    ids, exact = streams[n]
    with jax.default_matmul_precision("highest"):
        altered = ref.forward(cfg, 3, ids, q_block=64,
                              control=control)[0][0]
    moved = float(jnp.abs(exact - altered)[n:n + 23].max())
    assert moved > (1e-6 if control in ("state_bf16", "short_memory")
                    else 1e-3), control


def test_a_controls_gaps_are_read_like_the_programs():
    cfg = tiny_cfg()
    rng = np.random.default_rng(2)
    seq = [(rng.integers(0, 256, 40), rng.integers(0, 256, 24))]
    gaps = ref.served_token_gaps(cfg, 3, seq, q_block=64,
                                 control="no_normaliser")
    assert gaps[0].shape == (24,) and float(gaps[0].min()) >= 0.0
    assert float(gaps[0].max()) > 1e-2


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="unknown control"):
        ref.served_token_gaps(tiny_cfg(), 3, [([1, 2], [3])], q_block=64,
                              control="int4")


def test_the_reference_imports_nothing_of_the_program():
    src = open(ref.__file__).read()
    assert "mxnet_tpu" not in src and "import perfbench" not in src
    assert "from perfbench" not in src
    assert 'default_matmul_precision("highest")' in src


def test_the_gates_horizons_are_the_configurations():
    """gate_init: b_g = log(h - 1) with h log-uniform in [64, 8192], so
    1 / (1 - sigmoid(b_g)) = h lies in the range, a kv head a layer."""
    cfg = harness.load_json(harness.HERE, "configs", "brumby_14b.json")
    small = dict(cfg, hidden_size=64, intermediate_size=128,
                 vocab_size=256, num_hidden_layers=2)
    w = ref.Weights(small, 123)
    for l in range(2):
        assert w.layer(l)["bg"].dtype == jnp.float32
        bg = np.asarray(w.layer(l)["bg"], np.float64)
        assert bg.shape == (8,)
        h = 1.0 / (1.0 - 1.0 / (1.0 + np.exp(-bg)))
        assert (h > 63.9).all() and (h < 8193).all()
        assert float(np.std(np.asarray(w.layer(l)["wg"],
                                       np.float32))) < 0.003


# -- (8) the benchmark's cell, tiny ---------------------------------------------------

def test_tiny_rehearsal_of_the_brumby_cell(interpret):
    """perfbench/rehearsal.json may not grow outside a benchmark PR, so
    the cell's tiny preset is a file of its own, laid over the cell
    (what `rehearse.py --workload brumby_14b.longctx20` runs)."""
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in bm["workloads"]
             if harness.Cell(w["name"], bm).config["family"]
             == "retention_decoder"]
    assert cells == [CELL]
    assert harness.Cell(CELL, bm).config["num_hidden_layers"] == 6
    cell = rehearse.tiny_cell(CELL, bm)
    assert cell.config["head_dim"] == 16
    assert cell.traffic["server"]["num_blocks"] is None
    result = rehearse.run_tiny(cell, 2 ** 31 + 4242, 1.5)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 3 and result["metrics"] == {}


def test_the_controls_run_through_control_check():
    """control_check.run puts each control in the program's place on
    the finishing sessions' shapes and holds it to the traffic file's
    limits; at the tiny preset a control that moves no token of 256
    reads `correct` (the chip's table, PERF.md section 2, has none)."""
    cell = rehearse.tiny_cell(CELL)
    out = control_check.run(cell, 2 ** 31 + 77, jax.devices()[0],
                            ["degree_1", "no_normaliser", "no_rope"])
    assert sorted(out) == ["degree_1", "no_normaliser", "no_rope"]
    assert not any(r["correct"] for r in out.values())
    assert all(r["served_tokens"] >= 8 for r in out.values())


def test_the_configuration_is_the_catalogs_cut_in_depth_alone():
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bm["configs"]}["brumby_14b"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == "https://huggingface.co/manifestai/" \
        "Brumby-14B-Base/blob/main/config.json"
    cfg = harness.load_json(harness.ROOT, entry["file"])
    published = {"attention_bias": False, "head_dim": 128,
                 "hidden_act": "silu", "hidden_size": 5120,
                 "intermediate_size": 17408,
                 "max_position_embeddings": 32768,
                 "max_window_layers": 40, "model_type": "brumby",
                 "num_attention_heads": 40, "num_key_value_heads": 8,
                 "rms_norm_eps": 1e-06, "rope_scaling": None,
                 "rope_theta": 1000000, "sliding_window": None,
                 "tie_word_embeddings": False,
                 "use_sliding_window": False, "vocab_size": 151936}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 6
    assert cfg["num_hidden_layers_published"] == 40
    assert set(cfg["reduced_why"]) == {"num_hidden_layers"}
    assert set(cfg["assumed"]) >= {
        "retention", "retention_degree", "gate", "normaliser",
        "qk_norm_and_rope", "feature_map", "retention_state_dtype",
        "gate_init", "torch_dtype", "initializer_range", "eos"}
    assert cfg["retention_state_dtype"] == "float32"
    assert cfg["gate_init"] == {"weight_std": 0.002,
                                "horizon": [64, 8192]}
    traffic = harness.load_json(harness.HERE, "traffic", "longctx20.json")
    assert traffic["clients"] == traffic["server"]["batch_slots"] == 20
    assert traffic["server"]["max_len"] == 20480
    assert traffic["server"]["max_prompt_len"] == 12288
    assert traffic["context_tokens"] == {"dist": "uniform", "min": 4096,
                                         "max": 12288}
