"""Jamba (Mamba-1 layers beside attention layers) through the normal
serving path, against the plain float32 reference
(perfbench/reference/jamba_decoder.py), at tiny sizes on the CPU: the
net's forward, served logits over staggered admissions, a freed and
reused slot, a preempted and resumed request, an idle row beside active
ones, an admission while a tick is queued ahead, the state pool in the
cache's allocator, what the server refuses, and the tiny rehearsal of
the benchmark's Jamba cell."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.kernels import dispatch  # noqa: E402
from mxnet_tpu.models import jamba_math  # noqa: E402
from mxnet_tpu.serving import InferenceServer  # noqa: E402
from mxnet_tpu.serving.kv_cache import PagedKVCache  # noqa: E402
from perfbench import harness, rehearse  # noqa: E402
from perfbench.reference import jamba_decoder as ref  # noqa: E402

KERNELS = ("FLASH", "NORM", "SCAN")


def tiny_cfg(**over):
    """The benchmark's configuration file under its tiny preset: four
    layers, attention at 1, d_inner 128, 4 heads on one kv head."""
    cfg = rehearse.merge(
        harness.load_json(harness.HERE, "configs", "jamba2_3b.json"),
        harness.load_json(harness.HERE, "rehearsal.jamba.json")["config"])
    cfg.update(over)
    return cfg


@pytest.fixture
def interpret(monkeypatch):
    for k in KERNELS:
        monkeypatch.setenv(f"MXNET_TPU_{k}_INTERPRET", "1")


def build_server(cfg, seed, **spec):
    from perfbench.families import jamba_decoder as family

    spec = dict({"batch_slots": 4, "max_len": 96, "max_prompt_len": 48,
                 "kv_cache_dtype": "model"}, **spec)
    return family.build(cfg, spec, seed, jax.devices()[:1])


def reference_logits(cfg, seed, ids):
    with jax.default_matmul_precision("highest"):
        xs, watch = ref.forward(cfg, seed, ids, q_block=8)
        ends = ref.Weights(cfg, seed).ends()
        return [np.asarray(ref._rms(x.astype(jnp.float32),
                                    ends["norm"].astype(jnp.float32),
                                    cfg["rms_norm_eps"])
                           @ ends["embed"].astype(jnp.float32).T)
                for x in xs], watch


# -- (1) the net's forward against the reference ------------------------------

@pytest.mark.parametrize("dtype,tol", [
    # float32: the same arithmetic in another order of operations
    ("float32", 2e-4),
    # bfloat16: 8 bits of mantissa on every matmul operand and on the
    # residual stream, through 4 layers, on logits of size ~0.3
    ("bfloat16", 6e-2),
])
def test_the_net_forward_equals_the_reference(dtype, tol):
    cfg = tiny_cfg(torch_dtype=dtype)
    served = build_server(cfg, 5)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    got = served.server.net(mx.nd.array(ids, dtype="int32")).asnumpy()
    want, watch = reference_logits(cfg, 5, list(ids))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g.astype(np.float32), w[:24],
                                   atol=tol, rtol=tol)
    # the bring-up watch: a state and a residual stream that live
    assert len(watch["state_rms"]) == 3
    assert all(1e-4 < v < 1e2 for v in watch["state_rms"])
    assert 1e-3 < watch["stream_rms"] < 1e2


def test_the_layer_order_and_the_tied_head():
    net = mx.models.get_model("jamba")          # the published sizes
    cfg = net.model.cfg
    kinds = cfg.layer_kinds
    assert len(kinds) == 28 and kinds.count("full") == 2
    assert [i for i, k in enumerate(kinds) if k == "full"] == [7, 21]
    assert cfg.d_inner == 5120 and cfg.head_dim == 128
    names = set(net.collect_params())
    assert not any("lm_head" in n for n in names)
    shapes = {n: p.shape for n, p in net.collect_params().items()}
    assert shapes["model.layers.0.A_log"] == (16, 5120)
    assert shapes["model.layers.0.x_proj"] == (192, 5120)
    assert shapes["model.layers.7.wk"] == (128, 2560)
    n_params = sum(int(np.prod(s)) for s in shapes.values())
    assert abs(n_params - 3.03e9) < 0.01e9
    dec = net.decoder()
    assert dec.recurrent and not dec.mixed and dec.supports == frozenset()
    st = dec.state_shapes()
    assert st["h"] == ((16, 40, 128), jnp.float32)
    assert st["tail"] == ((3 * 5120,), jnp.bfloat16)  # tap after tap


# -- (2) served through the state pool and the paged cache ---------------------

@pytest.mark.parametrize("kernels", ["jnp", "interpreted"])
def test_served_logits_match_the_reference(kernels, monkeypatch):
    """Prefill, then decode through the state pool and the paged cache,
    equals the reference's one full forward, logits compared at every
    served position; staggered admissions, greedy and sampled rows in
    one batch, more requests than slots (a slot is freed and reused)."""
    if kernels == "interpreted":
        for k in KERNELS:
            monkeypatch.setenv(f"MXNET_TPU_{k}_INTERPRET", "1")
    cfg = tiny_cfg()
    served = build_server(cfg, 11, batch_slots=3)
    srv = served.server
    rng = np.random.default_rng(5)
    sampling = {"temperature": 0.7, "top_k": 20, "top_p": 0.9}
    mix = [(6, 12), (17, 20), (40, 9), (23, 14), (9, 16)]
    reqs, rows = [], {}

    def note():
        # the logits row a request's next token is sampled from
        row = np.asarray(srv._last_logits)
        for r in reqs:
            if r in srv._slot_req and not srv._flights:
                rows.setdefault(id(r), []).append(
                    row[srv._slot_req.index(r)])

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
        cfg, 11, [served.tokens(r) for r in reqs if r.temperature == 0],
        q_block=8)
    assert sum(len(g) for g in gaps) == 12 + 9 + 16
    assert max(float(g.max()) for g in gaps) < 1e-4
    # a sampled request's tokens: every one is among the reference's
    # top-20 at its position
    sampled = [r for r in reqs if r.temperature > 0]
    ids = [np.concatenate([r.prompt, r.output_tokens])[:-1]
           for r in sampled]
    logits, _ = reference_logits(cfg, 11, ids)
    for r, lg in zip(sampled, logits):
        n = len(r.prompt)
        for j, tok in enumerate(r.output_tokens):
            top = np.argsort(lg[n - 1 + j])[-20:]
            assert tok in top


def test_a_freed_slot_carries_no_state_over(interpret):
    """One slot, three requests one after another: the second and the
    third get the state of nobody (a stale state would move their
    logits off the reference's, which starts every sequence at 0)."""
    cfg = tiny_cfg()
    rng = np.random.default_rng(8)
    served = build_server(cfg, 4, batch_slots=1)
    reqs = [served.submit(rng.integers(0, cfg["vocab_size"], n), 10)
            for n in (30, 7, 19)]
    served.server.run()
    assert all(served.ok(r) for r in reqs)
    gaps = ref.served_token_gaps(cfg, 4, [served.tokens(r) for r in reqs],
                                 q_block=8)
    assert max(float(g.max()) for g in gaps) < 1e-4
    # and the yardstick sees a stale state: the second request's tokens
    # scored behind the first's prompt read a gap
    stale = ref.served_token_gaps(
        cfg, 4, [(np.concatenate([reqs[0].prompt, reqs[1].prompt]),
                  reqs[1].output_tokens)], q_block=8)
    assert float(stale[0].max()) > 1e-3


def test_a_preempted_request_resumes_identically():
    """A pool too small for every session preempts the youngest: its
    slot and its state row are freed, the rerun prefills the state
    anew, and greedy tokens equal a roomy pool's."""
    cfg = tiny_cfg()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg["vocab_size"], n) for n in (10, 12, 9)]

    def run(**spec):
        served = build_server(cfg, 21, batch_slots=3, **spec)
        served.server.max_preemptions = None
        reqs = [served.submit(p, 40, seed=i)
                for i, p in enumerate(prompts)]
        while served.busy():
            served.step()
            served.server.cache.check()
        kv = served.server.cache
        assert kv.num_used_blocks == 0 and kv.state_slots_used == 0
        return [list(r.output_tokens) for r in reqs], \
            served.server.preemptions, [r.status for r in reqs]

    roomy, n0, _ = run()
    tight, n1, status = run(num_blocks=9)
    assert n0 == 0 and n1 > 0 and status == ["ok"] * 3
    assert tight == roomy


def test_an_idle_row_keeps_its_state(interpret):
    """The decode program's masked update: rows not in `active` hand
    their state back bit for bit, active rows move."""
    cfg = tiny_cfg()
    served = build_server(cfg, 2, batch_slots=3)
    srv = served.server
    rng = np.random.default_rng(0)
    srv.submit(rng.integers(0, cfg["vocab_size"], 9), 6)
    srv.submit(rng.integers(0, cfg["vocab_size"], 14), 6)
    srv.step()                          # two prefills, ticks 1 and 2
    before = [{k: np.asarray(v) for k, v in pg.items()}
              for pg in srv.cache.pages]
    srv.step()                          # tick 3: rows 0, 1; row 2 idle
    after = [{k: np.asarray(v) for k, v in pg.items()}
             for pg in srv.cache.pages]
    kinds = srv.decoder.layer_kinds
    assert kinds.count("recurrent") == 3
    for kind, b, a in zip(kinds, before, after):
        if kind != "recurrent":
            continue
        assert set(b) == {"h", "tail"}
        for name in b:
            assert np.array_equal(b[name][2], a[name][2]), name
            assert not np.array_equal(b[name][:2], a[name][:2]), name
    srv.run()


@pytest.mark.parametrize("steps_before", [1, 2, 5])
def test_an_admission_while_a_tick_is_queued_ahead(steps_before,
                                                   interpret):
    """The masked-update case: a request admitted while another's tick
    is already queued on the device is an inactive row of that tick;
    its freshly prefilled state must come through it untouched: both
    requests' logits stay on the reference's."""
    cfg = tiny_cfg()
    rng = np.random.default_rng(12)
    first = rng.integers(0, cfg["vocab_size"], 11)
    late = rng.integers(0, cfg["vocab_size"], 21)
    served = build_server(cfg, 6, batch_slots=2)
    srv = served.server
    a = served.submit(first, 16)
    for _ in range(steps_before):
        srv.step()
    assert srv._flights                  # a tick is queued ahead
    b = served.submit(late, 12)
    while served.busy():
        srv.step()
        srv.cache.check()
    assert srv.stats()["ticks_ahead"] > 0
    assert served.ok(a) and served.ok(b)
    gaps = ref.served_token_gaps(cfg, 6, [served.tokens(a),
                                          served.tokens(b)], q_block=8)
    assert max(float(g.max()) for g in gaps) < 1e-4


# -- (3) the reference's controls ------------------------------------------------

@pytest.mark.parametrize("control", ref.CONTROLS)
def test_each_control_changes_the_answer(control):
    cfg = tiny_cfg()
    rng = np.random.default_rng(2)
    seq = [(rng.integers(0, 256, 40), rng.integers(0, 256, 24))]
    ids = [np.concatenate(seq[0])[:-1]]
    with jax.default_matmul_precision("highest"):
        exact = ref.forward(cfg, 3, ids, q_block=8)[0][0]
        altered = ref.forward(cfg, 3, ids, q_block=8, control=control,
                              handovers=[40])[0][0]
    moved = float(jnp.abs(exact - altered)[:63].max())
    assert moved > (1e-5 if control == "state_bf16" else 1e-3), control
    gaps = ref.served_token_gaps(cfg, 3, seq, q_block=8, control=control)
    assert gaps[0].shape == (24,) and float(gaps[0].min()) >= 0.0
    if control in ("fp8", "no_norms"):
        # the others move the logits too little to flip a token of 256
        # at this size; the chip's table (PERF.md section 2) has them
        assert float(gaps[0].max()) > 1e-4, control


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="unknown control"):
        ref.served_token_gaps(tiny_cfg(), 3, [([1, 2], [3])], q_block=8,
                              control="int4")


# -- (4) the state pool in the cache ---------------------------------------------

def make_cache(**kw):
    return PagedKVCache(**dict(dict(
        num_layers=4, num_kv_heads=1, head_dim=16, num_blocks=12,
        block_size=8, batch_slots=3, max_blocks_per_seq=6,
        layer_kinds=("recurrent", "full", "recurrent", "recurrent"),
        state_shapes={"h": ((4, 1, 128), jnp.float32),
                      "tail": ((3, 128), jnp.float32)}), **kw))


def test_block_pools_exist_for_the_attention_layers_only():
    kv = make_cache()
    assert [sorted(pg) for pg in kv.pages] == \
        [["h", "tail"], ["k", "v"], ["h", "tail"], ["h", "tail"]]
    assert kv.pages[0]["h"].shape == (3, 4, 1, 128)
    assert kv.pages[1]["k"].shape == (12, 1, 8, 16)
    assert kv.state_pool_bytes == 3 * 3 * (4 * 128 + 3 * 128) * 4
    assert kv.stats()["state_pool_bytes"] == kv.state_pool_bytes


def test_alloc_free_and_check_cover_the_state_rows():
    kv = make_cache()
    rng = np.random.default_rng(0)
    held = set()
    for _ in range(200):
        slot = int(rng.integers(0, 3))
        if slot in held:
            if rng.random() < 0.5:
                kv.ensure(slot, kv.slot_len(slot))
            else:
                kv.free_slot(slot)
                held.discard(slot)
        elif kv.alloc(slot, int(rng.integers(1, 20))):
            held.add(slot)
        kv.check()
        assert kv.state_slots_used == len(held)
        assert kv.stats()["state_slots_used"] == len(held)
    for slot in list(held):
        kv.free_slot(slot)
    kv.check()
    assert kv.state_slots_used == 0 and kv.num_used_blocks == 0


@pytest.mark.parametrize("fault", ["a state missing",
                                   "a row too few"])
def test_check_finds_a_state_pool_out_of_shape(fault):
    kv = make_cache()
    assert kv.alloc(1, 10)
    kv.check()
    if fault == "a state missing":
        del kv.pages[2]["tail"]
    else:
        kv.pages[0]["h"] = kv.pages[0]["h"][:2]
    with pytest.raises(AssertionError, match="recurrent layer's pool"):
        kv.check()


@pytest.mark.parametrize("kw", [{"quantized": True},
                                {"prefix_cache": True},
                                {"state_shapes": None}])
def test_a_recurrent_cache_refuses_what_it_cannot_hold(kw):
    with pytest.raises(NotImplementedError, match="recurrent"):
        make_cache(**kw)


# -- (5) what the server refuses ---------------------------------------------------

@pytest.mark.parametrize("feature,kw", [
    ("prefill_chunk", {"prefill_chunk_tokens": 8}),
    ("speculative", {"speculative": 2}),
    ("lora", {"lora": True}),
    ("int8", {"kv_cache_dtype": "int8"}),
    ("prefix_cache", {"prefix_cache": True}),
    ("kv_tier", {"kv_tiering": True}),
])
def test_unsupported_features_raise_by_name(feature, kw):
    net = mx.models.get_model("jamba_tiny")
    net.initialize()
    with pytest.raises(NotImplementedError) as e:
        InferenceServer(net, batch_slots=2, max_len=64, **kw)
    assert feature in str(e.value) and "JambaDecoder" in str(e.value)
    assert "recurrent" in str(e.value)


def test_the_other_decoders_take_no_new_operand():
    """The Llama block's prefill is called with the six operands it
    always had, and its cache holds no state row."""
    net = mx.models.get_model("llama_tiny")
    net.initialize()
    srv = InferenceServer(net, batch_slots=2, max_len=64)
    assert not srv.decoder.recurrent and not srv._recurrent
    assert srv.cache.state_pool_bytes == 0
    r = srv.submit(np.arange(9), max_new_tokens=6)
    srv.run()
    srv.cache.check()
    assert r.status == "ok"
    assert "state_pool_bytes" not in srv.stats()
    assert "kv_state_pool_bytes" not in srv.stats()


def test_the_step_and_the_scan_are_one_layer():
    """jamba_math: a layer over T positions equals T single-token
    steps from the same state, with a carried convolution tail."""
    net = mx.models.get_model("jamba_tiny")
    net.initialize(init=mx.init.Normal(0.2))
    cfg = net.model.cfg
    lp = net.decoder().params_tree(net)["layers"][0]
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 6, 64)), jnp.float32)
    whole, st = jamba_math.mamba_layer(lp, x, cfg)
    state = jamba_math.zero_state(cfg, 2)
    live = jnp.ones((2,), bool)
    step = jax.jit(lambda xt, s: jamba_math.mamba_layer_step(
        lp, xt, cfg, s, live))
    for t in range(6):
        y, state = step(x[:, t:t + 1], state)
        np.testing.assert_allclose(y[:, 0], whole[:, t], atol=2e-5,
                                   rtol=2e-5)
    for name in st:
        np.testing.assert_allclose(state[name], st[name], atol=2e-5,
                                   rtol=2e-5)
    # a prompt in two halves: the second starts from the first's state
    first, mid = jamba_math.mamba_layer(lp, x[:, :4], cfg)
    second, end = jamba_math.mamba_layer(lp, x[:, 4:], cfg, state=mid)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1),
                               whole, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(end["h"], st["h"], atol=2e-5, rtol=2e-5)


def test_the_step_of_a_recurrent_layer_is_three_operations(interpret):
    """`mixer_step` since PR 47: in_proj, ONE call that takes `xz` and
    both pools and gives `g` (the convolution, x_proj, the norms,
    dt_proj, the recurrence and the gate inside it), out_proj; the rest
    of its jaxpr only views operands."""
    net = mx.models.get_model("jamba_tiny")
    net.initialize()
    cfg = net.model.cfg
    lp = net.decoder().params_tree(net)["layers"][0]
    state = jamba_math.zero_state(cfg, 2)
    assert state["tail"].shape == (2, (cfg.d_conv - 1) * cfg.d_inner)
    jaxpr = jax.make_jaxpr(lambda u, s: jamba_math.mixer_step(
        lp, u, cfg, s, jnp.ones((2,), bool)))(jnp.zeros((2, 1, 64)),
                                              state)
    views = {"slice", "squeeze", "transpose", "reshape",
             "broadcast_in_dim"}
    work = [e for e in jaxpr.eqns if e.primitive.name not in views]
    assert [e.primitive.name for e in work] \
        == ["dot_general", "jit", "dot_general"]
    assert work[1].params["name"] == "_state_update"
    # the call takes in_proj's product whole and the pools as they are
    xz, (_, h, tail) = work[0].outvars[0], jaxpr.jaxpr.invars
    assert xz in work[1].invars and h in work[1].invars \
        and tail in work[1].invars
    assert work[1].outvars[0] in work[2].invars


# -- (6) the benchmark's Jamba cell, tiny ---------------------------------------------

def test_tiny_rehearsal_of_the_jamba_cell(interpret):
    """perfbench/rehearsal.json may not grow outside a benchmark PR, so
    the cell's tiny preset is a file of its own, laid over the cell."""
    tiny = harness.load_json(harness.HERE, "rehearsal.jamba.json")
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in bm["workloads"]
             if harness.Cell(w["name"], bm).config["family"]
             == "jamba_decoder"]
    assert cells == ["jamba2_3b.reason256"]
    cell = harness.Cell(cells[0], bm)
    assert cell.config["num_hidden_layers"] == 28
    cell.config = rehearse.merge(cell.config, tiny["config"])
    cell.traffic = rehearse.merge(cell.traffic, tiny["traffic"])
    result = rehearse.run_tiny(cell, 2 ** 31 + 4242, 1.5)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 3 and result["metrics"] == {}


def test_the_configuration_is_the_catalogs_uncut():
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bm["configs"]}["jamba2_3b"]
    assert entry["reduced"] == []
    cfg = harness.load_json(harness.ROOT, entry["file"])
    published = {"attn_layer_offset": 7, "attn_layer_period": 14,
                 "hidden_size": 2560, "intermediate_size": 8192,
                 "mamba_d_conv": 4, "mamba_d_state": 16,
                 "mamba_dt_rank": 160, "mamba_expand": 2,
                 "num_attention_heads": 20, "num_experts": 1,
                 "num_hidden_layers": 28, "num_key_value_heads": 1,
                 "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
                 "vocab_size": 65536}
    assert {k: cfg[k] for k in published} == published
    assert cfg["ssm_state_dtype"] == "float32"
    assert set(cfg["assumed"]) >= {"head_dim", "layer_order",
                                   "ssm_state_dtype", "mamba_init"}
