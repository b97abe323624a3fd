"""afmoe (Arcee Trinity family) through the normal serving path, against
the plain float32 reference (perfbench/reference/afmoe_decoder.py), at
tiny sizes on the CPU: served logits, the shares of an expert-parallel
deployment adding up, the held-expert layer, the windowed kernels
(interpreted), the two-kind paged cache, the combinations the server
refuses, and the tiny rehearsal of the benchmark's afmoe cell."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.kernels import flash_decode as fd  # noqa: E402
from mxnet_tpu.kernels.flash_attention import (  # noqa: E402
    flash_attention_raw, reference_attention)
from mxnet_tpu.parallel.moe import held_expert_ffn, route_top_k  # noqa: E402
from mxnet_tpu.serving import InferenceServer  # noqa: E402
from mxnet_tpu.serving.kv_cache import PagedKVCache  # noqa: E402
from perfbench import harness, rehearse  # noqa: E402
from perfbench.reference import afmoe_decoder as ref  # noqa: E402

WINDOW = 16


def tiny_cfg(**over):
    """The benchmark's configuration file under its tiny preset."""
    cfg = rehearse.merge(
        harness.load_json(harness.HERE, "configs", "trinity_large.json"),
        harness.load_json(harness.HERE, "rehearsal.afmoe.json")["config"])
    cfg.update(over)
    return cfg


@pytest.fixture
def interpret(monkeypatch):
    """The real Pallas kernels under the interpreter."""
    for k in ("FLASH", "NORM", "MOE"):
        monkeypatch.setenv(f"MXNET_TPU_{k}_INTERPRET", "1")


def build_server(cfg, seed, **spec):
    from perfbench.families import afmoe_decoder as family

    spec = dict({"batch_slots": 4, "max_len": 96, "max_prompt_len": 64,
                 "kv_cache_dtype": "model"}, **spec)
    return family.build(cfg, spec, seed, jax.devices()[:1])


# -- (1) the served model against the reference ------------------------------

@pytest.mark.parametrize("kernels", ["jnp", "interpreted"])
def test_served_logits_match_the_reference_across_the_window(
        kernels, monkeypatch):
    """Prefill then decode through the paged cache equals the
    reference's one full forward, logits compared at every served
    position, for contexts below (6), across (12, decodes past 16) and
    beyond (40) the window; greedy and sampled rows share the batch."""
    if kernels == "interpreted":
        for k in ("FLASH", "NORM", "MOE"):
            monkeypatch.setenv(f"MXNET_TPU_{k}_INTERPRET", "1")
    cfg = tiny_cfg()
    assert cfg["sliding_window"] == WINDOW
    served = build_server(cfg, 11)
    srv = served.server
    rng = np.random.default_rng(5)
    sampling = {"temperature": 0.7, "top_k": 20, "top_p": 0.9}
    reqs = [served.submit(rng.integers(0, cfg["vocab_size"], n), 24,
                          sampling if i % 2 else None, seed=i)
            for i, n in enumerate((6, 12, 40, 23))]
    # The logits that chose each token. The server keeps one tick
    # queued ahead: step 1, from idle, launches ticks 1 and 2 (their
    # tokens are sampled from the prefill's logits and from tick 1's,
    # which the host never holds between two steps), and every later
    # step k launches tick k + 1, sampled from the row read before
    # that step, and hands over tick k.
    before = []                             # rows read before step k
    while served.busy():
        before.append(np.asarray(srv._last_logits))
        srv.step()
        srv.cache.check()
        if len(before) == 1:
            slot_of = {id(r): srv._slot_req.index(r) for r in reqs}
    assert len(before) == 24 and not srv._flights
    seen = {id(r): [None, None]
            + [before[j - 2][slot_of[id(r)]] for j in range(3, 25)]
            for r in reqs}
    assert all(served.ok(r) for r in reqs)
    assert srv.compile_stats()["prefill_compiles"] == 1
    assert srv.compile_stats()["decode_compiles"] == 1
    ids = [np.concatenate([r.prompt, r.output_tokens])[:-1] for r in reqs]
    with jax.default_matmul_precision("highest"):
        xs, _ = ref.forward(cfg, 11, ids, q_block=8)
        ends = ref.Weights(cfg, 11).ends()
        for r, x, rows in zip(reqs, xs, seen.values()):
            want = np.asarray(
                ref._rms(x, ends["norm"], cfg["rms_norm_eps"])
                @ ends["head"].T)
            n = len(r.prompt)
            checked = 0
            for j, got in enumerate(rows):
                if got is None:
                    continue
                np.testing.assert_allclose(got, want[n - 1 + j],
                                           atol=2e-4, rtol=2e-4)
                checked += 1
            assert checked >= 20
    gaps = ref.served_token_gaps(
        cfg, 11, [served.tokens(r) for r in reqs if r.temperature == 0],
        q_block=8)
    assert max(float(g.max()) for g in gaps) < 1e-4


def test_reference_controls_change_the_answer():
    """Each control of the output check moves the reference off the
    served tokens: top-3, no window, no bias and fp8 all read a gap."""
    cfg = tiny_cfg()
    rng = np.random.default_rng(2)
    seq = [(rng.integers(0, 256, 40), rng.integers(0, 256, 24))]
    for control in ref.CONTROLS:
        gaps = ref.served_token_gaps(cfg, 3, seq, q_block=8,
                                     control=control)
        assert float(gaps[0].max()) > 1e-3, control
    with pytest.raises(ValueError, match="unknown control"):
        ref.served_token_gaps(cfg, 3, seq, q_block=8, control="int4")


# -- (2) the shares add up ---------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 4 held experts: their routed parts plus the shared
    expert counted once are the uncut reference's feed-forward, and the
    program's share equals the reference's share."""
    uncut = tiny_cfg(num_experts=16, held_experts_lo=0)
    lp = ref.Weights(uncut, 9).layer(2)
    m = jax.random.normal(jax.random.PRNGKey(4), (37, 64), jnp.float32)
    shared, whole = ref.ffn_parts(uncut, lp, m)
    total = np.zeros_like(np.asarray(whole))
    for rank in range(4):
        lo = 4 * rank
        share = tiny_cfg(num_experts=4, held_experts_lo=lo)
        lps = dict(lp, **{k: lp[k][lo:lo + 4]
                          for k in ("ex_gate", "ex_up", "ex_down")})
        sh, part = ref.ffn_parts(share, lps, m)
        np.testing.assert_allclose(sh, shared, atol=1e-6)
        got, pairs, *_ = held_expert_ffn(
            m, lps["router"], lps["bias"], lps["ex_gate"], lps["ex_up"],
            lps["ex_down"], lo=lo, top_k=2, route=route_top_k,
            route_scale=share["route_scale"])
        np.testing.assert_allclose(got, part, atol=2e-5)
        total += np.asarray(part)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert float(np.abs(np.asarray(whole)).max()) > 1e-2


# -- (3) the held-expert layer -----------------------------------------------

def _loop(x, rw, b, eg, eu, ed, lo, k, scale, valid=None):
    """Per-expert, per-pair loop: the layer's definition."""
    sel, w = (np.asarray(a) for a in route_top_k(x, rw, b, k, scale))
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        if valid is not None and not valid[t]:
            continue
        for j in range(k):
            e = sel[t, j] - lo
            if 0 <= e < eg.shape[0]:
                h = jax.nn.silu(x[t] @ eg[e]) * (x[t] @ eu[e])
                out[t] += w[t, j] * np.asarray(h @ ed[e])
    return out, sel


def _experts(T=48, D=64, I=32, E=16, n=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (T, D)),
            jax.random.normal(ks[1], (E, D)) * 0.3,
            jax.random.normal(ks[2], (E,)) * 0.1,
            jax.random.normal(ks[3], (n, D, I)) * 0.1,
            jax.random.normal(ks[4], (n, D, I)) * 0.1,
            jax.random.normal(ks[5], (n, I, D)) * 0.1)


@pytest.mark.parametrize("case", ["even", "skewed", "empty", "masked",
                                  "chunked"])
@pytest.mark.parametrize("kernel", ["ragged_dot", "pallas"])
def test_held_expert_layer_is_dropless(case, kernel, monkeypatch):
    """Against the per-pair loop: an even router; one skewed so a single
    held expert takes most pairs (no capacity, nothing dropped); one
    that leaves held experts empty; idle rows masked out; a long prefill
    routed a chunk at a time. Through XLA's ragged_dot and through the
    Pallas grouped matmul (interpreted)."""
    if kernel == "pallas":
        monkeypatch.setenv("MXNET_TPU_MOE_INTERPRET", "1")
    from mxnet_tpu.kernels import tuning
    x, rw, b, eg, eu, ed = _experts()
    lo, k, valid = 4, 3, None
    if case == "skewed":
        rw = rw.at[5].set(x.mean(0) * 4.0 + rw[5])
        b = b.at[5].set(2.0)
    if case == "empty":
        b = b.at[4:7].set(-5.0)
    if case == "masked":
        valid = np.arange(x.shape[0]) % 3 != 0
    if case == "chunked":
        tuning.set_runtime("moe_grouped_matmul", "chunk_tokens", 16)
    try:
        got, pairs, touched, fullest = held_expert_ffn(
            x, rw, b, eg, eu, ed, lo=lo, top_k=k, route=route_top_k,
            route_scale=2.448,
            valid=None if valid is None else jnp.asarray(valid))
    finally:
        tuning.clear_runtime()
    want, sel = _loop(x, rw, b, eg, eu, ed, lo, k, 2.448, valid)
    np.testing.assert_allclose(got, want, atol=2e-5)
    on = (sel >= lo) & (sel < lo + 4)
    if valid is not None:
        on &= valid[:, None]
    assert int(pairs) == int(on.sum())
    assert int(touched) == len(set(sel[on].tolist()))
    assert int(fullest) == max(
        [0] + [int((sel[on] == e).sum()) for e in range(lo, lo + 4)])
    if case == "skewed":
        assert (sel == 5).sum() > 0.8 * x.shape[0]
    if case == "empty":
        assert int(touched) == 1


def test_selection_bias_picks_but_does_not_weigh():
    x, rw, b, *_ = _experts()
    sel0, w0 = route_top_k(x, rw, jnp.zeros_like(b), 3, 1.0)
    sel1, w1 = route_top_k(x, rw, b * 5.0, 3, 1.0)
    assert (np.sort(sel0, -1) != np.sort(sel1, -1)).any()
    s = jax.nn.sigmoid(x @ rw.T)
    picked = np.take_along_axis(np.asarray(s), np.asarray(sel1), 1)
    np.testing.assert_allclose(w1, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w1).sum(-1), 1.0, rtol=1e-5)


# -- (4) the windowed kernels ------------------------------------------------

@pytest.mark.parametrize("window", [None, 100, 256, 600])
def test_flash_attention_window_matches_the_reference(window, interpret):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 512, 4, 128))
    k = jax.random.normal(ks[1], (2, 512, 2, 128))
    v = jax.random.normal(ks[2], (2, 512, 2, 128))
    L = jnp.array([512, 300])
    got = flash_attention_raw(q, k, v, lengths=L, window=window)
    want = reference_attention(q, k, v, lengths=L, window=window)
    np.testing.assert_allclose(got[0], want[0], atol=3e-6)
    np.testing.assert_allclose(got[1, :300], want[1, :300], atol=3e-6)
    if window is not None and window < 512:
        full = reference_attention(q, k, v, lengths=L)
        assert float(jnp.abs(full - want)[0].max()) > 1e-2


def test_flash_attention_window_differentiates_and_needs_causal(
        interpret):
    """The windowed kernel has its backward (it refused one until the
    dkv kernel learnt the window); a window without `causal` is still
    no attention this file knows."""
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 128, 2, 128))
    got = jax.grad(
        lambda a: flash_attention_raw(a, q, q, window=32).sum())(q)
    want = jax.grad(
        lambda a: reference_attention(a, q, q, window=32).sum())(q)
    np.testing.assert_allclose(got, want, atol=2e-5)
    with pytest.raises(ValueError, match="causal"):
        flash_attention_raw(q, q, q, causal=False, window=32)


def _pool(B=3, N=40, K=2, bs=16, d=128, nb=16, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    kp = jax.random.normal(ks[0], (N, K, bs, d))
    vp = jax.random.normal(ks[1], (N, K, bs, d))
    q = jax.random.normal(ks[2], (B, 4, d))
    vl = np.array([200, 37, 256])
    perm = np.random.default_rng(seed).permutation(np.arange(1, N))
    bt, c = np.zeros((B, nb), np.int32), 0
    for b in range(B):
        n = -(-vl[b] // bs)
        bt[b, :n] = perm[c:c + n]
        c += n
    return q, kp, vp, bt, vl


@pytest.mark.parametrize("window", [64, 100, 300])
def test_paged_decode_window_matches_the_reference(window, interpret):
    """The sweep starts at the first page inside the window: the table's
    entries before it are zeroed here, as the cache leaves them."""
    q, kp, vp, bt, vl = _pool()
    cut = bt.copy()
    for b in range(len(vl)):
        cut[b, :max(vl[b] - window, 0) // 16] = 0
    got = fd.flash_decode_paged(q, kp, vp, jnp.asarray(cut),
                                jnp.asarray(vl), window=window)
    want = fd.reference_decode_attention(
        q, fd.gather_kv_pages(kp, jnp.asarray(bt)),
        fd.gather_kv_pages(vp, jnp.asarray(bt)), jnp.asarray(vl),
        window=window)
    np.testing.assert_allclose(got, want, atol=3e-6)


def test_paged_decode_without_a_window_is_the_program_it_was(interpret):
    """window=None traces the kernel exactly as a call that never heard
    of windows, and a window no sequence reaches changes no bit."""
    q, kp, vp, bt, vl = _pool()
    args = (q, kp, vp, jnp.asarray(bt), jnp.asarray(vl))
    plain = jax.make_jaxpr(lambda *a: fd.flash_decode_paged(*a))(*args)
    none = jax.make_jaxpr(
        lambda *a: fd.flash_decode_paged(*a, window=None))(*args)
    assert str(plain) == str(none)
    wide = fd.flash_decode_paged(*args, window=10 ** 6)
    assert (np.asarray(wide) == np.asarray(
        fd.flash_decode_paged(*args))).all()


# -- (5) the cache with two kinds of layer -----------------------------------

def test_two_kind_allocator_fuzz():
    """200 steps of alloc / ensure / free over both pools: check()'s
    invariants hold, a sliding layer's sequence never owns more than its
    bound, and the full pool still holds every position."""
    rng = np.random.default_rng(7)
    c = PagedKVCache(num_layers=5, num_kv_heads=2, head_dim=8,
                     num_blocks=60, block_size=4, batch_slots=4,
                     max_blocks_per_seq=32,
                     layer_kinds=("sliding",) * 4 + ("full",), window=10,
                     window_num_blocks=14)
    assert c.window_blocks_per_seq == 4
    assert [p["k"].shape[0] for p in c.pages] == [14] * 4 + [60]
    pos = [None] * 4
    grew = refused = 0
    for _ in range(200):
        s = int(rng.integers(4))
        if pos[s] is None:
            n = int(rng.integers(1, 40))
            if c.alloc(s, n):
                pos[s] = n
            else:
                refused += 1
        elif rng.random() < 0.1 or pos[s] >= 120:
            c.free_slot(s)
            pos[s] = None
        elif c.ensure(s, pos[s]):
            pos[s] += 1
            grew += 1
            first = max(0, pos[s] - 10) // 4
            assert c.window_tables[s, first] != 0
            assert c.block_tables[s, (pos[s] - 1) // 4] != 0
        else:
            c.free_slot(s)                   # the scheduler would preempt
            pos[s] = None
        c.check()
        assert c.window_blocks_used <= 4 * c.window_blocks_per_seq
    assert grew > 80
    for s in range(4):
        c.free_slot(s)
    c.check()
    assert c.window_blocks_used == 0 and c.num_used_blocks == 0


def test_preemption_and_readmission_are_identical_under_greedy():
    """A pool too small for every session preempts the youngest; its
    greedy rerun serves the same tokens as a roomy pool does, both pools
    drain to empty, and the sliding pool alone can force it."""
    cfg = tiny_cfg()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, n) for n in (10, 12, 9)]

    def run(**spec):
        served = build_server(cfg, 21, batch_slots=3, **spec)
        served.server.max_preemptions = None     # retry without end
        reqs = [served.submit(p, 40, seed=i)
                for i, p in enumerate(prompts)]
        while served.busy():
            served.step()
            served.server.cache.check()
        kv = served.server.cache
        assert kv.window_blocks_used == 0 and kv.num_used_blocks == 0
        return [list(r.output_tokens) for r in reqs], \
            served.server.preemptions, [r.status for r in reqs]

    roomy, n0, _ = run()
    tight, n1, status = run(num_blocks=9)             # the full pool
    wtight, n2, wstatus = run(window_num_blocks=5)    # the sliding pool
    assert n0 == 0 and n1 > 0 and n2 > 0
    assert status == wstatus == ["ok"] * 3
    assert tight == roomy and wtight == roomy


#: (prompt tokens, new tokens, sampled) and the `step()`s between the
#: arrivals; contexts end below, across and beyond the window of 16
AHEAD_MIX = [(6, 20, False), (12, 14, True), (30, 10, False),
             (9, 18, True), (20, 12, False)]
AHEAD_ARRIVALS = [(2, 2), (2, 3), (1, 0)]      # (submit, steps)
#: what commit 268f475 (the serial tick) served for AHEAD_MIX, seed 17
AHEAD_PINNED = [
    [197, 204, 102, 198, 223, 123, 169, 71, 125, 186, 147, 209, 157, 36,
     176, 161, 254, 210, 255, 3],
    [52, 211, 39, 174, 116, 238, 194, 173, 38, 190, 48, 248, 80, 113],
    [210, 252, 151, 222, 46, 158, 190, 160, 187, 247],
    [163, 97, 147, 236, 222, 143, 3, 54, 174, 1, 210, 26, 52, 70, 57,
     191, 223, 205],
    [93, 85, 178, 197, 105, 122, 7, 145, 223, 150, 16, 100]]


@pytest.mark.parametrize("eos_at", [None, 4])
def test_a_tick_queued_ahead_serves_the_serial_orders_tokens(eos_at):
    """Two kinds of layer under the tick that is launched before the
    one ahead of it is read: greedy and sampled requests arriving
    between ticks hold, one by one, the tokens the serial order served;
    one ended by `eos_id` holds them up to that token and not the row
    that was already queued behind it; both pools drain to empty."""
    cfg = tiny_cfg()
    served = build_server(cfg, 17, batch_slots=3)
    srv = served.server
    rng = np.random.default_rng(9)
    sampling = {"temperature": 0.7, "top_k": 20, "top_p": 0.9}
    todo = list(enumerate(AHEAD_MIX))
    reqs = []
    for n_submit, n_steps in AHEAD_ARRIVALS:
        for _ in range(n_submit):
            i, (n, new, sampled) = todo.pop(0)
            reqs.append(served.submit(
                rng.integers(0, cfg["vocab_size"], n), new,
                sampling if sampled else None, seed=i))
        if eos_at is not None:
            reqs[0].eos_id = AHEAD_PINNED[0][eos_at]
        for _ in range(n_steps):
            srv.step()
    while served.busy():
        srv.step()
        srv.cache.check()
    want = [list(t) for t in AHEAD_PINNED]
    if eos_at is not None:
        want[0] = want[0][:eos_at + 1]
        assert reqs[0].finish_reason == "eos"
    assert [list(r.output_tokens) for r in reqs] == want
    st = srv.stats()
    assert st["tokens_generated"] == sum(len(t) for t in want)
    assert 0 < st["ticks_ahead"] < st["ticks"]
    # the tick with the dropped row held other requests' rows too, so
    # it was handed over like any other
    assert st["decode_calls"] == st["ticks"]
    assert not srv._flights
    kv = srv.cache
    assert kv.window_blocks_used == 0 and kv.num_used_blocks == 0


# -- (6) what the server refuses ---------------------------------------------

@pytest.mark.parametrize("feature,kw", [
    ("prefill_chunk", {"prefill_chunk_tokens": 8}),
    ("speculative", {"speculative": 2}),
    ("lora", {"lora": True}),
    ("int8", {"kv_cache_dtype": "int8"}),
    ("prefix_cache", {"prefix_cache": True}),
    ("kv_tier", {"kv_tiering": True}),
])
def test_unsupported_combinations_raise_by_name(feature, kw):
    net = mx.models.get_model("afmoe_tiny", held_experts=(0, 4))
    net.initialize()
    with pytest.raises(NotImplementedError) as e:
        InferenceServer(net, batch_slots=2, max_len=64, **kw)
    assert feature in str(e.value) and "AfmoeDecoder" in str(e.value)
    assert "sliding" in str(e.value)


def test_the_llama_block_serves_through_the_same_seam():
    """One prefill and one decode executable for the Llama block, its
    decode program handing back no counts."""
    net = mx.models.get_model("llama_tiny")
    net.initialize()
    srv = InferenceServer(net, batch_slots=2, max_len=64)
    assert type(srv.decoder).__name__ == "LlamaDecoder"
    assert not srv.decoder.mixed and srv.cache.window_tables is None
    r = srv.submit(np.arange(9), max_new_tokens=6)
    srv.run()
    assert r.status == "ok" and len(r.output_tokens) == 6
    cs = srv.compile_stats()
    assert cs["prefill_compiles"] == cs["decode_compiles"] == 1
    assert "pairs" not in cs and "window_blocks_used" not in cs


def test_get_model_takes_published_and_held_experts_separately():
    net = mx.models.get_model("afmoe_tiny", num_experts=16,
                              held_experts=(8, 4))
    net.initialize()
    shapes = {n: p.shape for n, p in net.collect_params().items()}
    assert shapes["model.layers.1.router"] == (16, 64)
    assert shapes["model.layers.1.ex_gate"] == (4, 64, 32)
    assert "model.layers.0.router" not in shapes        # dense layer
    assert net.model.cfg.held_lo == 8
    with pytest.raises(ValueError, match="no range"):
        mx.models.get_model("afmoe_tiny", held_experts=(14, 4))


# -- (8) the benchmark's afmoe cell, tiny ------------------------------------

def test_tiny_rehearsal_of_the_afmoe_cell(interpret):
    """perfbench/rehearsal.json may not grow outside a benchmark PR, so
    the cell's tiny preset is a file of its own, laid over the cell."""
    tiny = harness.load_json(harness.HERE, "rehearsal.afmoe.json")
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in bm["workloads"]
             if harness.Cell(w["name"], bm).config["family"]
             == "afmoe_decoder"]
    assert len(cells) == 1
    cell = harness.Cell(cells[0], bm)
    cell.config = rehearse.merge(cell.config, tiny["config"])
    cell.traffic = rehearse.merge(cell.traffic, tiny["traffic"])
    result = rehearse.run_tiny(cell, 2 ** 31 + 4242, 1.5)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 3 and result["metrics"] == {}
