"""Sarvam's latent-attention decoder (`sarvam_mla`) through the normal
serving path, against the plain NON-absorbed float32 reference
(perfbench/reference/mla_moe_decoder.py), at tiny sizes on the CPU:
served logits, the absorbed decode against the materialised layer, the
YaRN numbers, the shares of the expert-parallel deployment adding up,
the controls, the latent cache under preemption, what the server
refuses, and the tiny rehearsal of the benchmark's cell."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.models import mla_math  # noqa: E402
from mxnet_tpu.models.decoder import LATENT  # noqa: E402
from mxnet_tpu.parallel.moe import held_expert_ffn, route_top_k  # noqa: E402
from mxnet_tpu.serving import InferenceServer  # noqa: E402
from mxnet_tpu.serving.kv_cache import PagedKVCache  # noqa: E402
from perfbench import harness, rehearse, schedule  # noqa: E402
from perfbench.reference import mla_moe_decoder as ref  # noqa: E402


def tiny_cfg(**over):
    """The benchmark's configuration file under its tiny preset."""
    cfg = rehearse.merge(
        harness.load_json(harness.HERE, "configs", "sarvam_105b.json"),
        harness.load_json(harness.HERE, "rehearsal.sarvam.json")["config"])
    cfg.update(over)
    return cfg


def build_server(cfg, seed, **spec):
    from perfbench.families import mla_moe_decoder as family

    spec = dict({"batch_slots": 4, "max_len": 96, "max_prompt_len": 64,
                 "kv_cache_dtype": "model"}, **spec)
    return family.build(cfg, spec, seed, jax.devices()[:1])


def reference_logits(cfg, seed, reqs):
    """(T, vocab) of the reference's full forward over each request's
    prompt and served tokens."""
    ids = [np.concatenate([r.prompt, r.output_tokens])[:-1] for r in reqs]
    with jax.default_matmul_precision("highest"):
        xs, _ = ref.forward(cfg, seed, ids, q_block=8)
        ends = ref.Weights(cfg, seed).ends()
        return [np.asarray(ref._rms(x, ends["norm"], cfg["rms_norm_eps"])
                           @ ends["head"].T) for x in xs]


# -- (1) the served model against the reference ------------------------------

@pytest.mark.parametrize("kernels", ["jnp", "interpreted"])
def test_served_logits_match_the_reference(kernels, monkeypatch):
    """Prefill (keys and values materialised) then decode (absorbed,
    through the paged latents) equals the reference's one full forward,
    logits compared at every served position, for ragged prompts;
    greedy and sampled rows share the batch."""
    if kernels == "interpreted":
        for k in ("FLASH", "NORM", "MOE"):
            monkeypatch.setenv(f"MXNET_TPU_{k}_INTERPRET", "1")
    cfg = tiny_cfg()
    served = build_server(cfg, 11)
    srv = served.server
    assert set(srv.decoder.layer_kinds) == {LATENT}
    rng = np.random.default_rng(5)
    sampling = {"temperature": 0.7, "top_k": 20, "top_p": 0.9}
    reqs = [served.submit(rng.integers(0, cfg["vocab_size"], n), 24,
                          sampling if i % 2 else None, seed=i)
            for i, n in enumerate((6, 12, 40, 23))]
    # the rows read before step k chose the token of tick k + 1 (the
    # server keeps one tick queued ahead; tests/test_afmoe_serving.py)
    before = []
    while served.busy():
        before.append(np.asarray(srv._last_logits))
        srv.step()
        srv.cache.check()
        if len(before) == 1:
            slot_of = {id(r): srv._slot_req.index(r) for r in reqs}
    assert len(before) == 24 and not srv._flights
    assert all(served.ok(r) for r in reqs)
    cs = srv.compile_stats()
    assert cs["prefill_compiles"] == cs["decode_compiles"] == 1
    for r, want in zip(reqs, reference_logits(cfg, 11, reqs)):
        n, checked = len(r.prompt), 0
        for j in range(3, 25):
            np.testing.assert_allclose(
                before[j - 2][slot_of[id(r)]], want[n - 1 + j - 1],
                atol=2e-4, rtol=2e-4)
            checked += 1
        assert checked >= 20
    gaps = ref.served_token_gaps(
        cfg, 11, [served.tokens(r) for r in reqs if r.temperature == 0],
        q_block=8)
    assert max(float(g.max()) for g in gaps) < 1e-4


def test_a_request_admitted_mid_run_and_a_reused_slot():
    """Three slots, five greedy requests: two arrive while the others
    decode, one of them waits for a finished request's slot; each holds
    the tokens the reference puts first at every position, its first
    (the prefill's logits) among them."""
    cfg = tiny_cfg()
    served = build_server(cfg, 13, batch_slots=3)
    srv = served.server
    rng = np.random.default_rng(8)
    plan = [(9, 6), (30, 20), (17, 12), (5, 10), (44, 8)]
    reqs = [served.submit(rng.integers(0, cfg["vocab_size"], n), new)
            for n, new in plan[:3]]
    for _ in range(4):
        srv.step()
    reqs += [served.submit(rng.integers(0, cfg["vocab_size"], n), new)
             for n, new in plan[3:]]
    slots = set()
    while served.busy():
        srv.step()
        srv.cache.check()
        slots |= {srv._slot_req.index(r) for r in reqs[3:]
                  if r in srv._slot_req}
    assert all(served.ok(r) for r in reqs) and slots <= {0, 1, 2}
    assert srv.cache.num_used_blocks == 0
    gaps = ref.served_token_gaps(cfg, 13, [served.tokens(r) for r in reqs],
                                 q_block=8)
    assert [len(g) for g in gaps] == [new for _, new in plan]
    assert max(float(g.max()) for g in gaps) < 1e-4


# -- (2) absorbed against materialised ---------------------------------------

def test_absorbed_decode_equals_the_materialised_layer():
    """`layer_qkv` + attention over the cached rows + `layer_finish`
    (W_uk in the query, W_uv on the output) is `decoder_layer` (keys
    and values per head, causal softmax) to float32 rounding, on the
    rows that very prefill would cache."""
    net = mx.models.get_model("sarvam_mla_tiny", held_experts=(0, 16))
    net.initialize()
    cfg = net.model.cfg
    rng = np.random.default_rng(3)
    lp = {r: jnp.asarray(rng.normal(0, 1 if len(p.shape) == 1 else .2,
                                    p.shape), jnp.float32)
          for r, p in ((r, getattr(net.model.layers[1], r))
                       for r in net.model.layers[1].roles)}
    B, T = 2, 19
    x = jnp.asarray(rng.normal(0, 1, (B, T, cfg.hidden_size)), jnp.float32)
    pos = jnp.arange(T)
    with jax.default_matmul_precision("highest"):
        # T = 19 is no whole 128-row tile: the gate takes the jnp twin
        want, row, _ = mla_math.decoder_layer(lp, x, pos, cfg)
        q, row2 = mla_math.layer_qkv(lp, x, pos, cfg)
        np.testing.assert_array_equal(row, row2)
        assert row.shape == (B, T, 1, cfg.cache_row)
        assert not np.asarray(row[..., cfg.kv_lora_rank
                                  + cfg.qk_rope_head_dim:]).any()
        s = jnp.einsum("bthr,bsr->bhts", q, row[:, :, 0]) \
            * mla_math.softmax_scale(cfg)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        att = jnp.einsum("bhts,bsl->bthl", jax.nn.softmax(s, -1),
                         row[:, :, 0, :cfg.kv_lora_rank])
        got, _ = mla_math.layer_finish(lp, x, att, cfg)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    assert float(jnp.abs(want - x).max()) > 0.1


def test_yarn_frequencies_and_scale_by_hand():
    """(factor 40, original 4,096, beta_fast 32, beta_slow 1, dim 64,
    base 10000): the correction range is dims 10..23 of the 32; below
    it the plain frequency, above it a fortieth, a linear blend
    between; the softmax scale is 192^-1/2 * (0.1 ln 40 + 1)^2."""
    inv = mla_math.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32, 1)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    # 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 -> 10;
    # 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(          # dim 16: ramp 6 / 13
        inv[16], 0.01 / 40 * (6 / 13) + 0.01 * (7 / 13), rtol=1e-6)
    np.testing.assert_allclose(inv[16], 0.00550, rtol=1e-3)
    assert mla_math.yarn_mscale(40.0, 1.0) == pytest.approx(1.368888,
                                                            rel=1e-6)
    net = mx.models.get_model("sarvam_mla", num_layers=1, vocab_size=8,
                              held_experts=(0, 1))
    cfg = net.model.cfg
    np.testing.assert_array_equal(cfg.rope_inv_freq, inv)
    assert mla_math.softmax_scale(cfg) == pytest.approx(0.135234,
                                                        rel=1e-5)
    assert (cfg.q_head_dim, cfg.cache_row, cfg.num_kv_heads) \
        == (192, 640, 1)
    # the reference computes the same numbers on its own
    published = harness.load_json(harness.HERE, "configs",
                                  "sarvam_105b.json")
    np.testing.assert_allclose(ref.rope_frequencies(published), inv,
                               rtol=1e-6)
    assert ref.softmax_scale(published) == pytest.approx(0.135234,
                                                         rel=1e-5)
    assert ref.softmax_scale(published, False) == pytest.approx(
        0.0721688, rel=1e-5)


# -- (3) the shares add up, the controls -------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """8 shares of 2 held experts: their routed parts plus the shared
    expert counted once are the uncut reference's feed-forward, and the
    program's share equals the reference's share."""
    uncut = tiny_cfg(num_experts=16, held_experts_lo=0)
    lp = ref.Weights(uncut, 9).layer(2)
    m = jax.random.normal(jax.random.PRNGKey(4), (37, 64), jnp.float32)
    shared, whole = ref.ffn_parts(uncut, lp, m)
    total = np.zeros_like(np.asarray(whole))
    for rank in range(8):
        lo = 2 * rank
        share = tiny_cfg(num_experts=2, held_experts_lo=lo)
        lps = dict(lp, **{k: lp[k][lo:lo + 2]
                          for k in ("ex_gate", "ex_up", "ex_down")})
        sh, part = ref.ffn_parts(share, lps, m)
        np.testing.assert_allclose(sh, shared, atol=1e-6)
        got, *_ = held_expert_ffn(
            m, lps["router"], lps["bias"], lps["ex_gate"], lps["ex_up"],
            lps["ex_down"], lo=lo, top_k=2, route=route_top_k,
            route_scale=share["routed_scaling_factor"])
        np.testing.assert_allclose(got, part, atol=2e-5)
        total += np.asarray(part)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert float(np.abs(np.asarray(whole)).max()) > 1e-2


def test_each_control_changes_the_answer():
    """Every control of the output check moves the reference off the
    served tokens, the ones the chip's check cannot fail at seeded
    weights among them (PERF.md section 2)."""
    cfg = tiny_cfg(num_hidden_layers=2)
    rng = np.random.default_rng(2)
    seq = [(rng.integers(0, 256, 24), rng.integers(0, 256, 16))]
    for control in ref.CONTROLS:
        gaps = ref.served_token_gaps(cfg, 3, seq, q_block=8,
                                     control=control)
        assert float(gaps[0].max()) > 1e-3, control
    with pytest.raises(ValueError, match="unknown control"):
        ref.served_token_gaps(cfg, 3, seq, q_block=8, control="int4")


def test_the_cells_check_fails_the_controls_put_in_the_programs_place():
    """perfbench/control_check.py, the committed driver of the readings
    the cell's limits rest on (on the chip at the cell's sizes, PERF.md
    section 2), here on the tiny cell: the two controls the mean-gap
    limit is held to come out not correct, by that limit."""
    from perfbench import control_check

    cell = rehearse.tiny_cell("sarvam_105b.longctx64")
    # enough tokens for fp8 to move a first choice at these widths
    cell.traffic = rehearse.merge(cell.traffic, {
        "finishing": 3, "finishing_remaining": {"min": 40, "max": 60}})
    seqs = control_check.finishing_sequences(cell.config, cell.traffic, 7)
    assert [len(c) for c, _ in seqs] == [
        s["context"] for s in schedule.closed_loop(cell.traffic)[
            "initial"][:cell.traffic["finishing"]]]
    out = control_check.run(cell, 2 ** 31 + 4242, None,
                            ("fp8", "no_rope_term"))
    assert list(out) == ["fp8", "no_rope_term"]
    for name, r in out.items():
        assert r["correct"] is False, name
        assert r["served_tokens"] == sum(len(s) for _, s in seqs)
        mean, limit = r["checks"]["mean_served_logit_gap"]
        assert mean > cell.traffic["limits"]["mean_logit_gap"], name
        assert limit == f"<= {cell.traffic['limits']['mean_logit_gap']}"


# -- (4) the latent cache ----------------------------------------------------

def test_a_latent_layers_pool_is_one_row_a_position():
    c = PagedKVCache(num_layers=2, num_kv_heads=1, head_dim=128,
                     num_blocks=9, block_size=8, batch_slots=2,
                     max_blocks_per_seq=4, dtype=jnp.bfloat16,
                     layer_kinds=("latent",) * 2)
    assert [sorted(p) for p in c.pages] == [["k"]] * 2
    assert c.pages[0]["k"].shape == (9, 1, 8, 128)
    assert c.latent_pool_bytes == 2 * 9 * 8 * 128 * 2
    assert c.stats()["latent_pool_tokens"] == 8 * 8
    assert c.alloc(0, 20) and c.ensure(0, 24)
    c.check()
    c.pages[1]["v"] = c.pages[1]["k"]
    with pytest.raises(AssertionError, match="latent layer's pools"):
        c.check()
    with pytest.raises(NotImplementedError, match="one row a position"):
        PagedKVCache(num_layers=1, num_kv_heads=2, head_dim=128,
                     num_blocks=9, block_size=8, batch_slots=2,
                     max_blocks_per_seq=4, layer_kinds=("latent",))
    assert "latent_pool_bytes" not in PagedKVCache(
        num_layers=1, num_kv_heads=1, head_dim=8, num_blocks=9,
        block_size=8, batch_slots=2, max_blocks_per_seq=4).stats()


def test_preemption_and_readmission_are_identical_under_greedy():
    """A pool too small for every session preempts the youngest; its
    greedy rerun serves the same tokens as a roomy pool does, `check()`
    holds after every step and the pool drains to empty."""
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
        assert served.server.cache.num_used_blocks == 0
        return [list(r.output_tokens) for r in reqs], \
            served.server.preemptions, [r.status for r in reqs]

    roomy, n0, _ = run()
    tight, n1, status = run(num_blocks=9)
    assert n0 == 0 and n1 > 0 and status == ["ok"] * 3
    assert tight == roomy


def test_the_counters_say_what_a_cached_token_costs():
    cfg = tiny_cfg()
    served = build_server(cfg, 5, batch_slots=2, num_blocks=13)
    r = served.submit(np.arange(20) % 256, 6)
    while served.busy():
        served.step()
    assert served.ok(r)
    st = served.server.stats()
    # 3 layers x 13 blocks x 16 positions x a row of 128 float32
    assert st["latent_pool_bytes"] == 3 * 13 * 16 * 128 * 4
    assert st["latent_pool_tokens"] == 12 * 16
    cs = served.counters()
    assert cs["latent_pool_bytes"] == st["latent_pool_bytes"]
    # a row of 128 float32 in each of 3 layers, the scratch block's
    # bytes shared out over the other 12: the cell's metric
    assert cs["latent_pool_bytes"] / cs["latent_pool_tokens"] \
        == 3 * 512 * 13 / 12
    assert cs["moe_layers"] == 2 and cs["held_experts"] == 4
    assert served.server.compile_stats()["context_tokens"] > 0
    assert set(served.server.decoder_counts) == {
        "pairs", "touched", "prefill_pairs", "prefill_touched"}


# -- (5) what the server refuses ---------------------------------------------

@pytest.mark.parametrize("feature,kw", [
    ("prefill_chunk", {"prefill_chunk_tokens": 8}),
    ("speculative", {"speculative": 2}),
    ("lora", {"lora": True}),
    ("int8", {"kv_cache_dtype": "int8"}),
    ("prefix_cache", {"prefix_cache": True}),
    ("kv_tier", {"kv_tiering": True}),
])
def test_unsupported_combinations_raise_by_name(feature, kw):
    net = mx.models.get_model("sarvam_mla_tiny", held_experts=(0, 4))
    net.initialize()
    with pytest.raises(NotImplementedError) as e:
        InferenceServer(net, batch_slots=2, max_len=64, **kw)
    assert feature in str(e.value) and "SarvamDecoder" in str(e.value)
    assert "latent" in str(e.value)


def test_get_model_takes_published_and_held_experts_separately():
    net = mx.models.get_model("sarvam_mla_tiny", num_experts=16,
                              held_experts=(8, 4))
    net.initialize()
    shapes = {n: p.shape for n, p in net.collect_params().items()}
    assert shapes["model.layers.1.router"] == (16, 64)
    assert shapes["model.layers.1.ex_gate"] == (4, 64, 32)
    assert shapes["model.layers.1.wkv_a"] == (40, 64)
    assert shapes["model.layers.1.wkv_b"] == (4 * 32, 32)
    assert "model.layers.0.router" not in shapes        # dense layer
    assert net.model.cfg.held_lo == 8
    out = net(mx.nd.array(np.arange(12)[None] % 256, dtype="int32"))
    assert out.shape == (1, 12, 256)
    with pytest.raises(ValueError, match="no range"):
        mx.models.get_model("sarvam_mla_tiny", held_experts=(14, 4))


# -- (6) the benchmark's cell, tiny ------------------------------------------

def test_tiny_rehearsal_of_the_sarvam_cell(monkeypatch):
    """perfbench/rehearsal.json may not grow outside a benchmark PR, so
    the cell's tiny preset is a file of its own, laid over the cell."""
    for k in ("FLASH", "NORM", "MOE"):
        monkeypatch.setenv(f"MXNET_TPU_{k}_INTERPRET", "1")
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in bm["workloads"]
             if harness.Cell(w["name"], bm).config["family"]
             == "mla_moe_decoder"]
    assert cells == ["sarvam_105b.longctx64"]
    cell = rehearse.tiny_cell(cells[0], bm)
    assert cell.config["kv_lora_rank"] == 32
    result = rehearse.run_tiny(cell, 2 ** 31 + 4242, 1.5)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 3 and result["metrics"] == {}
