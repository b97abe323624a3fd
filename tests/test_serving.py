"""Continuous-batching inference server: paged KVCache allocator,
block-table decode parity, persistent-executable compile accounting,
scheduler admit/evict/preempt semantics, per-request sampling
isolation, and token parity vs one-shot generate()."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry, tracing
from mxnet_tpu.models.llama_infer import generate
from mxnet_tpu.serving import InferenceServer, PagedKVCache
from mxnet_tpu.serving import executables as exe


@pytest.fixture(scope="module")
def net():
    mx.random.seed(0)
    n = mx.models.get_model("llama_tiny")
    n.initialize()
    n(mx.nd.array(np.zeros((1, 4)), dtype="int32"))  # materialize
    return n


def _cache(**kw):
    args = dict(num_layers=2, num_kv_heads=2, head_dim=8,
                num_blocks=9, block_size=4, batch_slots=3,
                max_blocks_per_seq=4)
    args.update(kw)
    return PagedKVCache(**args)


# -- PagedKVCache allocator -------------------------------------------------

def test_alloc_distinct_blocks_and_table():
    c = _cache()
    assert c.alloc(0, 7)          # 2 blocks
    assert c.alloc(1, 9)          # 3 blocks
    a, b = c.slot_blocks(0), c.slot_blocks(1)
    assert len(a) == 2 and len(b) == 3
    assert not (set(a) & set(b))
    assert 0 not in a + b         # scratch never handed out
    # table rows hold the physical ids in logical order, 0 elsewhere
    assert list(c.block_tables[0, :2]) == a
    assert list(c.block_tables[0, 2:]) == [0, 0]
    c.check()


def test_alloc_fails_without_blocks_and_leaves_state_clean():
    c = _cache(num_blocks=4)      # 3 usable
    assert c.alloc(0, 12)         # takes all 3
    assert not c.alloc(1, 5)      # needs 2, none free
    assert c.num_free_blocks == 0
    assert c.slot_blocks(1) == []
    c.check()


def test_free_returns_blocks_and_clears_table():
    c = _cache()
    c.alloc(0, 16)
    used = c.slot_blocks(0)
    c.free_slot(0)
    assert c.num_free_blocks == 8
    assert (c.block_tables[0] == 0).all()
    # freed blocks are reusable
    assert c.alloc(1, 16)
    assert set(c.slot_blocks(1)) == set(used) or c.num_free_blocks == 4
    c.check()


def test_ensure_allocates_on_block_boundary_only():
    c = _cache()
    c.alloc(0, 4)                 # exactly 1 block
    free0 = c.num_free_blocks
    assert c.ensure(0, 3)         # still inside block 0
    assert c.num_free_blocks == free0
    assert c.ensure(0, 4)         # crosses into block 1
    assert c.num_free_blocks == free0 - 1
    assert c.slot_len(0) == 5
    c.check()


def test_fragmentation_interleaved_alloc_free_conserves_blocks():
    c = _cache(num_blocks=13, batch_slots=4, max_blocks_per_seq=3)
    rs = np.random.RandomState(0)
    held = {}
    for _ in range(200):
        slot = rs.randint(4)
        if slot in held:
            c.free_slot(slot)
            del held[slot]
        else:
            n = int(rs.randint(1, 12))
            if c.alloc(slot, n):
                held[slot] = n
        c.check()
    st = c.stats()
    assert st["used_blocks"] + st["free_blocks"] == 12
    assert st["allocs"] - st["frees"] == st["used_blocks"]


def test_alloc_beyond_max_blocks_raises():
    c = _cache()
    with pytest.raises(ValueError):
        c.alloc(0, 17)            # 5 blocks > max_blocks_per_seq=4


def test_quantized_cache_page_shapes():
    c = _cache(quantized=True)
    pg = c.pages[0]
    assert pg["k"].dtype == jnp.int8 and pg["v"].dtype == jnp.int8
    assert pg["ks"].shape == (9, 2, 4, 1)
    assert pg["ks"].dtype == jnp.float32


# -- the paged row write ----------------------------------------------------

@pytest.mark.parametrize("quantized", [False, True],
                         ids=["model", "int8"])
@pytest.mark.parametrize("T", [1, 20, 2048])
@pytest.mark.parametrize("K", [1, 4, 8])
def test_write_rows_equals_the_indexed_write(K, T, quantized):
    """`write_rows` stores through the pool's (N, K*bs, d) view; the
    pool it returns is, bit for bit, what the plain indexed write
    `pool.at[blk, :, offs, :].set(rows)` gives — rows masked to the
    scratch block 0 (several to one position) among them."""
    rs = np.random.RandomState(K * 10007 + T)
    bs, d = 16, 32
    N = max(T // bs, 1) + 12
    shape = (N, K, bs, d)
    if quantized:
        pg = {"k": rs.randint(-127, 128, shape).astype(np.int8),
              "ks": rs.rand(N, K, bs, 1).astype(np.float32),
              "v": rs.randint(-127, 128, shape).astype(np.int8),
              "vs": rs.rand(N, K, bs, 1).astype(np.float32)}
    else:
        pg = {f: rs.randn(*shape).astype(jnp.bfloat16)
              for f in ("k", "v")}
    pg = {f: jnp.asarray(a) for f, a in pg.items()}
    # a prompt's positions through a shuffled block table, as prefill
    # writes them: the head (a shared prefix) and the tail (padding)
    # sink into scratch; a decode tick's inactive rows do the same
    t = np.arange(T)
    table = 1 + rs.permutation(N - 1)
    keep = (t >= T // 8) & (t < T - T // 5) if T > 1 else t >= 0
    blk = jnp.asarray(np.where(keep, table[t // bs], 0), jnp.int32)
    offs = jnp.asarray(np.where(keep, t % bs, t % 2), jnp.int32)
    k_rows = jnp.asarray(rs.randn(T, K, d), jnp.bfloat16)
    v_rows = jnp.asarray(rs.randn(T, K, d), jnp.bfloat16)

    def indexed(pg, blk, offs, k_rows, v_rows):
        rows = {"k": k_rows, "v": v_rows}
        if quantized:
            rows["k"], rows["ks"] = exe._quant_rows(k_rows)
            rows["v"], rows["vs"] = exe._quant_rows(v_rows)
        return {f: pg[f].at[blk, :, offs, :].set(rows[f]) for f in pg}

    got = jax.jit(exe.write_rows)(pg, blk, offs, k_rows, v_rows)
    want = jax.jit(indexed)(pg, blk, offs, k_rows, v_rows)
    assert set(got) == set(pg)
    for f in pg:
        assert got[f].shape == pg[f].shape and got[f].dtype == pg[f].dtype
        assert np.array_equal(np.asarray(got[f]), np.asarray(want[f])), f
    # and it did write: a kept row is in its place
    if not quantized:
        b, o = int(blk[T // 2]), int(offs[T // 2])
        assert np.array_equal(np.asarray(got["k"][b, :, o]),
                              np.asarray(k_rows[T // 2]))


# -- block-table gather path ------------------------------------------------

def test_flash_decode_paged_matches_contiguous():
    from mxnet_tpu.kernels.flash_decode import (flash_decode,
                                                flash_decode_paged)
    rs = np.random.RandomState(3)
    B, K, H, d, bs, nb = 2, 2, 4, 8, 4, 4
    S = nb * bs
    k = rs.randn(B, K, S, d).astype(np.float32)
    v = rs.randn(B, K, S, d).astype(np.float32)
    q = rs.randn(B, H, d).astype(np.float32)
    vl = np.array([S - 3, 5], np.int32)
    # scatter the contiguous caches into a shuffled page pool
    N = B * nb + 1
    perm = 1 + rs.permutation(N - 1)
    bt = perm.reshape(B, nb).astype(np.int32)
    kp = np.zeros((N, K, bs, d), np.float32)
    vp = np.zeros((N, K, bs, d), np.float32)
    for b in range(B):
        for j in range(nb):
            kp[bt[b, j]] = k[b, :, j * bs:(j + 1) * bs]
            vp[bt[b, j]] = v[b, :, j * bs:(j + 1) * bs]
    ref = flash_decode(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                       jnp.asarray(vl))
    out = flash_decode_paged(jnp.asarray(q), jnp.asarray(kp),
                             jnp.asarray(vp), jnp.asarray(bt),
                             jnp.asarray(vl))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_flash_decode_paged_quantized_matches_contiguous():
    from mxnet_tpu.kernels.flash_decode import (
        flash_decode_quantized, flash_decode_paged_quantized,
        quantize_kv)
    rs = np.random.RandomState(4)
    B, K, H, d, bs, nb = 2, 2, 4, 8, 4, 3
    S = nb * bs
    k = rs.randn(B, K, S, d).astype(np.float32)
    v = rs.randn(B, K, S, d).astype(np.float32)
    q = rs.randn(B, H, d).astype(np.float32)
    vl = np.array([S, 7], np.int32)
    k8, ks, v8, vs = (np.asarray(x) for x in
                      quantize_kv(jnp.asarray(k), jnp.asarray(v)))
    N = B * nb + 1
    bt = (1 + rs.permutation(N - 1)).reshape(B, nb).astype(np.int32)
    k8p = np.zeros((N, K, bs, d), np.int8)
    ksp = np.zeros((N, K, bs, 1), np.float32)
    v8p = np.zeros((N, K, bs, d), np.int8)
    vsp = np.zeros((N, K, bs, 1), np.float32)
    for b in range(B):
        for j in range(nb):
            sl = slice(j * bs, (j + 1) * bs)
            k8p[bt[b, j]], ksp[bt[b, j]] = k8[b, :, sl], ks[b, :, sl]
            v8p[bt[b, j]], vsp[bt[b, j]] = v8[b, :, sl], vs[b, :, sl]
    ref = flash_decode_quantized(*(jnp.asarray(x) for x in
                                   (q, k8, ks, v8, vs, vl)))
    out = flash_decode_paged_quantized(
        jnp.asarray(q), jnp.asarray(k8p), jnp.asarray(ksp),
        jnp.asarray(v8p), jnp.asarray(vsp), jnp.asarray(bt),
        jnp.asarray(vl))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# -- persistent executables -------------------------------------------------

def test_generate_reuses_compiled_executables(net):
    exe.reset_programs(net)
    tracing.reset_cache_stats()
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, 256, (2, 4)).astype(np.int32)
    a = generate(net, prompt, max_new_tokens=5)
    b = generate(net, prompt, max_new_tokens=5)
    np.testing.assert_array_equal(a, b)
    per = tracing.cache_stats()["per_block"]
    assert per["gen_prefill"]["compiles"] == 1
    assert per["gen_prefill"]["hits"] == 1
    assert per["gen_scan_greedy"]["compiles"] == 1
    assert per["gen_scan_greedy"]["hits"] == 1
    assert per["gen_prefill"]["compile_seconds"] > 0


def test_sampling_params_do_not_retrace(net):
    """temperature/top_k/top_p are traced vectors: changing them hits
    the SAME executable."""
    exe.reset_programs(net)
    tracing.reset_cache_stats()
    rs = np.random.RandomState(6)
    prompt = rs.randint(0, 256, (1, 4)).astype(np.int32)
    generate(net, prompt, max_new_tokens=4, temperature=1.0, top_k=5)
    generate(net, prompt, max_new_tokens=4, temperature=0.3,
             top_p=0.9, seed=2)
    per = tracing.cache_stats()["per_block"]
    assert per["gen_scan_sample"]["compiles"] == 1
    assert per["gen_scan_sample"]["hits"] == 1


def test_generate_beam_reuses_step_program(net):
    from mxnet_tpu.models.llama_infer import generate_beam
    exe.reset_programs(net)
    tracing.reset_cache_stats()
    rs = np.random.RandomState(7)
    prompt = rs.randint(0, 256, (1, 5)).astype(np.int32)
    a = generate_beam(net, prompt, max_new_tokens=3, beam_size=2)
    b = generate_beam(net, prompt, max_new_tokens=3, beam_size=2)
    np.testing.assert_array_equal(a, b)
    per = tracing.cache_stats()["per_block"]
    assert per["gen_step"]["compiles"] == 1
    assert per["gen_step"]["hits"] >= 1


def test_per_row_sampling_params(net):
    """(B,) sampling vectors: a greedy row rides next to a hot row in
    one call and still matches its solo greedy decode."""
    rs = np.random.RandomState(8)
    prompt = rs.randint(0, 256, (2, 5)).astype(np.int32)
    out = generate(net, prompt, max_new_tokens=5,
                   temperature=np.array([1.5, 0.0], np.float32),
                   top_k=np.array([20, 0], np.int32), seed=4)
    solo = generate(net, prompt[1:2], max_new_tokens=5)
    np.testing.assert_array_equal(out[1], solo[0])


# -- ragged prompts + eos ---------------------------------------------------

def test_ragged_prompts_match_per_row_solo(net):
    rs = np.random.RandomState(9)
    ids = np.zeros((3, 8), np.int32)
    lens = [8, 3, 5]
    for i, L in enumerate(lens):
        ids[i, :L] = rs.randint(0, 256, L)
    out = generate(net, ids, max_new_tokens=4,
                   valid_len=np.array(lens), max_len=16)
    for i, L in enumerate(lens):
        solo = generate(net, ids[i:i + 1, :L], max_new_tokens=4,
                        max_len=16)
        np.testing.assert_array_equal(out[i, 8:], solo[0, L:])


def test_ragged_valid_len_validation(net):
    ids = np.zeros((2, 6), np.int32)
    with pytest.raises(ValueError):
        generate(net, ids, max_new_tokens=2, valid_len=np.array([7, 3]))
    with pytest.raises(ValueError):
        generate(net, ids, max_new_tokens=2, valid_len=np.array([0, 3]))


def test_eos_early_exit_and_finish_positions(net):
    rs = np.random.RandomState(10)
    prompt = rs.randint(0, 256, (2, 4)).astype(np.int32)
    g1 = generate(net, prompt, max_new_tokens=1)
    eos = int(g1[0, -1])          # row 0's greedy next token
    out, fin = generate(net, prompt, max_new_tokens=12, eos_id=eos,
                        return_finished=True)
    assert out.shape == (2, 16)
    assert fin[0] == 0            # row 0 hits eos immediately
    gen0 = out[0, 4:]
    assert (gen0 == eos).all()    # frozen to eos after the hit
    if fin[1] >= 0:               # row 1 may or may not hit eos
        assert out[1, 4 + fin[1]] == eos
        assert (out[1, 4 + fin[1]:] == eos).all()
    # rows that never finish match the plain greedy decode
    plain = generate(net, prompt, max_new_tokens=12)
    if fin[1] < 0:
        np.testing.assert_array_equal(out[1], plain[1])


def test_eos_none_keeps_legacy_contract(net):
    rs = np.random.RandomState(11)
    prompt = rs.randint(0, 256, (1, 4)).astype(np.int32)
    out, fin = generate(net, prompt, max_new_tokens=5,
                        return_finished=True)
    assert fin[0] == -1
    assert out.shape == (1, 9)


# -- the server -------------------------------------------------------------

def _mixed_requests(server, rs, n, eos_id=None):
    reqs = []
    for _ in range(n):
        T = int(rs.randint(3, server.max_prompt_len + 1))
        p = rs.randint(0, 256, T).astype(np.int32)
        new = int(rs.randint(2, 9))
        reqs.append((p, new,
                     server.submit(p, max_new_tokens=new,
                                   eos_id=eos_id)))
    return reqs


def test_server_16_requests_token_parity_one_compile_each(net):
    """The acceptance bar: 16 mixed-length greedy requests through the
    continuous-batching server are token-identical to per-request
    one-shot generate(), with exactly ONE prefill compile and ONE
    decode compile."""
    rs = np.random.RandomState(12)
    server = InferenceServer(net, batch_slots=4, max_len=64,
                             block_size=8, max_prompt_len=12)
    reqs = _mixed_requests(server, rs, 16)
    server.run()
    cs = server.compile_stats()
    assert cs["prefill_compiles"] == 1, cs
    assert cs["decode_compiles"] == 1, cs
    assert cs["prefill_calls"] == 16
    per = tracing.cache_stats()["per_block"]
    assert per["serving_prefill"]["compiles"] == 1
    assert per["serving_prefill"]["hits"] == 15
    assert per["serving_decode"]["compiles"] == 1
    for p, new, r in reqs:
        assert r.state == "finished" and r.finish_reason == "length"
        one = generate(net, p[None, :], max_new_tokens=new, max_len=64)
        np.testing.assert_array_equal(
            np.asarray(r.output_tokens), one[0, len(p):],
            err_msg=f"request {r.id} diverged from one-shot generate")
    # everything was released
    assert server.cache.num_used_blocks == 0
    server.cache.check()


def test_server_admit_evict_ordering(net):
    """FIFO admission; finished slots are evicted and refilled from
    the queue at the next tick."""
    rs = np.random.RandomState(13)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    reqs = [server.submit(rs.randint(0, 256, 4).astype(np.int32),
                          max_new_tokens=2 + i) for i in range(5)]
    server.step()
    # first two admitted in submit order
    assert reqs[0].state == "running" and reqs[1].state == "running"
    assert reqs[2].state == "queued"
    server.run()
    assert [r.state for r in reqs] == ["finished"] * 5
    # completion respects slot reuse: r0 (2 toks) finished first and
    # its slot went to r2 before r3/r4
    fin = sorted(reqs, key=lambda r: r.t_finish)
    assert fin[0] is reqs[0]


def test_server_per_request_sampling_isolation(net):
    rs = np.random.RandomState(14)
    server = InferenceServer(net, batch_slots=3, max_len=64,
                             block_size=8, max_prompt_len=12)
    pg = rs.randint(0, 256, 5).astype(np.int32)
    r_greedy = server.submit(pg, max_new_tokens=6)
    server.submit(rs.randint(0, 256, 9).astype(np.int32),
                  max_new_tokens=6, temperature=1.5, top_k=30, seed=3)
    server.submit(rs.randint(0, 256, 3).astype(np.int32),
                  max_new_tokens=6, temperature=0.8, top_p=0.95,
                  seed=5)
    server.run()
    solo = generate(net, pg[None, :], max_new_tokens=6, max_len=64)
    np.testing.assert_array_equal(np.asarray(r_greedy.output_tokens),
                                  solo[0, 5:])


def test_server_sampled_requests_deterministic_by_seed(net):
    rs = np.random.RandomState(15)
    p = rs.randint(0, 256, 6).astype(np.int32)

    def run_once():
        server = InferenceServer(net, batch_slots=2, max_len=64,
                                 block_size=8, max_prompt_len=8)
        r = server.submit(p, max_new_tokens=6, temperature=1.0,
                          top_k=10, seed=11)
        server.run()
        return list(r.output_tokens)

    assert run_once() == run_once()


def test_server_int8_cache_parity(net):
    rs = np.random.RandomState(16)
    server = InferenceServer(net, batch_slots=2, max_len=64,
                             block_size=8, max_prompt_len=12,
                             kv_cache_dtype="int8")
    reqs = _mixed_requests(server, rs, 4)
    server.run()
    for p, new, r in reqs:
        one = generate(net, p[None, :], max_new_tokens=new,
                       max_len=64, kv_cache_dtype="int8")
        np.testing.assert_array_equal(np.asarray(r.output_tokens),
                                      one[0, len(p):])


def test_server_eos_finishes_early(net):
    rs = np.random.RandomState(17)
    p = rs.randint(0, 256, 5).astype(np.int32)
    g1 = generate(net, p[None, :], max_new_tokens=1, max_len=64)
    eos = int(g1[0, -1])
    server = InferenceServer(net, batch_slots=2, max_len=64,
                             block_size=8, max_prompt_len=8)
    r = server.submit(p, max_new_tokens=10, eos_id=eos)
    server.run()
    assert r.finish_reason == "eos"
    assert r.output_tokens == [eos]


def test_server_preemption_under_tiny_pool(net):
    """Pool holds ~1.5 sequences: the scheduler must preempt the
    younger request, finish the older, then complete the preempted one
    with token-identical greedy output."""
    rs = np.random.RandomState(18)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=12,
                             num_blocks=6)
    pa = rs.randint(0, 256, 10).astype(np.int32)
    pb = rs.randint(0, 256, 10).astype(np.int32)
    ra = server.submit(pa, max_new_tokens=12)
    rb = server.submit(pb, max_new_tokens=12)
    server.run()
    assert ra.state == "finished" and rb.state == "finished"
    assert ra.preemptions + rb.preemptions >= 1
    for p, r in ((pa, ra), (pb, rb)):
        one = generate(net, p[None, :], max_new_tokens=12, max_len=32)
        np.testing.assert_array_equal(np.asarray(r.output_tokens),
                                      one[0, 10:])
    server.cache.check()


def test_server_preemption_cascade_skips_evicted_slots(net):
    """Regression: three slots churning in a 6-block pool. When an
    older slot's ensure() preempts a younger slot that comes later in
    the ensure pass, the pass must skip the now-evicted slot instead
    of allocating a block to the empty slot (which poisoned its next
    admission with 'slot already holds N blocks')."""
    rs = np.random.RandomState(20)
    server = InferenceServer(net, batch_slots=3, max_len=16,
                             block_size=4, max_prompt_len=4,
                             num_blocks=7)
    prompts = [rs.randint(0, 256, 4).astype(np.int32)
               for _ in range(3)]
    reqs = [server.submit(p, max_new_tokens=8) for p in prompts]
    server.run(max_ticks=1000)
    assert all(r.state == "finished" for r in reqs)
    for p, r in zip(prompts, reqs):
        one = generate(net, p[None, :], max_new_tokens=8, max_len=16)
        np.testing.assert_array_equal(np.asarray(r.output_tokens),
                                      one[0, 4:])
    server.cache.check()


def test_server_preemption_token_accounting(net):
    """Regression: tokens regenerated after a preemption must not be
    counted twice into tokens_generated / serving_tokens_total."""
    telemetry.reset()
    telemetry.enable()
    try:
        rs = np.random.RandomState(21)
        server = InferenceServer(net, batch_slots=2, max_len=32,
                                 block_size=8, max_prompt_len=12,
                                 num_blocks=6)
        ra = server.submit(rs.randint(0, 256, 10).astype(np.int32),
                           max_new_tokens=12)
        rb = server.submit(rs.randint(0, 256, 10).astype(np.int32),
                           max_new_tokens=12)
        server.run()
        assert ra.preemptions + rb.preemptions >= 1
        total_out = len(ra.output_tokens) + len(rb.output_tokens)
        assert server.tokens_generated == total_out
        snap = telemetry.snapshot()
        assert snap["counters"]["serving_tokens_total"] == total_out
    finally:
        telemetry.disable()
        telemetry.reset()


def test_server_rejects_request_larger_than_pool(net):
    """Regression: a request whose lifetime KV footprint exceeds the
    whole pool used to sit in the queue forever (run() spun on it);
    submit() now rejects it up front. Requests that do fit the shrunk
    pool still run to completion."""
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=4, max_prompt_len=12,
                             num_blocks=3)
    with pytest.raises(ValueError, match="KV blocks"):
        server.submit(np.arange(12, dtype=np.int32), max_new_tokens=2)
    r = server.submit(np.arange(4, dtype=np.int32), max_new_tokens=4)
    server.run(max_ticks=100)
    assert r.state == "finished"
    server.cache.check()


def test_server_submit_validation(net):
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    with pytest.raises(ValueError):
        server.submit(np.arange(9, dtype=np.int32), max_new_tokens=2)
    with pytest.raises(ValueError):
        server.submit(np.arange(8, dtype=np.int32), max_new_tokens=30)
    with pytest.raises(ValueError):
        server.submit(np.zeros(0, np.int32), max_new_tokens=2)
    with pytest.raises(ValueError):
        InferenceServer(net, max_len=30, block_size=8)


def test_server_telemetry(net):
    telemetry.reset()
    telemetry.enable()
    try:
        rs = np.random.RandomState(19)
        server = InferenceServer(net, batch_slots=2, max_len=32,
                                 block_size=8, max_prompt_len=8)
        for _ in range(3):
            server.submit(rs.randint(0, 256, 5).astype(np.int32),
                          max_new_tokens=3)
        server.run()
        snap = telemetry.snapshot()
        assert snap["histograms"]["serving_ttft_seconds"]["count"] == 3
        assert snap["counters"]["serving_tokens_total"] == 9.0
        assert snap["counters"]["serving_requests_total"] == 3.0
        assert snap["counters"]["serving_requests_finished"] == 3.0
        # phase spans landed in the step-time breakdown
        bd = snap["step_time_breakdown"]
        assert "serve_admit" in bd and "serve_decode" in bd
        assert "serve_prefill" in bd
        assert "serving_queue_depth" in snap["gauges"]
        assert "serving_kv_blocks_free" in snap["gauges"]
        assert snap["histograms"]["serving_tick_seconds"]["count"] >= 3
    finally:
        telemetry.disable()
        telemetry.reset()


def test_server_refresh_params_picks_up_new_weights(net):
    rs = np.random.RandomState(20)
    p = rs.randint(0, 256, 5).astype(np.int32)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    r0 = server.submit(p, max_new_tokens=4)
    server.run()
    gate = net.model.layers[0].mlp.gate_proj.weight
    orig = gate.data().asnumpy()
    try:
        gate.set_data(mx.nd.array(orig + 0.05 * np.sign(orig)))
        server.refresh_params()
        r1 = server.submit(p, max_new_tokens=4)
        server.run()
        one = generate(net, p[None, :], max_new_tokens=4, max_len=32)
        np.testing.assert_array_equal(np.asarray(r1.output_tokens),
                                      one[0, 5:])
    finally:
        gate.set_data(mx.nd.array(orig))
    # no recompile across the weight refresh
    assert server.compile_stats()["decode_compiles"] == 1
    assert r0.output_tokens  # the pre-update run completed too


# -- robustness: deadlines, preemption cap, watchdog, graceful shutdown ------

def test_request_terminal_status_ok(net):
    rs = np.random.RandomState(30)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    reqs = [server.submit(rs.randint(0, 256, 4).astype(np.int32),
                          max_new_tokens=3) for _ in range(3)]
    server.run()
    assert all(r.status == "ok" for r in reqs)
    st = server.stats()["status_counts"]
    assert st == {"ok": 3, "timed_out": 0, "preempted": 0, "rejected": 0,
                  "cancelled": 0}


def test_deadline_expires_queued_request(net):
    rs = np.random.RandomState(31)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    dead = server.submit(rs.randint(0, 256, 4).astype(np.int32),
                         max_new_tokens=4, deadline_s=0.0)
    live = server.submit(rs.randint(0, 256, 4).astype(np.int32),
                         max_new_tokens=4)
    import time as _t
    _t.sleep(0.002)
    server.run()
    assert dead.state == "finished" and dead.status == "timed_out"
    assert dead.finish_reason == "timeout"
    assert dead.output_tokens == []   # never admitted after expiry
    assert live.status == "ok"
    assert server.stats()["status_counts"]["timed_out"] == 1


def test_deadline_expires_running_request(net):
    import time as _t
    rs = np.random.RandomState(32)
    server = InferenceServer(net, batch_slots=1, max_len=64,
                             block_size=8, max_prompt_len=8)
    r = server.submit(rs.randint(0, 256, 4).astype(np.int32),
                      max_new_tokens=40, deadline_s=0.05)
    server.step()                      # admitted + first token
    assert r.state == "running" and r.output_tokens
    _t.sleep(0.06)
    server.run(max_ticks=3)            # next sweep sees it expired
    assert r.status == "timed_out" and r.state == "finished"
    assert len(r.output_tokens) < 40   # partial output is preserved
    assert server.cache.num_used_blocks == 0
    server.cache.check()


def test_preemption_retry_cap_fails_request(net):
    """max_preemptions=0: the first preemption is terminal instead of
    a requeue — the victim fails with status 'preempted' and the
    survivor runs to completion."""
    rs = np.random.RandomState(33)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=12,
                             num_blocks=6, max_preemptions=0)
    ra = server.submit(rs.randint(0, 256, 10).astype(np.int32),
                       max_new_tokens=12)
    rb = server.submit(rs.randint(0, 256, 10).astype(np.int32),
                       max_new_tokens=12)
    server.run()
    statuses = sorted([ra.status, rb.status])
    assert statuses == ["ok", "preempted"]
    victim = ra if ra.status == "preempted" else rb
    winner = rb if victim is ra else ra
    assert winner.finish_reason == "length"
    assert victim.state == "finished" and victim.preemptions == 1
    assert server.stats()["status_counts"]["preempted"] == 1
    assert server.cache.num_used_blocks == 0
    server.cache.check()


def test_watchdog_trips_on_injected_stall(net):
    from mxnet_tpu import faults
    from mxnet_tpu.serving import ServerStalledError
    telemetry.reset()
    telemetry.enable()
    rs = np.random.RandomState(34)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8,
                             watchdog_ticks=5)
    server.submit(rs.randint(0, 256, 4).astype(np.int32),
                  max_new_tokens=4)
    faults.inject("serving.stall")     # every tick is a dead tick
    try:
        with pytest.raises(ServerStalledError, match="5 consecutive"):
            server.run()
        snap = telemetry.snapshot()["counters"]
        assert snap["serving_watchdog_stalls_total"] == 1.0
        assert snap["faults_injected_total{site=serving.stall}"] == 5.0
        # disarm: the server recovers on the very next tick
        faults.clear()
        done = server.run()
        assert [r.status for r in done] == ["ok"]
    finally:
        faults.clear()
        telemetry.disable()
        telemetry.reset()


def test_watchdog_quiet_when_idle(net):
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8,
                             watchdog_ticks=2)
    for _ in range(10):                # empty ticks are not stalls
        server.step()
    assert server._stall_ticks == 0


def test_drain_then_shutdown_rejects_submit(net):
    rs = np.random.RandomState(35)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    reqs = [server.submit(rs.randint(0, 256, 4).astype(np.int32),
                          max_new_tokens=3) for _ in range(4)]
    done = server.drain()
    assert len(done) == 4 and all(r.status == "ok" for r in reqs)
    with pytest.raises(RuntimeError, match="draining"):
        server.submit(rs.randint(0, 256, 4).astype(np.int32),
                      max_new_tokens=2)
    server.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        server.submit(rs.randint(0, 256, 4).astype(np.int32),
                      max_new_tokens=2)
    server.shutdown()                  # idempotent
    st = server.stats()
    assert st["shutdown"] and st["draining"]


def test_shutdown_without_drain_rejects_pending(net):
    telemetry.reset()
    telemetry.enable()
    try:
        rs = np.random.RandomState(36)
        server = InferenceServer(net, batch_slots=2, max_len=32,
                                 block_size=8, max_prompt_len=8)
        reqs = [server.submit(rs.randint(0, 256, 4).astype(np.int32),
                              max_new_tokens=8) for _ in range(3)]
        server.step()                  # 2 running, 1 queued
        server.shutdown(drain=False)
        assert [r.status for r in reqs] == ["rejected"] * 3
        assert all(r.state == "finished" for r in reqs)
        assert server.cache.num_used_blocks == 0
        st = server.stats()["status_counts"]
        assert st["rejected"] == 3 and st["ok"] == 0
        snap = telemetry.snapshot()["counters"]
        assert snap["serving_requests_total{status=rejected}"] == 3.0
        server.cache.check()
    finally:
        telemetry.disable()
        telemetry.reset()


def test_labeled_status_counters(net):
    telemetry.reset()
    telemetry.enable()
    try:
        rs = np.random.RandomState(37)
        server = InferenceServer(net, batch_slots=2, max_len=32,
                                 block_size=8, max_prompt_len=8)
        server.submit(rs.randint(0, 256, 4).astype(np.int32),
                      max_new_tokens=2)
        server.submit(rs.randint(0, 256, 4).astype(np.int32),
                      max_new_tokens=2, deadline_s=0.0)
        import time as _t
        _t.sleep(0.002)
        server.run()
        snap = telemetry.snapshot()["counters"]
        assert snap["serving_requests_total"] == 2.0          # submits
        assert snap["serving_requests_total{status=ok}"] == 1.0
        assert snap["serving_requests_total{status=timed_out}"] == 1.0
        prom = telemetry.to_prometheus()
        assert 'serving_requests_total{status="ok"}' in prom \
            or "serving_requests_total{status=ok}" in prom
    finally:
        telemetry.disable()
        telemetry.reset()


# -- prefix cache -----------------------------------------------------------

def _pcache(**kw):
    return _cache(prefix_cache=True, **kw)


def test_prefix_full_share_refcounts_and_stats():
    c = _pcache()
    p = list(range(8))                       # exactly 2 full blocks
    plan = c.alloc_shared(0, p)
    assert plan == {"shared_len": 0, "cow": None}   # cold: miss
    c.register_prefix(0, p)
    plan = c.alloc_shared(1, p)
    assert plan["shared_len"] == 8 and plan["cow"] is None
    assert c.slot_blocks(1) == c.slot_blocks(0)     # zero new blocks
    st = c.stats()
    assert st["shared_blocks"] == 2
    assert st["prefix_hits"] == 1 and st["prefix_tokens_shared"] == 8
    c.check()
    # blocks only return to the pool when the LAST reference drops
    c.free_slot(0)
    assert c.num_free_blocks == 6
    c.free_slot(1)
    assert c.num_free_blocks == 8
    c.check()


def test_prefix_tail_share_then_decode_cow():
    c = _pcache()
    p = [5, 6, 7, 8, 9, 1]                   # 1 full block + 2-token tail
    c.alloc_shared(0, p)
    c.register_prefix(0, p)
    plan = c.alloc_shared(1, p)              # identical prompt
    assert plan["shared_len"] == 6 and plan["cow"] is None
    tail = c.slot_blocks(0)[1]
    assert c.slot_blocks(1)[1] == tail
    # slot 1's first decode write lands in the shared tail -> CoW
    pw = c.prepare_write(1, 6)
    assert isinstance(pw, tuple)
    src, dst = pw
    assert src == tail and dst == c.slot_blocks(1)[1] and dst != tail
    assert c.block_tables[1, 1] == dst       # table already repointed
    # slot 0 is sole owner again: its write goes in place
    assert c.prepare_write(0, 6) is None
    assert c.stats()["cow_copies"] == 1
    c.check()


def test_prefix_cow_at_admit_mid_block_extension():
    c = _pcache()
    c.alloc_shared(0, [1, 2, 3])             # partial single block
    c.register_prefix(0, [1, 2, 3])
    # the new prompt extends past the shared content INSIDE the block:
    # prefill would overwrite it, so the copy happens at admit time
    plan = c.alloc_shared(1, [1, 2, 3, 4, 5])
    assert plan["shared_len"] == 3 and plan["cow"] is not None
    src, dst = plan["cow"]
    assert src == c.slot_blocks(0)[0]
    assert dst == c.slot_blocks(1)[0]
    assert src not in c.slot_blocks(1)       # private copy, not shared
    assert c.stats()["cow_copies"] == 1
    c.check()


def test_prefix_never_shares_on_mid_block_divergence():
    c = _pcache()
    c.alloc_shared(0, [1, 2, 3, 4])
    c.register_prefix(0, [1, 2, 3, 4])
    blocks, L = c.match_prefix([1, 2, 9, 9])  # diverges inside block
    assert L == 0 and blocks == []
    plan = c.alloc_shared(1, [1, 2, 9, 9])
    assert plan["shared_len"] == 0
    assert not (set(c.slot_blocks(1)) & set(c.slot_blocks(0)))
    c.check()


def test_prefix_shorter_prompt_shares_tail():
    c = _pcache()
    p = [1, 2, 3, 4, 5, 6, 7, 8]
    c.alloc_shared(0, p)
    c.register_prefix(0, p)
    blocks, L = c.match_prefix([1, 2, 3, 4, 5, 6])
    assert L == 6 and len(blocks) == 2       # full block + partial tail
    plan = c.alloc_shared(1, [1, 2, 3, 4, 5, 6])
    # prompt ENDS inside the shared block: adopt as-is, CoW deferred to
    # the first decode write via prepare_write
    assert plan["shared_len"] == 6 and plan["cow"] is None
    assert c.slot_blocks(1) == c.slot_blocks(0)
    c.check()


def test_prefix_freed_content_resurrected_then_purged_on_reuse():
    c = _pcache()
    p = list(range(8))
    c.alloc_shared(0, p)
    c.register_prefix(0, p)
    blocks = c.slot_blocks(0)
    c.free_slot(0)
    assert c.num_free_blocks == 8            # fully freed...
    plan = c.alloc_shared(1, p)              # ...but content survives
    assert plan["shared_len"] == 8
    assert c.slot_blocks(1) == blocks        # resurrected, not rewritten
    c.check()
    # once a freed registered block is REUSED its registration purges
    c.free_slot(1)
    assert c.alloc(2, 16) and c.alloc(1, 16)  # drain all 8 blocks
    assert c.match_prefix(p)[1] == 0
    c.check()


def test_prefix_prepare_write_exhaustion_then_sole_owner():
    c = _pcache(num_blocks=5)                # 4 usable
    p = list(range(6))
    c.alloc_shared(0, p)
    c.register_prefix(0, p)
    assert c.alloc_shared(1, p)["shared_len"] == 6
    assert c.num_free_blocks == 2
    assert c.ensure(0, 8) and c.ensure(0, 12)  # slot 0 drains the pool
    # CoW for slot 1's tail write has no destination: caller must
    # preempt something and retry (the scheduler's contract)
    assert c.prepare_write(1, 6) is False
    c.free_slot(0)
    # the sharer died with the pool: slot 1 is now sole owner, so the
    # retry needs no copy at all
    assert c.prepare_write(1, 6) is None
    c.check()


def test_prefix_refcount_no_leak_after_churn():
    c = _pcache(num_blocks=17, batch_slots=4, max_blocks_per_seq=4)
    rs = np.random.RandomState(3)
    prompts = [list(rs.randint(0, 5, int(rs.randint(3, 14))))
               for _ in range(6)]             # tiny vocab -> collisions
    held = {}
    for _ in range(80):
        slot = int(rs.randint(4))
        if slot in held:
            c.free_slot(slot)
            del held[slot]
        else:
            p = prompts[int(rs.randint(6))]
            if c.alloc_shared(slot, p) is not None:
                c.register_prefix(slot, p)
                held[slot] = p
        c.check()
    assert c.stats()["prefix_hits"] > 0
    for s in list(held):
        c.free_slot(s)
    assert c.num_free_blocks == 16
    assert int(c._refcount.sum()) == 0        # no leaked references
    c.check()


def test_server_prefix_cache_token_parity(net):
    """Prefix sharing must be invisible in the tokens: identical,
    extended, shorter, and cold prompts produce exactly the same
    outputs with the prefix cache on and off."""
    rs = np.random.RandomState(23)
    base = rs.randint(0, 256, 10).astype(np.int32)
    ext = np.concatenate([base, rs.randint(0, 256, 2).astype(np.int32)])
    prompts = [base, base.copy(), ext, base[:6].copy(),
               rs.randint(0, 256, 7).astype(np.int32)]
    outs = {}
    for pc in (False, True):
        server = InferenceServer(net, batch_slots=5, max_len=64,
                                 block_size=8, max_prompt_len=12,
                                 prefix_cache=pc)
        reqs = [server.submit(p, max_new_tokens=6) for p in prompts]
        server.run()
        outs[pc] = [list(r.output_tokens) for r in reqs]
        if pc:
            st = server.cache.stats()
            # identical (10) + extension (10) + shorter (6) all hit
            assert st["prefix_hits"] == 3
            assert st["prefix_tokens_shared"] == 26
            assert st["cow_copies"] >= 1      # ext forks mid-block
        cs = server.compile_stats()
        assert cs["prefill_compiles"] == 1 and cs["decode_compiles"] == 1
        assert server.cache.num_used_blocks == 0
        server.cache.check()
    assert outs[True] == outs[False]


def test_server_prefix_16_requests_one_compile_each(net):
    """The acceptance workload with the prefix cache ON: half the
    requests are prefixes of one base prompt; tokens stay identical to
    one-shot generate() and it is still exactly one prefill + one
    decode compile (plus at most one for the CoW block copy)."""
    rs = np.random.RandomState(24)
    server = InferenceServer(net, batch_slots=4, max_len=64,
                             block_size=8, max_prompt_len=12,
                             prefix_cache=True)
    base = rs.randint(0, 256, 12).astype(np.int32)
    reqs = []
    for i in range(16):
        T = int(rs.randint(3, 13))
        p = base[:T].copy() if i % 2 == 0 \
            else rs.randint(0, 256, T).astype(np.int32)
        new = int(rs.randint(2, 9))
        reqs.append((p, new, server.submit(p, max_new_tokens=new)))
    server.run()
    cs = server.compile_stats()
    assert cs["prefill_compiles"] == 1, cs
    assert cs["decode_compiles"] == 1, cs
    assert cs["copy_compiles"] <= 1, cs
    assert server.cache.stats()["prefix_hits"] >= 1
    for p, new, r in reqs:
        assert r.state == "finished"
        one = generate(net, p[None, :], max_new_tokens=new, max_len=64)
        np.testing.assert_array_equal(
            np.asarray(r.output_tokens), one[0, len(p):],
            err_msg=f"request {r.id} diverged with prefix cache on")
    assert server.cache.num_used_blocks == 0
    server.cache.check()


# -- in-kernel paged decode in the server -----------------------------------

def test_server_gather_bytes_avoided_telemetry(net, monkeypatch):
    """With the in-kernel paged path active the server credits the
    per-tick gather traffic it no longer pays; with the kernel gated
    off the counter must stay silent."""
    from mxnet_tpu.kernels.flash_decode import paged_gather_bytes

    monkeypatch.setenv("MXNET_TPU_FLASH_INTERPRET", "1")
    telemetry.reset()
    telemetry.enable()
    try:
        server = InferenceServer(net, batch_slots=2, max_len=32,
                                 block_size=8, max_prompt_len=8)
        assert server._kernel_paged            # bs=8 passes the gate
        pool = server.cache.pages[0]["k"]
        expect = 2 * paged_gather_bytes(       # llama_tiny: 2 layers
            pool.shape, tuple(server.cache.block_tables.shape),
            pool.dtype.itemsize)
        assert server._gather_bytes_per_tick == expect
        server.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=3)
        server.run()
        got = telemetry.snapshot()["counters"][
            "serving_gather_bytes_avoided_total"]
        assert got > 0 and got % expect == 0
    finally:
        telemetry.disable()
        telemetry.reset()


def test_server_block4_stays_on_gather_path(net):
    # block_size=4 fails the Mosaic sublane gate: same tokens, no
    # gather-bytes credit, and the paged fallback counter stays flat
    # (the gather path is the DESIGNED fallback, not an error)
    from mxnet_tpu.kernels import flash_decode as fd

    before = fd._paged_fallback.count
    telemetry.reset()
    telemetry.enable()
    try:
        rs = np.random.RandomState(25)
        p = rs.randint(0, 256, 6).astype(np.int32)
        server = InferenceServer(net, batch_slots=2, max_len=32,
                                 block_size=4, max_prompt_len=8)
        assert not server._kernel_paged
        r = server.submit(p, max_new_tokens=4)
        server.run()
        one = generate(net, p[None, :], max_new_tokens=4, max_len=32)
        np.testing.assert_array_equal(np.asarray(r.output_tokens),
                                      one[0, 6:])
        counters = telemetry.snapshot()["counters"]
        assert "serving_gather_bytes_avoided_total" not in counters
        assert fd._paged_fallback.count == before
    finally:
        telemetry.disable()
        telemetry.reset()


# -- per-request traces, health probe, flight dump (ISSUE 10) ---------------

def test_server_tracing_acceptance(net):
    """Acceptance bar: a 16-request workload with tracing ON still
    compiles exactly one prefill + one decode executable, and the
    reported trace TTFT matches the request's `ttft` property."""
    rs = np.random.RandomState(41)
    server = InferenceServer(net, batch_slots=4, max_len=64,
                             block_size=8, max_prompt_len=12,
                             trace_sample_every=1)
    reqs = _mixed_requests(server, rs, 16)
    server.run()
    cs = server.compile_stats()
    assert cs["prefill_compiles"] == 1, cs
    assert cs["decode_compiles"] == 1, cs
    for _, _, r in reqs:
        tr = server.trace(r.id)
        assert tr is not None
        assert tr["ttft_s"] == r.ttft
        assert tr["latency_s"] == r.t_finish - r.t_submit
        assert tr["decode_tokens"] == len(r.output_tokens)
        names = [e["name"] for e in tr["events"]]
        assert names[0] == "queued" and names[-1] == "finish"
        assert "admit" in names and "prefill" in names
        ts = [e["t"] for e in tr["events"]]
        assert ts == sorted(ts)
        # timed spans carry durations
        by_name = {e["name"]: e for e in tr["events"]}
        assert by_name["queued"]["dur_s"] == tr["queue_wait_s"]
        assert by_name["prefill"]["dur_s"] > 0
        if tr["decode_tokens"] > 1:
            assert "decode" in names
            assert tr["tpot_s"] is not None and tr["tpot_s"] >= 0


def test_trace_sampling_knob(net):
    """trace_sample_every=N keeps every Nth request (by submit order);
    the rest are dropped at the terminal transition."""
    rs = np.random.RandomState(42)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8,
                             trace_sample_every=2)
    reqs = [server.submit(rs.randint(0, 256, 4).astype(np.int32),
                          max_new_tokens=3) for _ in range(6)]
    server.run()
    kept = [r for r in reqs if server.trace(r.id) is not None]
    assert [r.id for r in kept] == [reqs[0].id, reqs[2].id, reqs[4].id]


def test_trace_slow_outlier_always_kept(net):
    """A request slower than trace_slow_s is retained even when the
    sampling knob would discard it."""
    rs = np.random.RandomState(43)
    srv_all = InferenceServer(net, batch_slots=2, max_len=32,
                              block_size=8, max_prompt_len=8,
                              trace_sample_every=0, trace_slow_s=0.0)
    r = srv_all.submit(rs.randint(0, 256, 4).astype(np.int32),
                       max_new_tokens=3)
    srv_all.run()
    assert srv_all.trace(r.id) is not None   # everything beats 0.0s
    srv_none = InferenceServer(net, batch_slots=2, max_len=32,
                               block_size=8, max_prompt_len=8,
                               trace_sample_every=0, trace_slow_s=1e9)
    r2 = srv_none.submit(rs.randint(0, 256, 4).astype(np.int32),
                         max_new_tokens=3)
    srv_none.run()
    assert srv_none.trace(r2.id) is None


def test_trace_capacity_evicts_oldest(net):
    rs = np.random.RandomState(44)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8,
                             trace_sample_every=1, trace_capacity=2)
    reqs = [server.submit(rs.randint(0, 256, 4).astype(np.int32),
                          max_new_tokens=3) for _ in range(5)]
    server.run()
    kept = [r.id for r in reqs if server.trace(r.id) is not None]
    assert kept == [reqs[-2].id, reqs[-1].id]


def test_trace_preemption_splits_decode_windows(net):
    """Preemption shows up in the trace as a `preempt` transition and a
    second decode window; TPOT only counts within-window time."""
    rs = np.random.RandomState(45)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=12,
                             num_blocks=6, trace_sample_every=1)
    pa = rs.randint(0, 256, 10).astype(np.int32)
    pb = rs.randint(0, 256, 10).astype(np.int32)
    ra = server.submit(pa, max_new_tokens=12)
    rb = server.submit(pb, max_new_tokens=12)
    server.run()
    victim = ra if ra.preemptions else rb
    assert victim.preemptions >= 1
    tr = server.trace(victim.id)
    names = [e["name"] for e in tr["events"]]
    assert names.count("preempt") == victim.preemptions
    assert names.count("admit") == victim.preemptions + 1
    assert names.count("prefill") == victim.preemptions + 1
    assert tr["preemptions"] == victim.preemptions
    decs = [e for e in tr["events"] if e["name"] == "decode"]
    assert len(decs) >= 2


def test_trace_live_request_visible(net):
    """trace() works mid-flight: queued and running requests expose
    their partial timelines before the terminal transition."""
    rs = np.random.RandomState(46)
    server = InferenceServer(net, batch_slots=1, max_len=32,
                             block_size=8, max_prompt_len=8,
                             trace_sample_every=1)
    r1 = server.submit(rs.randint(0, 256, 4).astype(np.int32),
                       max_new_tokens=6)
    r2 = server.submit(rs.randint(0, 256, 4).astype(np.int32),
                       max_new_tokens=6)
    server.step()                       # r1 admitted, r2 still queued
    t1, t2 = server.trace(r1.id), server.trace(r2.id)
    assert t1["state"] == "running" and t1["latency_s"] is None
    assert [e["name"] for e in t2["events"]] == ["queued"]
    assert len(server.request_traces()) == 2
    server.run()


def test_queue_age_percentiles(net):
    import time as _time
    rs = np.random.RandomState(47)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    st = server.stats()
    assert st["queue_age_p50_s"] == 0.0 and st["queue_age_p95_s"] == 0.0
    for _ in range(4):
        server.submit(rs.randint(0, 256, 4).astype(np.int32),
                      max_new_tokens=2)
    _time.sleep(0.02)
    st = server.stats()
    assert st["queue_age_p50_s"] >= 0.02
    assert st["queue_age_p95_s"] >= st["queue_age_p50_s"]
    server.run()
    assert server.stats()["queue_age_p50_s"] == 0.0


def test_health_probe_transitions(net):
    rs = np.random.RandomState(48)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    assert server.health() == (True, "ok")
    # the server registered itself with telemetry at construction
    ok, reason = telemetry.health()
    assert ok and reason == "ok"
    server.submit(rs.randint(0, 256, 4).astype(np.int32),
                  max_new_tokens=2)
    server.drain()
    ok, reason = server.health()
    assert not ok and "draining" in reason
    server.shutdown()
    ok, reason = server.health()
    assert not ok and "shutdown" in reason
    ok, reason = telemetry.health()     # aggregate view goes 503
    assert not ok
    telemetry.unregister_health_source(server)
    assert telemetry.health() == (True, "ok")


def test_health_stalled_and_recovers(net):
    from mxnet_tpu import faults
    from mxnet_tpu.serving import ServerStalledError
    rs = np.random.RandomState(49)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8,
                             watchdog_ticks=3)
    server.submit(rs.randint(0, 256, 4).astype(np.int32),
                  max_new_tokens=3)
    faults.inject("serving.stall")
    try:
        with pytest.raises(ServerStalledError):
            server.run()
        ok, reason = server.health()
        assert not ok and "stalled" in reason
        faults.clear()
        server.run()                    # progress clears the flag
        assert server.health() == (True, "ok")
    finally:
        faults.clear()
        telemetry.unregister_health_source(server)


def test_watchdog_stall_flight_dump(net, tmp_path, monkeypatch):
    """Acceptance bar: an induced watchdog stall leaves a flight dump
    whose FINAL event is the stall record."""
    import json
    from mxnet_tpu import faults, flight
    from mxnet_tpu.serving import ServerStalledError
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))
    flight.clear()
    flight.enable()
    rs = np.random.RandomState(50)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8,
                             watchdog_ticks=4)
    server.submit(rs.randint(0, 256, 4).astype(np.int32),
                  max_new_tokens=3)
    faults.inject("serving.stall")
    try:
        with pytest.raises(ServerStalledError):
            server.run()
    finally:
        faults.clear()
        flight.disable()
        telemetry.unregister_health_source(server)
    path = tmp_path / f"flight-serving_stall-p{__import__('os').getpid()}.jsonl"
    assert path.exists()
    lines = [json.loads(l) for l in path.open()]
    assert lines[0]["reason"] == "serving_stall"
    last = lines[-1]
    assert last["kind"] == "stall" and last["site"] == "serving.watchdog"
    assert last["payload"]["ticks"] == 4
    # the dead ticks leading up to it are the preceding fault records
    assert any(e.get("site") == "serving.stall" for e in lines[1:-1])
    flight.clear()


def test_chrome_trace_merges_request_spans(net, tmp_path):
    import gc
    import json
    # the export merges EVERY live trace source (weakref registry) —
    # collect cyclic garbage so earlier tests' dead servers are gone
    # before the exact-equality tid assertion below
    gc.collect()
    telemetry.reset()
    telemetry.enable()
    try:
        rs = np.random.RandomState(51)
        server = InferenceServer(net, batch_slots=2, max_len=32,
                                 block_size=8, max_prompt_len=8,
                                 trace_sample_every=1)
        reqs = [server.submit(rs.randint(0, 256, 4).astype(np.int32),
                              max_new_tokens=3) for _ in range(3)]
        server.run()
        out = telemetry.export_chrome_trace(str(tmp_path / "tr.json"))
        evs = json.load(open(out))["traceEvents"]
        req_evs = [e for e in evs
                   if e.get("pid") == telemetry.REQUEST_PID]
        names = {e["name"] for e in req_evs if e.get("ph") != "M"}
        assert {"queued", "prefill", "decode", "admit",
                "finish"} <= names
        tids = {e.get("tid") for e in req_evs if e.get("ph") != "M"}
        assert tids == {r.id for r in reqs}
        # spans are "X" with microsecond durations; transitions are "i"
        spans = [e for e in req_evs if e.get("ph") == "X"]
        assert spans and all(e["dur"] >= 0 for e in spans)
        metas = [e for e in req_evs if e.get("ph") == "M"]
        assert any(e["name"] == "process_name" for e in metas)
    finally:
        telemetry.disable()
        telemetry.reset()
        telemetry.unregister_health_source(server)


# -- cancel / drain / health detail (fleet satellites) -----------------------

def test_server_cancel_running_and_queued(net):
    rs = np.random.RandomState(50)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    reqs = [server.submit(rs.randint(0, 256, 4).astype(np.int32),
                          max_new_tokens=8) for _ in range(3)]
    server.step()                      # r0, r1 running; r2 queued
    used = server.cache.num_used_blocks
    assert server.cancel(reqs[0].id)
    assert reqs[0].state == "finished"
    assert reqs[0].status == "cancelled"
    assert reqs[0].finish_reason == "cancel"
    assert server.cache.num_used_blocks < used   # blocks released
    assert server.cancel(reqs[2].id)   # cancel straight out of the queue
    assert reqs[2].status == "cancelled"
    assert not server.cancel(reqs[0].id)         # already finished
    assert not server.cancel(10 ** 9)            # unknown id
    server.run()
    assert reqs[1].status == "ok"      # the survivor is unaffected
    assert server.stats()["status_counts"]["cancelled"] == 2
    assert server.cache.num_used_blocks == 0
    server.cache.check()


def test_server_health_detail_structure(net):
    import time as _time
    rs = np.random.RandomState(51)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    d = server.health_detail()
    assert d["ok"] is True and d["reason"] == "ok"
    assert not d["draining"] and not d["shutdown"] and not d["stalled"]
    assert d["slots"] == 2 and d["block_size"] == 8
    assert d["max_prompt_len"] == 8 and d["max_len"] == 32
    assert d["queued"] == 0 and d["active"] == 0
    assert d["blocks_free"] == server.cache.num_free_blocks
    server.begin_drain()               # non-blocking drain flip
    d = server.health_detail()
    assert d["draining"] and d["ok"] is False
    assert "draining" in d["reason"]
    server.end_drain()
    assert server.health_detail()["ok"] is True
    [server.submit(rs.randint(0, 256, 4).astype(np.int32),
                   max_new_tokens=4) for _ in range(5)]
    server.step()
    _time.sleep(0.01)
    d = server.health_detail()
    assert d["active"] == 2 and d["queued"] == 3
    assert d["queue_age_p95_s"] >= d["queue_age_p50_s"] > 0
    server.run()
    server.shutdown()
    with pytest.raises(RuntimeError, match="shut-down"):
        server.end_drain()


# -- subprocess fleet: SIGKILL one replica, zero requests lost ---------------

import os as _os
import signal
import subprocess as _subprocess
import sys as _sys

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))


def _spawn_fleet_worker(d, name, fault=None, max_wall_s=240):
    env = dict(_os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_TPU_FAULTS", None)
    env["JAX_PLATFORMS"] = "cpu"
    if fault:
        env["MXNET_TPU_FAULTS"] = fault
    log = open(_os.path.join(d, f"{name}.log"), "w")
    return _subprocess.Popen(
        [_sys.executable, "-u", "-m", "mxnet_tpu.serving.router",
         "--dir", d, "--name", name, "--slots", "4", "--max-len", "64",
         "--block", "8", "--max-prompt", "12",
         "--max-wall-s", str(max_wall_s)],
        stdout=log, stderr=log, env=env, cwd=_REPO)


def test_fleet_subprocess_kill_failover_zero_lost(net, tmp_path):
    """The fleet acceptance bar: two subprocess replicas over the
    FileKV channel, one SIGKILLed mid-stream by `replica.kill` — every
    request still finishes exactly once with tokens identical to
    one-shot generate(), and the survivor stays at ONE prefill + ONE
    decode compile (its warmup)."""
    import time as _time
    from mxnet_tpu.serving.router import FileKV, FleetRouter, ProcReplica

    d = str(tmp_path)
    kv = FileKV(d)
    procs = [_spawn_fleet_worker(d, "w0",
                                 fault="replica.kill:at=6"),
             _spawn_fleet_worker(d, "w1")]
    try:
        # wait until both replicas warmed up and published a heartbeat
        # (workers warm-compile BEFORE the first beat), so the kill
        # target is guaranteed to receive live traffic
        t0 = _time.perf_counter()
        while _time.perf_counter() - t0 < 180:
            if all(kv.get(f"fleet/w{i}/hb") is not None
                   for i in range(2)):
                break
            for i, p in enumerate(procs):
                if p.poll() is not None:   # died before serving
                    pytest.fail(f"worker w{i} exited rc={p.returncode} "
                                "during warmup: " + open(_os.path.join(
                                    d, f"w{i}.log")).read()[-2000:])
            _time.sleep(0.05)
        else:
            pytest.fail("fleet workers never became healthy: "
                        + open(_os.path.join(d, "w0.log")).read()[-2000:])

        fleet = FleetRouter([ProcReplica(kv, "w0"),
                             ProcReplica(kv, "w1")],
                            affinity_blocks=0, backoff_base_s=0.01,
                            heartbeat_timeout_s=2.0)
        rs = np.random.RandomState(52)
        reqs = []
        for _ in range(8):
            p = rs.randint(0, 256, rs.randint(2, 10)).astype(np.int32)
            new = int(rs.randint(8, 14))
            reqs.append((p, new, fleet.submit(p, new)))
        fleet.run(timeout_s=240)

        # zero lost, zero duplicated
        assert len(fleet.finished) == 8
        for p, new, fr in reqs:
            assert fr.status == "ok", (fr, fleet.stats())
        assert fleet.stats()["duplicates"] == 0
        assert fleet.n_failovers >= 1, fleet.stats()

        # the injected kill really SIGKILLed w0 mid-run
        assert procs[0].wait(timeout=60) == -signal.SIGKILL
        # survivor: clean stop, warmup was its only compile
        final = fleet.stop_fleet(timeout_ms=60_000)
        assert final["w0"] is None
        assert final["w1"] is not None
        assert final["w1"]["prefill_compiles"] == 1, final["w1"]
        assert final["w1"]["decode_compiles"] == 1, final["w1"]
        assert procs[1].wait(timeout=60) == 0

        # token parity: replica-independent greedy decoding (the
        # workers build the same seeded llama_tiny as the fixture)
        for p, new, fr in reqs:
            one = generate(net, p[None, :], max_new_tokens=new,
                           max_len=64)
            np.testing.assert_array_equal(
                np.asarray(fr.output_tokens), one[0, len(p):],
                err_msg=f"{fr.token} diverged after failover")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)


# -- chunked prefill + speculative decoding ---------------------------------

from mxnet_tpu.serving.speculative import NgramProposer, as_proposer


class _StubProposer:
    """Deterministic test proposer: returns a fixed guess list
    regardless of context (the server drops guess 0 on non-warm
    ticks, so wrong[0] is free and wrong[1:] become the drafts)."""

    def __init__(self, k, fn):
        self.k = k
        self._fn = fn

    def propose(self, tokens):
        return np.asarray(self._fn(np.asarray(tokens)), np.int32)


def test_ngram_proposer_lookup():
    p = NgramProposer(k=3, ngram=2)
    # trailing bigram (1, 2) last occurred at the start
    out = p.propose([1, 2, 9, 8, 1, 2])
    assert out.tolist() == [9, 8, 1, 2]        # k + 1 guesses
    # most recent occurrence wins over the earlier one
    out = p.propose([1, 2, 5, 1, 2, 7, 1, 2])
    assert out.tolist()[0] == 7
    # unigram fallback when no bigram repeats
    out = p.propose([4, 9, 4])
    assert out.tolist() == [9, 4]
    # nothing repeats -> empty
    assert p.propose([1, 2, 3]).size == 0
    assert p.propose([5]).size == 0


def test_as_proposer_normalization():
    assert as_proposer(None) is None
    assert as_proposer(False) is None
    assert isinstance(as_proposer(True), NgramProposer)
    assert as_proposer(6).k == 6
    stub = _StubProposer(2, lambda t: [])
    assert as_proposer(stub) is stub
    with pytest.raises(TypeError):
        as_proposer("ngram")
    with pytest.raises(ValueError):
        NgramProposer(k=0)


def test_chunked_prefill_16_requests_token_parity_one_compile(net):
    """The acceptance bar with chunked prefill ON: 16 mixed-length
    greedy requests, prefill spread over 4-token ticks, token-identical
    to one-shot generate() with exactly ONE windowed-prefill compile
    and ONE decode compile. The chunk window (start, len) is traced —
    ragged tails never retrace."""
    rs = np.random.RandomState(41)
    server = InferenceServer(net, batch_slots=4, max_len=64,
                             block_size=8, max_prompt_len=12,
                             prefill_chunk_tokens=4)
    reqs = _mixed_requests(server, rs, 16)
    server.run()
    cs = server.compile_stats()
    assert cs["prefill_compiles"] == 1, cs
    assert cs["decode_compiles"] == 1, cs
    assert cs["prefill_calls"] > 16      # chunks, not prompts
    per = tracing.cache_stats()["per_block"]
    assert per["serving_prefill_chunk"]["compiles"] == 1
    for p, new, r in reqs:
        assert r.state == "finished" and r.finish_reason == "length"
        one = generate(net, p[None, :], max_new_tokens=new, max_len=64)
        np.testing.assert_array_equal(
            np.asarray(r.output_tokens), one[0, len(p):],
            err_msg=f"request {r.id} diverged under chunked prefill")
    assert server.cache.num_used_blocks == 0
    server.cache.check()


@pytest.mark.parametrize("chunk,spec,prefix,blocks", [
    (3, None, False, None),      # chunking alone
    (4, None, True, None),       # chunking x prefix sharing
    (4, None, False, 6),         # chunking x preemption (tight pool)
    (5, 3, True, None),          # chunking x speculation x prefix
    (None, 3, False, 6),         # speculation x preemption
    (4, 2, True, 6),             # everything at once
])
def test_tail_latency_fuzz_grid(net, chunk, spec, prefix, blocks):
    """Chunked prefill x speculative decoding x prefix sharing x
    preemption x deadlines must be invisible in the tokens: every
    combination is token-identical to one-shot generate() at exactly
    1 prefill + 1 decode (+ <=1 verify) compile."""
    rs = np.random.RandomState(43 + (chunk or 0) + (spec or 0))
    kw = dict(batch_slots=3, max_len=32, block_size=4,
              max_prompt_len=12, prefix_cache=prefix,
              prefill_chunk_tokens=chunk, speculative=spec)
    if blocks:
        # tight pool: thrash hard, but let every victim retry through
        kw.update(num_blocks=blocks, max_preemptions=20)
    server = InferenceServer(net, **kw)
    # programs are cached ACROSS servers keyed on executable shapes
    # (num_blocks is not part of the key — the pool is a traced
    # operand), so earlier grid cases may already have compiled this
    # entry for a different pool shape: assert the DELTA this
    # workload adds, which is what the compile discipline promises
    cs0 = server.compile_stats()
    base = rs.randint(0, 256, 12).astype(np.int32)
    reqs = []
    for i in range(8):
        T = int(rs.randint(3, 13))
        p = base[:T].copy() if (prefix and i % 2 == 0) \
            else rs.randint(0, 256, T).astype(np.int32)
        new = int(rs.randint(2, 9))
        reqs.append((p, new, server.submit(p, max_new_tokens=new)))
    # a dead-on-arrival request must time out without disturbing parity
    doa = server.submit(rs.randint(0, 256, 5).astype(np.int32),
                        max_new_tokens=4, deadline_s=0.0)
    import time as _t
    _t.sleep(0.002)
    server.run()
    assert doa.status == "timed_out"
    cs = server.compile_stats()
    assert cs["prefill_compiles"] - cs0["prefill_compiles"] <= 1, cs
    assert cs["decode_compiles"] - cs0["decode_compiles"] <= 1, cs
    assert cs.get("verify_compiles", 0) \
        - cs0.get("verify_compiles", 0) <= 1, cs
    if blocks:
        assert sum(r.preemptions for _, _, r in reqs) >= 1
    for p, new, r in reqs:
        assert r.state == "finished" and r.status == "ok"
        one = generate(net, p[None, :], max_new_tokens=new, max_len=32)
        np.testing.assert_array_equal(
            np.asarray(r.output_tokens), one[0, len(p):],
            err_msg=f"request {r.id} diverged (chunk={chunk} "
                    f"spec={spec} prefix={prefix} blocks={blocks})")
    assert server.cache.num_used_blocks == 0
    server.cache.check()


def test_chunk_budget_utilization_gauge(net):
    telemetry.reset()
    telemetry.enable()
    try:
        rs = np.random.RandomState(44)
        server = InferenceServer(net, batch_slots=2, max_len=32,
                                 block_size=8, max_prompt_len=12,
                                 prefill_chunk_tokens=4)
        server.submit(rs.randint(0, 256, 11).astype(np.int32),
                      max_new_tokens=3)
        server.run()
        g = telemetry.snapshot()["gauges"]
        assert "serving_chunk_budget_utilization" in g
        assert 0.0 < g["serving_chunk_budget_utilization"] <= 1.0
    finally:
        telemetry.disable()
        telemetry.reset()


def test_prefill_skip_on_full_prefix_cover(net):
    """A prompt the prefix cache covers END-TO-END never dispatches a
    prefill at all: the slot warms from the cached blocks and the
    first decode tick re-derives the last prompt position's logits."""
    telemetry.reset()
    telemetry.enable()
    try:
        rs = np.random.RandomState(45)
        p = rs.randint(0, 256, 9).astype(np.int32)
        server = InferenceServer(net, batch_slots=2, max_len=64,
                                 block_size=8, max_prompt_len=12,
                                 prefix_cache=True)
        r1 = server.submit(p, max_new_tokens=6)
        server.run()
        calls_after_cold = server.compile_stats()["prefill_calls"]
        r2 = server.submit(p.copy(), max_new_tokens=6)
        server.run()
        assert server.prefills_skipped == 1
        # no second prefill dispatch happened
        assert server.compile_stats()["prefill_calls"] == calls_after_cold
        assert list(r2.output_tokens) == list(r1.output_tokens)
        one = generate(net, p[None, :], max_new_tokens=6, max_len=64)
        np.testing.assert_array_equal(np.asarray(r2.output_tokens),
                                      one[0, 9:])
        snap = telemetry.snapshot()["counters"]
        assert snap["serving_prefill_skipped_total"] == 1.0
        assert server.stats()["prefills_skipped"] == 1
    finally:
        telemetry.disable()
        telemetry.reset()


def test_prefill_skip_sampled_stream_parity(net):
    """The warm first tick consumes no PRNG randomness the cold path
    would not: a sampled request served from a full prefix hit emits
    the same stream as the cold run at the same seed."""
    rs = np.random.RandomState(46)
    p = rs.randint(0, 256, 8).astype(np.int32)
    server = InferenceServer(net, batch_slots=2, max_len=64,
                             block_size=8, max_prompt_len=12,
                             prefix_cache=True)
    r1 = server.submit(p, max_new_tokens=8, temperature=0.8, seed=5)
    server.run()
    r2 = server.submit(p.copy(), max_new_tokens=8, temperature=0.8,
                       seed=5)
    server.run()
    assert server.prefills_skipped == 1
    assert list(r2.output_tokens) == list(r1.output_tokens)
    assert server.cache.num_used_blocks == 0
    server.cache.check()


def test_speculative_all_rejected_keeps_parity(net):
    """Adversarial proposer that always drafts wrong tokens: every
    draft is rejected, throughput falls back to one token per tick,
    and output stays token-identical — a bad proposer can never
    corrupt the stream."""
    rs = np.random.RandomState(47)
    wrong = _StubProposer(3, lambda t: (t[-4:] + 1) % 256)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8,
                             speculative=wrong)
    p = rs.randint(0, 256, 6).astype(np.int32)
    r = server.submit(p, max_new_tokens=8)
    server.run()
    assert server.spec_tokens_accepted == 0
    assert server.spec_tokens_rejected > 0
    one = generate(net, p[None, :], max_new_tokens=8, max_len=32)
    np.testing.assert_array_equal(np.asarray(r.output_tokens),
                                  one[0, 6:])
    assert server.compile_stats()["verify_compiles"] == 1
    assert server.cache.num_used_blocks == 0
    server.cache.check()


def test_speculative_oracle_all_accepted(net):
    """Oracle proposer drafting the true continuation: every draft is
    accepted, so N tokens land in ~N/(k+1) verify dispatches — and the
    output is still bit-identical to the non-speculative tick."""
    rs = np.random.RandomState(48)
    p = rs.randint(0, 256, 6).astype(np.int32)
    one = np.asarray(generate(net, p[None, :], max_new_tokens=12,
                              max_len=64))[0]

    def oracle(tokens):
        L = len(tokens)
        return one[L:L + 4]  # k + 1 = 4 true next tokens

    server = InferenceServer(net, batch_slots=2, max_len=64,
                             block_size=8, max_prompt_len=8,
                             speculative=_StubProposer(3, oracle))
    r = server.submit(p, max_new_tokens=12)
    server.run()
    np.testing.assert_array_equal(np.asarray(r.output_tokens),
                                  one[6:18])
    assert server.spec_tokens_rejected == 0
    assert server.spec_tokens_accepted >= 8
    cs = server.compile_stats()
    # 12 tokens in ~3 verify ticks, not 12 decode ticks
    assert cs["verify_calls"] + cs["decode_calls"] <= 5, cs
    assert server.stats()["draft_accept_rate"] == 1.0
    assert server.cache.num_used_blocks == 0
    server.cache.check()


def test_speculative_rewind_under_cow(net):
    """Rejected drafts must rewind blocks that were CoW-forked off
    SHARED prefix content without corrupting the other owner: B and C
    both warm-start on A's full-prefix blocks concurrently (refcount 2
    on every shared block), each speculates into its own CoW fork of
    the shared tail, rejects everything, rewinds — and all three
    streams stay verbatim-identical to one-shot generate()."""
    rs = np.random.RandomState(49)
    p = rs.randint(0, 256, 9).astype(np.int32)   # ragged tail: 9 % 4
    wrong = _StubProposer(3, lambda t: (t[-4:] + 7) % 256)
    server = InferenceServer(net, batch_slots=3, max_len=32,
                             block_size=4, max_prompt_len=12,
                             prefix_cache=True, speculative=wrong)
    ra = server.submit(p, max_new_tokens=5)
    server.run()
    rb = server.submit(p.copy(), max_new_tokens=5)
    rc = server.submit(p.copy(), max_new_tokens=5)
    server.run()                 # B and C share A's blocks live
    assert server.prefills_skipped == 2
    assert server.spec_tokens_rejected > 0
    assert server.cache.stats()["cow_copies"] >= 1
    one = np.asarray(generate(net, p[None, :], max_new_tokens=5,
                              max_len=32))[0, 9:]
    for r in (ra, rb, rc):
        np.testing.assert_array_equal(np.asarray(r.output_tokens), one)
    assert server.cache.num_used_blocks == 0
    server.cache.check()


def test_speculative_sampled_requests_fall_back(net):
    """temperature > 0 requests are never drafted (verify acceptance
    is argmax-based); their streams match the non-speculative server
    at the same seed even when greedy neighbors speculate."""
    rs = np.random.RandomState(50)
    p1 = rs.randint(0, 256, 6).astype(np.int32)
    p2 = rs.randint(0, 256, 6).astype(np.int32)
    outs = {}
    for spec in (None, 3):
        server = InferenceServer(net, batch_slots=2, max_len=32,
                                 block_size=8, max_prompt_len=8,
                                 speculative=spec)
        r1 = server.submit(p1, max_new_tokens=6, temperature=0.7,
                           seed=9)
        r2 = server.submit(p2, max_new_tokens=6)
        server.run()
        outs[spec] = (list(r1.output_tokens), list(r2.output_tokens))
    assert outs[None] == outs[3]


def test_spec_telemetry_counters_and_tpot_labels(net):
    telemetry.reset()
    telemetry.enable()
    try:
        # repetitive prompt so the n-gram proposer actually drafts
        p = np.array([7, 3, 7, 3, 7, 3], np.int32)
        server = InferenceServer(net, batch_slots=2, max_len=32,
                                 block_size=8, max_prompt_len=8,
                                 speculative=3)
        server.submit(p, max_new_tokens=8)
        server.run()
        snap = telemetry.snapshot()
        cnt = snap["counters"]
        total = cnt.get("serving_spec_tokens_accepted_total", 0) \
            + cnt.get("serving_spec_tokens_rejected_total", 0)
        assert total > 0
        assert "serving_draft_accept_rate" in snap["gauges"]
        assert snap["histograms"][
            "serving_tpot_seconds{spec=on}"]["count"] == 1
    finally:
        telemetry.disable()
        telemetry.reset()


def test_chunked_prefill_health_backlog_signal(net):
    rs = np.random.RandomState(52)
    server = InferenceServer(net, batch_slots=1, max_len=32,
                             block_size=8, max_prompt_len=12,
                             prefill_chunk_tokens=4)
    server.submit(rs.randint(0, 256, 12).astype(np.int32),
                  max_new_tokens=2)
    server.submit(rs.randint(0, 256, 10).astype(np.int32),
                  max_new_tokens=2)
    server.step()   # admit + first 4-token chunk
    d = server.health_detail()
    # 8 unprefilled tokens on the running slot + 10 queued
    assert d["prefill_backlog_tokens"] == 18
    assert d["prefill_chunk_tokens"] == 4
    assert d["speculative"] is False
    server.run()
    assert server.health_detail()["prefill_backlog_tokens"] == 0


# -- one decode tick queued ahead ---------------------------------------------
# The server launches tick n+1 before it reads tick n's tokens. Every
# request must still hold the tokens of the order that reads before it
# launches (the parent's), and a row computed for a request that has
# ended in the meantime must reach nobody.

class _NoDrafts:
    """A proposer that never proposes. `speculative=` makes the server
    read a tick before it launches the next, so this is the serial
    order on the plain decode program: the in-process control."""
    k = 2

    def propose(self, tokens):
        return np.zeros(0, np.int32)


#: (prompt tokens, new tokens, temperature) and the `step()`s between
#: the arrivals; greedy and sampled rows share every tick
AHEAD_MIX = [(5, 9, 0.0), (11, 6, 0.8), (3, 12, 0.0), (16, 7, 1.1),
             (8, 10, 0.0), (2, 5, 0.7), (13, 8, 0.0)]
AHEAD_ARRIVALS = [(2, 2), (2, 3), (1, 1), (2, 0)]   # (submit, steps)
#: what commit 268f475 (the serial tick) served for AHEAD_MIX
AHEAD_PINNED = [
    [223, 223, 223, 223, 223, 223, 223, 223, 223],
    [245, 183, 160, 252, 153, 8],
    [169, 169, 54, 119, 125, 6, 67, 58, 202, 119, 119, 119],
    [5, 129, 31, 119, 58, 112, 81],
    [119, 82, 82, 82, 98, 98, 98, 9, 195, 195],
    [67, 80, 166, 150, 202],
    [92, 102, 102, 158, 149, 149, 149, 133]]


def _staggered(net, **kw):
    """AHEAD_MIX through a three-slot server, arriving in four groups
    with ticks between them. Returns (server, requests)."""
    rs = np.random.RandomState(21)
    kw = dict(dict(batch_slots=3, max_len=48, block_size=8,
                   max_prompt_len=16), **kw)
    server = InferenceServer(net, **kw)
    todo = list(enumerate(AHEAD_MIX))
    reqs = []
    for n_submit, n_steps in AHEAD_ARRIVALS:
        for _ in range(n_submit):
            i, (n, new, temp) = todo.pop(0)
            reqs.append(server.submit(
                rs.randint(0, 256, n).astype(np.int32),
                max_new_tokens=new, temperature=temp,
                top_k=20 if temp else 0, top_p=0.9 if temp else 0.0,
                seed=100 + i))
        for _ in range(n_steps):
            server.step()
    server.run()
    return server, reqs


def test_ahead_serves_the_serial_orders_tokens_pinned(net):
    # the programs are cached on the net by shape: count from here
    calls0 = InferenceServer(net, batch_slots=3, max_len=48, block_size=8,
                             max_prompt_len=16).stats()["decode_calls"]
    server, reqs = _staggered(net)
    free0 = server.cache.num_blocks - 1
    assert [r.output_tokens for r in reqs] == AHEAD_PINNED
    assert all(r.status == "ok" for r in reqs)
    st = server.stats()
    assert 0 < st["ticks_ahead"] < st["ticks"]
    assert st["tokens_generated"] == sum(n for _, n, _ in AHEAD_MIX)
    # nothing in flight, and the pool is back where it started
    assert not server._flights
    assert server.cache.num_free_blocks == free0
    assert st["decode_calls"] - calls0 == st["ticks"]   # none dropped
    server.cache.check()


@pytest.mark.parametrize("kw", [
    {}, {"prefix_cache": True}, {"prefill_chunk_tokens": 4},
    {"kv_cache_dtype": "int8"},
    {"num_blocks": 6, "max_preemptions": None},
    {"prefix_cache": True, "prefill_chunk_tokens": 8, "num_blocks": 7,
     "max_preemptions": None}],
    ids=["plain", "prefix", "chunked", "int8", "starved",
         "prefix-chunked-starved"])
def test_ahead_equals_read_before_launch(net, kw):
    """Request by request the tokens of the serial order, whatever
    else the server does in the tick; the serial control never has a
    tick queued ahead."""
    ahead, got = _staggered(net, **kw)
    serial, want = _staggered(net, speculative=_NoDrafts(), **kw)
    assert serial.stats()["ticks_ahead"] == 0
    assert ahead.stats()["ticks_ahead"] > 0
    for g, w in zip(got, want):
        assert g.status == w.status == "ok"
        assert g.output_tokens == w.output_tokens
    for s in (ahead, serial):
        assert not s._flights
        assert s.cache.num_used_blocks == 0
        s.cache.check()
    if "num_blocks" in kw:
        assert ahead.stats()["preemptions"] > 0


def test_step_from_idle_hands_over_the_first_tick(net):
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    p = np.arange(1, 6, dtype=np.int32)
    want = generate(net, p[None, :], max_new_tokens=3, max_len=32)[0, 5:]
    r = server.submit(p, max_new_tokens=3)
    calls0 = server.compile_stats()["decode_calls"]
    calls = lambda: (server.compile_stats()["decode_calls"]  # noqa: E731
                     - calls0)
    # launch tick 1, launch tick 2, hand over tick 1
    assert server.step() == 1
    assert r.output_tokens == [int(want[0])]
    assert calls() == 2 and len(server._flights) == 1
    assert server.stats()["ticks_ahead"] == 1
    # `_active` holds until the LAST token has been handed over, so a
    # drive loop keeps stepping while a tick is in flight
    assert server.step() == 1 and calls() == 3
    assert server._active.any() and len(server._flights) == 1
    # the third token is in flight: nothing left to launch
    assert server.step() == 1 and calls() == 3
    assert r.status == "ok" and r.output_tokens == list(want)
    assert not server._active.any() and not server._flights
    assert server.step() == 0 and calls() == 3


def _first_new_token(tokens, at_least=2):
    """Index of the first token from `at_least` on that none before it
    equals: as `eos_id` it ends the request exactly there."""
    return next(k for k in range(at_least, len(tokens))
                if tokens[k] not in tokens[:k])


def test_eos_row_behind_the_last_token_is_dropped(net):
    rs = np.random.RandomState(61)
    pa = rs.randint(0, 256, 6).astype(np.int32)
    pb = rs.randint(0, 256, 4).astype(np.int32)
    solo_a = [int(t) for t in generate(
        net, pa[None, :], max_new_tokens=12, max_len=32)[0, 6:]]
    solo_b = [int(t) for t in generate(
        net, pb[None, :], max_new_tokens=10, max_len=32)[0, 4:]]
    k = _first_new_token(solo_a)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    free0 = server.cache.num_free_blocks
    ra = server.submit(pa, max_new_tokens=12, eos_id=solo_a[k])
    rb = server.submit(pb, max_new_tokens=10)
    while ra.status is None:
        server.step()
        assert len(ra.output_tokens) <= k + 1
    assert ra.finish_reason == "eos"
    assert ra.output_tokens == solo_a[:k + 1]
    # the tick behind the eos was already queued with a row of ra's:
    # it reaches nobody, and is not counted
    server.run()
    assert ra.output_tokens == solo_a[:k + 1]
    assert rb.output_tokens == solo_b
    assert server.tokens_generated == k + 1 + 10
    assert not server._flights
    assert server.cache.num_free_blocks == free0
    server.cache.check()


def test_eos_of_the_only_request_leaves_nothing_in_flight(net):
    p = np.arange(3, 9, dtype=np.int32)
    solo = [int(t) for t in generate(
        net, p[None, :], max_new_tokens=12, max_len=32)[0, 6:]]
    k = _first_new_token(solo)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    r = server.submit(p, max_new_tokens=12, eos_id=solo[k])
    calls0 = server.compile_stats()["decode_calls"]
    steps = 0
    while server.queue or server._active.any():     # a caller's loop
        server.step()
        steps += 1
    assert steps == k + 1 and r.output_tokens == solo[:k + 1]
    # k + 2 ticks ran; the last held a dropped row alone and was let go
    assert server.compile_stats()["decode_calls"] - calls0 == k + 2
    assert server.ticks == k + 1
    assert not server._flights
    assert server.cache.num_used_blocks == 0


def test_cancel_drops_the_row_in_flight_and_the_slot_is_reused(net):
    rs = np.random.RandomState(62)
    prompts = [rs.randint(0, 256, n).astype(np.int32) for n in (5, 7, 4)]
    solo = [[int(t) for t in generate(
        net, p[None, :], max_new_tokens=9, max_len=32)[0, len(p):]]
        for p in prompts]
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    r0 = server.submit(prompts[0], max_new_tokens=9)
    r1 = server.submit(prompts[1], max_new_tokens=9)
    for _ in range(3):
        server.step()
    assert len(server._flights) == 1       # with a row of r0's in it
    assert server.cancel(r0.id)
    # the newcomer takes r0's slot while that row is still in flight
    r2 = server.submit(prompts[2], max_new_tokens=9)
    server.step()
    assert server._slot_req.index(r2) == 0
    server.run()
    assert r0.status == "cancelled" and r0.output_tokens == solo[0][:3]
    assert r1.output_tokens == solo[1]
    assert r2.output_tokens == solo[2]     # none of r0's tokens
    assert server.tokens_generated == 3 + 9 + 9
    assert not server._flights and server.cache.num_used_blocks == 0
    server.cache.check()


def test_deadline_drops_the_row_in_flight(net):
    import time as _t
    p = np.arange(2, 8, dtype=np.int32)
    solo = [int(t) for t in generate(
        net, p[None, :], max_new_tokens=20, max_len=32)[0, 6:]]
    server = InferenceServer(net, batch_slots=1, max_len=32,
                             block_size=8, max_prompt_len=8)
    server.warmup()                        # no compile inside the deadline
    r = server.submit(p, max_new_tokens=20, deadline_s=0.5)
    server.step()
    server.step()
    assert r.output_tokens == solo[:2] and len(server._flights) == 1
    _t.sleep(0.55)
    assert server.step() == 0              # expired at the tick's top
    assert r.status == "timed_out" and r.output_tokens == solo[:2]
    assert not server._flights
    assert server.cache.num_used_blocks == 0
    server.cache.check()


def test_preempted_requests_row_in_flight_is_dropped(net):
    """A starved pool: whenever a step has preempted a request, that
    request holds no token (its row of the tick in flight went with
    it), and in the end each holds the serial order's tokens."""
    rs = np.random.RandomState(18)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=12,
                             num_blocks=6)
    prompts = [rs.randint(0, 256, 10).astype(np.int32) for _ in range(2)]
    reqs = [server.submit(p, max_new_tokens=12) for p in prompts]
    seen = 0
    while server.queue or server._active.any():
        server.step()
        for r in server.queue:
            assert r.preemptions and r.output_tokens == []
            seen += 1
    assert seen and sum(r.preemptions for r in reqs) >= 1
    for p, r in zip(prompts, reqs):
        one = generate(net, p[None, :], max_new_tokens=12, max_len=32)
        assert r.output_tokens == [int(t) for t in one[0, 10:]]
    assert server.tokens_generated == 24
    assert not server._flights and server.cache.num_used_blocks == 0
    server.cache.check()


@pytest.mark.parametrize("how", ["run", "drain", "shutdown",
                                 "shutdown-now"])
def test_teardown_leaves_no_tick_in_flight(net, how):
    rs = np.random.RandomState(63)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    free0 = server.cache.num_free_blocks
    reqs = [server.submit(rs.randint(0, 256, 5).astype(np.int32),
                          max_new_tokens=n) for n in (6, 9, 4)]
    server.step()
    server.step()
    assert len(server._flights) == 1
    if how == "run":
        server.run()
    elif how == "drain":
        server.drain()
    else:
        server.shutdown(drain=how == "shutdown")
    assert not server._flights
    assert not server._active.any() and not server.queue
    assert server.cache.num_free_blocks == free0
    if how == "shutdown-now":
        assert [r.status for r in reqs] == ["rejected"] * 3
        assert all(len(r.output_tokens) == 2 for r in reqs[:2])
    else:
        assert [len(r.output_tokens) for r in reqs] == [6, 9, 4]
    server.cache.check()


def test_speculation_reads_before_it_launches(net):
    """Drafts are proposed from the tokens just handed over, so with
    `speculative=` no tick is ever queued ahead."""
    rs = np.random.RandomState(64)
    server = InferenceServer(net, batch_slots=2, max_len=48,
                             block_size=8, max_prompt_len=8,
                             speculative=2)
    prompts = [rs.randint(0, 256, 6).astype(np.int32) for _ in range(3)]
    reqs = [server.submit(p, max_new_tokens=14) for p in prompts]
    while server.queue or server._active.any():
        server.step()
        assert not server._flights
    assert server.stats()["ticks_ahead"] == 0
    assert server.stats()["spec_tokens_accepted"] > 0
    for p, r in zip(prompts, reqs):
        one = generate(net, p[None, :], max_new_tokens=14, max_len=48)
        assert r.output_tokens == [int(t) for t in one[0, 6:]]


def test_uploads_are_copies_not_views():
    from mxnet_tpu.serving.server import _upload
    host = np.zeros((64, 64), np.int32)
    dev = _upload(host)
    row = _upload(host[3])
    host[:] = 7
    assert int(dev.sum()) == 0 and int(row.sum()) == 0
