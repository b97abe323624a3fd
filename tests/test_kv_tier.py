"""KV-block memory hierarchy (mxnet_tpu.serving.kv_tier): content-key
and payload codec round-trips, the disk-backed PrefixStore's
manifest/digest discipline, host-tier spill/restore through the traced
spill/restore executables (token parity, compile discipline, allocator
invariants under churn), spill-on-preempt under pool pressure, the
`kv.spill_corrupt` / `kv.restore_slow` fault sites, warm restarts from
the persistent store, and disaggregated prefill→decode block streaming
through the fleet router."""
import json
import os
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, telemetry
from mxnet_tpu.serving import (InferenceServer, FleetRouter,
                               LocalReplica, ProcReplica, FileKV,
                               KVTierManager, PrefixStore,
                               run_fleet_worker)
from mxnet_tpu.serving import kv_tier
from mxnet_tpu.serving.kv_tier import (TierBlock, _chain_key,
                                       _flatten_key, _pack, _unpack,
                                       _payload_digest, encode_wire,
                                       decode_wire)


@pytest.fixture(autouse=True)
def _clean():
    faults.clear()
    telemetry.disable()
    telemetry.reset()
    yield
    faults.clear()
    telemetry.disable()
    telemetry.reset()


@pytest.fixture(scope="module")
def net():
    mx.random.seed(0)
    n = mx.models.get_model("llama_tiny")
    n.initialize()
    n(mx.nd.array(np.zeros((1, 4)), dtype="int32"))  # materialize
    return n


def _srv(net, **kw):
    args = dict(batch_slots=4, max_len=64, block_size=4,
                max_prompt_len=32, kv_tiering=True)
    args.update(kw)
    return InferenceServer(net, **args)


def _prompts(seed, specs):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 250, (n,)).tolist() for n in specs]


def _serve(s, prompts, new=6, seed=0):
    reqs = [s.submit(p, new, seed=seed) for p in prompts]
    s.run()
    assert all(r.status == "ok" for r in reqs), \
        [(r.status, r.finish_reason) for r in reqs]
    return [r.output_tokens for r in reqs]


# -- content keys and payload codec -----------------------------------------

def test_flat_and_chain_key_roundtrip():
    toks = (5, 1, 2, 3, 4, 9, 9)
    key = _chain_key(toks, 3)
    assert key == (((None, (5, 1, 2)), (3, 4, 9)), (9,))
    assert _flatten_key(key) == toks
    assert _flatten_key(None) == ()
    assert _chain_key((), 4) is None


def test_pack_unpack_roundtrip_extension_dtypes():
    import jax.numpy as jnp
    payload = {
        "k": np.asarray(jnp.arange(24, dtype=jnp.bfloat16)
                        .reshape(2, 3, 4)),
        "v": np.random.RandomState(0).randn(2, 3, 4)
        .astype(np.float32),
        "ks": np.random.RandomState(1).randn(2, 3).astype(np.float32),
    }
    out = _unpack(_pack(payload))
    assert set(out) == set(payload)
    for f in payload:
        assert out[f].dtype == payload[f].dtype
        np.testing.assert_array_equal(np.asarray(out[f], np.float32),
                                      np.asarray(payload[f],
                                                 np.float32))
    assert _payload_digest(out) == _payload_digest(payload)


def test_wire_roundtrip_drops_tampered_entries():
    payload = {"k": np.arange(8, dtype=np.float32).reshape(2, 4)}
    good = TierBlock((1, 2, 3), payload)
    wire = encode_wire([good])
    out = decode_wire(wire)
    assert len(out) == 1 and out[0].tokens == (1, 2, 3)
    np.testing.assert_array_equal(out[0].payload["k"], payload["k"])
    # tamper with the payload: the digest check drops the entry
    recs = json.loads(wire)
    bad = TierBlock((1, 2, 3), {"k": payload["k"] + 1.0})
    recs[0]["data"] = json.loads(encode_wire([bad]))[0]["data"]
    assert decode_wire(json.dumps(recs)) == []
    assert decode_wire("not json") == []


# -- PrefixStore ------------------------------------------------------------

def _entries(n=3, seed=0):
    rs = np.random.RandomState(seed)
    return [TierBlock(tuple(range(i * 4, i * 4 + 4)),
                      {"k": rs.randn(2, 2, 4).astype(np.float32)})
            for i in range(n)]


def test_prefix_store_roundtrip_and_content_dedup(tmp_path):
    st = PrefixStore(str(tmp_path))
    ents = _entries()
    w1 = st.save(ents)
    assert w1 > 0
    # a second generation with identical content writes no new payload
    assert st.save(ents) == 0
    out = st.load()
    assert {e.tokens for e in out} == {e.tokens for e in ents}
    assert all(e.source == "disk" for e in out)
    for e, o in zip(sorted(ents, key=lambda x: x.tokens),
                    sorted(out, key=lambda x: x.tokens)):
        assert e.digest == o.digest


def test_prefix_store_skips_damaged_payload_and_manifest(tmp_path):
    st = PrefixStore(str(tmp_path))
    ents = _entries()
    st.save(ents)
    # corrupt one payload file: its entry is skipped, the rest load
    victim = os.path.join(st._bdir, ents[0].digest + ".bin")
    with open(victim, "r+b") as f:
        f.seek(20)
        f.write(b"\xff\xff\xff\xff")
    out = st.load()
    assert {e.tokens for e in out} \
        == {e.tokens for e in ents[1:]}
    # a damaged newest manifest falls back to the previous generation
    st.save(ents[1:])
    gens = st._generations()
    with open(os.path.join(st._mdir, f"{gens[-1] + 1}.json"),
              "w") as f:
        f.write("{broken")
    assert {e.tokens for e in st.load()} == {e.tokens
                                             for e in ents[1:]}


def test_store_damage_means_cold_start_not_crash(net, tmp_path):
    s = _srv(net, prefix_store_dir=str(tmp_path))
    _serve(s, _prompts(11, [16]))
    s.shutdown()
    assert s.tier.persist_saved > 0
    # corrupt every payload file: the next server must come up cold
    bdir = os.path.join(str(tmp_path), "blocks")
    for fn in os.listdir(bdir):
        with open(os.path.join(bdir, fn), "r+b") as f:
            f.seek(0)
            f.write(b"\x00" * 16)
    s2 = _srv(net, prefix_store_dir=str(tmp_path))
    assert s2.tier.host_blocks() == 0
    _serve(s2, _prompts(11, [16]))         # still serves fine
    s2.cache.check()


# -- host tier: spill / restore / parity ------------------------------------

def test_tiered_server_token_parity_and_warm_restore(net):
    prompts = _prompts(21, [24, 18])
    want = _serve(_srv(net, kv_tiering=False, prefix_cache=True),
                  prompts)
    s = _srv(net)
    got = _serve(s, prompts)
    assert got == want
    # park everything on the host tier, then resubmit: blocks restore
    # and prefill is skipped — the warm path, not a recompute
    spilled = s.tier.spill_parked()
    assert spilled > 0 and s.tier.host_blocks() == spilled
    assert s.cache.parked_blocks() == 0    # tier-aware accounting
    skipped0 = s.prefills_skipped
    got2 = _serve(s, prompts[:1])
    assert got2 == want[:1]
    assert s.tier.restores > 0 and s.tier.restore_bytes > 0
    assert s.prefills_skipped == skipped0 + 1
    assert s.tier.hits["host"] >= 1
    s.cache.check()


def test_compile_discipline_one_spill_one_restore_program(net):
    s = _srv(net)
    s.warm_tier()
    _serve(s, _prompts(22, [20, 12]))
    s.tier.spill_parked()
    _serve(s, _prompts(22, [20]))
    cs = s.compile_stats()
    assert cs["spill_compiles"] == 1, cs
    assert cs["restore_compiles"] == 1, cs
    assert cs["spill_calls"] > 1 and cs["restore_calls"] > 1


def test_demote_on_purge_instead_of_discard(net):
    """The parked-block purge bug: reclaiming a parked block under a
    cold allocation must demote its content to the host tier, not
    discard it."""
    s = _srv(net, batch_slots=2, max_len=32, num_blocks=9,
             max_prompt_len=16)
    first = _prompts(23, [12])
    _serve(s, first)                       # parks 12/4 = 3 blocks
    # a different stream of prompts reclaims the parked blocks
    _serve(s, _prompts(24, [12, 12]))
    assert s.tier.spills > 0
    flat = tuple(first[0][:s.cache.block_size])
    assert any(k[:len(flat)] == flat for k in
               s.tier.resident_keys() if len(k) >= len(flat))
    s.cache.check()


def test_pressure_run_spills_instead_of_preempting(net):
    """The pressure leg in miniature: a pool sized to force
    preemptions without tiering completes with zero (destructive)
    preemptions when the tier is on — evictions become spills,
    re-admissions become restores, tokens are unchanged."""
    def pressure(**kw):
        s = InferenceServer(net, batch_slots=4, max_len=32,
                            block_size=4, max_prompt_len=16,
                            num_blocks=13, max_preemptions=10, **kw)
        reqs = [s.submit(p, 12, seed=i) for i, p in
                enumerate(_prompts(25, [10, 10, 10, 10]))]
        s.run()
        assert all(r.status == "ok" for r in reqs)
        return s, [r.output_tokens for r in reqs]

    control, want = pressure(prefix_cache=True)
    assert control.preemptions > 0, "pool must be under pressure"
    tiered, got = pressure(kv_tiering=True)
    assert got == want
    assert tiered.preemptions == 0
    assert tiered.spill_preemptions > 0
    assert tiered.tier.spill_bytes > 0
    assert tiered.tier.restore_bytes > 0
    tiered.cache.check()


def test_allocator_check_survives_churn_with_spill(net):
    """100 rounds of admit/park/spill/restore churn keep every
    allocator + tier invariant intact."""
    s = _srv(net, batch_slots=3, max_len=32, num_blocks=17,
             max_prompt_len=16)
    rs = np.random.RandomState(26)
    pool = _prompts(27, [12, 8, 12, 16, 8, 12])
    for round_ in range(100):
        p = pool[rs.randint(len(pool))]
        r = s.submit(p, int(rs.randint(1, 4)), seed=0)
        s.run()
        assert r.status == "ok"
        if round_ % 3 == 0:
            s.tier.spill_parked(int(rs.randint(1, 5)))
        s.cache.check()                    # includes tier.check()
    assert s.tier.spills > 0 and s.tier.restores > 0


def test_host_capacity_evicts_lru(net):
    s = _srv(net, tier_host_blocks=2)
    _serve(s, _prompts(28, [16, 16]))
    s.tier.spill_parked()
    assert s.tier.host_blocks() <= 2
    assert s.tier.dropped > 0
    s.cache.check()


# -- fault sites ------------------------------------------------------------

def test_spill_corrupt_detected_and_recomputed(net):
    """`kv.spill_corrupt` flips a byte after the digest seals: the
    restore-side verification drops the entry, counts the failure,
    and the request recomputes to the same tokens."""
    prompts = _prompts(31, [20])
    want = _serve(_srv(net, kv_tiering=False, prefix_cache=True),
                  prompts)
    telemetry.enable()
    s = _srv(net)
    _serve(s, prompts)
    faults.inject("kv.spill_corrupt", at=1)
    s.tier.spill_parked()
    faults.clear()
    got = _serve(s, prompts)
    assert got == want                     # recompute fallback
    assert s.tier.restore_failed >= 1
    snap = telemetry.snapshot()["counters"]
    assert snap.get("serving_tier_restore_failed_total", 0) >= 1
    s.cache.check()                        # conservation still holds


def test_restore_slow_fault_trips_prefetch_timeout(net):
    prompts = _prompts(32, [24])
    s = _srv(net, tier_prefetch_timeout_s=0.001)
    _serve(s, prompts)
    s.tier.spill_parked()
    faults.inject("kv.restore_slow", ms=30)
    got = _serve(s, prompts)
    faults.clear()
    assert len(got[0]) == 6                # request still completes
    assert s.tier.restore_timeouts >= 1
    s.cache.check()


# -- persistence across restarts --------------------------------------------

def test_persistent_store_warm_restart_skips_prefill(net, tmp_path):
    prompts = _prompts(33, [24, 18])
    s = _srv(net, prefix_store_dir=str(tmp_path))
    want = _serve(s, prompts)
    s.shutdown()                           # persists resident prefixes
    assert s.tier.persist_saved > 0

    s2 = _srv(net, prefix_store_dir=str(tmp_path))
    assert s2.tier.persist_loaded > 0
    assert s2.tier.host_blocks() > 0
    got = _serve(s2, prompts[:1])
    assert got == want[:1]
    assert s2.prefills_skipped == 1        # restored-prefix warm path
    assert s2.tier.hits["disk"] >= 1
    s2.cache.check()


def test_tier_transition_fuzz_token_identical(net, tmp_path):
    """Tier-transition fuzz: random interleavings of spill-ahead,
    restore-at-admit, CoW-shared prefixes, preemption pressure, and a
    simulated SIGKILL restart (fresh server over the same persist
    dir) always produce tokens identical to a no-tiering server —
    at the 1-prefill + 1-decode compile discipline."""
    base = _prompts(34, [20, 16])
    shared = [base[0][:12] + _prompts(35, [8])[0],   # CoW prefixes
              base[0][:8] + _prompts(36, [6])[0]]
    pool = base + shared
    ref = InferenceServer(net, batch_slots=2, max_len=48,
                          block_size=4, max_prompt_len=32,
                          prefix_cache=True)
    rs = np.random.RandomState(37)

    def mk():
        return InferenceServer(net, batch_slots=2, max_len=48,
                               block_size=4, max_prompt_len=32,
                               num_blocks=21, max_preemptions=10,
                               kv_tiering=True,
                               prefix_store_dir=str(tmp_path))
    s = mk()
    cs0 = None
    for round_ in range(8):
        picks = [pool[i] for i in rs.randint(len(pool), size=2)]
        want = _serve(ref, picks)
        got = _serve(s, picks)
        assert got == want, f"diverged in round {round_}"
        if cs0 is None:
            # round 0 paid the one prefill + one decode compile (per
            # pool geometry); everything after — spills, restores,
            # preemptions, restarts — must reuse those executables
            cs0 = {k: v for k, v in s.compile_stats().items()
                   if k.endswith("_compiles")}
        op = round_ % 4
        if op == 0:
            s.tier.spill_parked(int(rs.randint(1, 6)))
        elif op == 1:
            s._preempt_youngest(-1)  # spill-preempt path
        elif op == 2:                      # simulated SIGKILL restart
            s.persist_prefixes()
            s = mk()
        s.cache.check()
    cs1 = {k: v for k, v in s.compile_stats().items()
           if k.endswith("_compiles")}
    extra = {k: (cs0.get(k, 0), v) for k, v in cs1.items()
             if v > cs0.get(k, 0)
             and k not in ("spill_compiles", "restore_compiles")}
    assert not extra, f"recompiled after round 0: {extra}"
    assert cs1.get("spill_compiles", 0) <= 1
    assert cs1.get("restore_compiles", 0) <= 1
    assert s.tier.spills > 0


# -- telemetry / stats surfaces ---------------------------------------------

def test_tier_stats_and_gauges_exported(net):
    telemetry.enable()
    s = _srv(net)
    _serve(s, _prompts(41, [16]))
    s.tier.spill_parked()
    _serve(s, _prompts(41, [16]))
    st = s.stats()
    for k in ("kv_tier_host_blocks", "kv_tier_spills",
              "kv_tier_restores", "kv_tier_hit_rates",
              "kv_tier_spill_bytes"):
        assert k in st, k
    assert st["kv_tier_spills"] > 0
    snap = telemetry.snapshot()
    assert snap["counters"].get("serving_tier_spills_total", 0) > 0
    assert snap["counters"].get("serving_tier_restores_total", 0) > 0
    gauges = snap["gauges"]
    assert "serving_tier_host_blocks" in gauges
    assert any(k.startswith("serving_tier_hit_rate") for k in gauges)
    hd = s.health_detail()
    assert hd["tiering"] is True


def test_tier_disabled_has_no_tier_surface(net):
    s = InferenceServer(net, batch_slots=2, max_len=32,
                        block_size=4, max_prompt_len=16)
    assert s.tier is None
    assert "kv_tier_spills" not in s.stats()
    assert s.health_detail()["tiering"] is False


# -- disaggregated prefill -> decode streaming ------------------------------

def test_disaggregated_fleet_token_identical(net):
    """The disaggregation leg: a 1-prefill + 1-decode fleet serves
    token-identical output to one combined replica, with blocks
    streamed over the kv channel and ZERO extra compiles on the
    decode replica after warm-up."""
    prompts = _prompts(42, [24, 16, 20])
    combined = _srv(net)
    combined.warm_tier()
    want = _serve(combined, prompts, new=8)

    telemetry.enable()
    sp, sd = _srv(net), _srv(net)
    sp.warm_tier()
    sd.warm_tier()
    cs0 = dict(sd.compile_stats())
    fleet = FleetRouter(
        [LocalReplica(sp, name="pf", role="prefill"),
         LocalReplica(sd, name="dc", role="decode")],
        disaggregate=True, affinity_blocks=0)
    frs = [fleet.submit(p, 8, seed=0) for p in prompts]
    fleet.run(timeout_s=120)
    assert [fr.status for fr in frs] == ["ok"] * 3
    assert [list(fr.output_tokens) for fr in frs] == want
    st = fleet.stats()
    assert st["prefill_exports"] == 3
    assert st["stream_dispatches"] == 3
    assert st["disagg_fallbacks"] == 0
    assert st["replicas"]["pf"]["role"] == "prefill"
    assert sd.tier.streamed_in > 0
    assert sd.prefills_skipped == 3        # decode never prefills
    snap = telemetry.snapshot()["counters"]
    assert snap.get("serving_blocks_streamed_total", 0) > 0
    cs1 = dict(sd.compile_stats())
    extra = {k: cs1[k] - cs0.get(k, 0) for k in cs1
             if k.endswith("_compiles") and cs1[k] != cs0.get(k, 0)}
    assert not extra, f"decode replica recompiled: {extra}"
    sd.cache.check()
    sp.cache.check()


def test_disaggregate_falls_back_without_prefill_replica(net):
    """With no prefill-role replica eligible the router serves
    combined (least-loaded) — availability over disaggregation."""
    prompts = _prompts(43, [16, 12])
    want = _serve(_srv(net), prompts, new=6)
    fleet = FleetRouter([LocalReplica(_srv(net), name="a"),
                         LocalReplica(_srv(net), name="b")],
                        disaggregate=True, affinity_blocks=0)
    frs = [fleet.submit(p, 6, seed=0) for p in prompts]
    fleet.run(timeout_s=120)
    assert [fr.status for fr in frs] == ["ok", "ok"]
    assert [list(fr.output_tokens) for fr in frs] == want
    assert fleet.stats()["disagg_fallbacks"] == 2
    assert fleet.stats()["prefill_exports"] == 0


def test_disagg_proc_replica_worker_protocol(net, tmp_path):
    """The worker half of disaggregation over FileKV: a threaded
    fleet worker answers `prefill_export` commands by publishing the
    wire on the kv channel; a LocalReplica decode adopts it."""
    kv = FileKV(str(tmp_path))
    t = threading.Thread(
        target=run_fleet_worker, args=(kv, "pf0"),
        kwargs=dict(server=_srv(net), hb_interval_s=0.02,
                    max_wall_s=300.0),
        daemon=True)
    t.start()
    sd = _srv(net)
    sd.warm_tier()
    try:
        fleet = FleetRouter(
            [ProcReplica(kv, "pf0", role="prefill"),
             LocalReplica(sd, name="dc", role="decode")],
            disaggregate=True, heartbeat_timeout_s=60.0,
            affinity_blocks=0)
        prompts = _prompts(44, [20, 12])
        want = _serve(_srv(net), prompts, new=6)
        frs = [fleet.submit(p, 6, seed=0) for p in prompts]
        fleet.run(timeout_s=240)
        assert [fr.status for fr in frs] == ["ok", "ok"]
        assert [list(fr.output_tokens) for fr in frs] == want
        assert {fr.replica for fr in frs} == {"dc"}
        assert fleet.stats()["prefill_exports"] == 2
        assert sd.tier.streamed_in > 0
        fleet.stop_fleet(timeout_ms=30_000)
    finally:
        t.join(timeout=60)
    assert not t.is_alive(), "worker must exit on stop"
