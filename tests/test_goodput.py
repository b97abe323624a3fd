"""Goodput ledger, MFU accounting, memory-pressure forecasting, and the
bench regression sentinel (ISSUE 15).

The load-bearing invariant everywhere: **conservation** — every second
of wall clock since the ledger's epoch is attributed to exactly one
category (productive or a named badput bucket), so
``sum(snapshot()["seconds"].values()) == snapshot()["elapsed_s"]`` at
any instant, across overlapping spans, across publish(), and across a
SIGKILL + restart (the dead window lands in ``fault_recovery``).
"""
import json
import math
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from mxnet_tpu import flight, goodput, telemetry
from mxnet_tpu.goodput import CATEGORIES, GoodputLedger, PoolForecaster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_slate():
    goodput.reset()
    telemetry.disable()
    telemetry.reset()
    flight.disable()
    flight.clear()
    yield
    goodput.reset()
    telemetry.disable()
    telemetry.reset()
    flight.disable()
    flight.clear()


def _conserved(led, now):
    snap = led.snapshot(now=now)
    total = sum(snap["seconds"].values())
    assert math.isclose(total, snap["elapsed_s"], rel_tol=0, abs_tol=1e-6), \
        (total, snap["elapsed_s"], snap["seconds"])
    return snap


# -- ledger: conservation under adversarial charging ------------------------

def test_ledger_conservation_fuzz():
    """Random overlapping spans and gap charges on a synthetic clock:
    the categories always sum to elapsed, and no category goes
    negative."""
    rs = np.random.RandomState(7)
    cats = [c for c in CATEGORIES if c != "idle"]
    led = GoodputLedger(t0=100.0)
    t = 100.0
    for _ in range(300):
        t += float(rs.rand()) * 0.5
        op = rs.randint(3)
        cat = cats[rs.randint(len(cats))]
        if op == 0:
            # span ending now — may overlap the frontier arbitrarily
            led.charge_span(cat, float(rs.rand()) * 2.0, end=t)
        elif op == 1:
            led.charge_gap(cat, now=t)
        # op == 2: let wall clock pass unattributed (idle remainder)
        snap = _conserved(led, t)
        assert all(v >= -1e-9 for v in snap["seconds"].values()), \
            snap["seconds"]


def test_ledger_deterministic_spans_and_idle():
    led = GoodputLedger(t0=0.0)
    led.charge_span("compile", 2.0, end=2.0)
    # overlapping span: only the post-frontier tail (1.0s) is charged
    led.charge_span("productive", 2.0, end=3.0)
    snap = led.snapshot(now=10.0)
    assert math.isclose(snap["seconds"]["compile"], 2.0, abs_tol=1e-9)
    assert math.isclose(snap["seconds"]["productive"], 1.0, abs_tol=1e-9)
    assert math.isclose(snap["seconds"]["idle"], 7.0, abs_tol=1e-9)
    _conserved(led, now=10.0)


def test_ledger_rejects_unknown_category():
    led = GoodputLedger(t0=0.0)
    with pytest.raises(KeyError):
        led.charge_span("snacks", 1.0, end=1.0)


def test_ledger_restart_gap_becomes_fault_recovery():
    """state_dict() → (process dies) → restore_state() on a fresh
    ledger: the dead wall-clock window is charged to fault_recovery and
    conservation holds for the merged ledger."""
    a = GoodputLedger()
    time.sleep(0.05)
    a.charge_gap("productive")  # attribute everything since epoch
    st = a.state_dict()
    st["wall"] -= 3.0          # pretend the save happened 3s ago
    b = GoodputLedger()
    b.restore_state(st)
    snap = b.snapshot()
    assert snap["seconds"]["fault_recovery"] >= 2.9
    assert snap["seconds"]["productive"] >= 0.04
    total = sum(snap["seconds"].values())
    assert math.isclose(total, snap["elapsed_s"], abs_tol=1e-3)


# -- hook plumbing: phase marks and flight events feed the ledger -----------

def test_mark_phase_feeds_ledger_and_publish_exports():
    telemetry.enable()
    goodput.enable()
    telemetry.mark_phase("fused_step", 0.05)
    telemetry.mark_phase("definitely_not_a_phase", 0.5)  # unmapped
    secs = goodput.snapshot()["seconds"]
    assert secs["productive"] > 0.0
    goodput.publish()
    prom = telemetry.to_prometheus()
    assert "goodput_seconds_total" in prom
    keys = [k for k in telemetry.snapshot()["counters"]
            if k.startswith("goodput_seconds_total")
            and "productive" in k]
    assert keys, telemetry.snapshot()["counters"]
    assert "goodput" in telemetry.breakdown_table()


def test_publish_exports_settled_seconds_only():
    """The pending frontier→now idle remainder is NOT exported — the
    counter carries settled attribution only."""
    telemetry.enable()
    goodput.enable()
    t0 = goodput.ledger().t0
    goodput.charge_span("productive", 1.0, end=t0 + 1.0)
    goodput.publish()
    counters = telemetry.snapshot()["counters"]
    total = sum(v for k, v in counters.items()
                if k.startswith("goodput_seconds_total"))
    assert math.isclose(total, 1.0, abs_tol=1e-6), counters


def test_flight_events_become_badput():
    telemetry.enable()
    flight.enable()
    goodput.enable()
    time.sleep(0.01)
    flight.record("stall", "test_site")
    secs = goodput.snapshot()["seconds"]
    assert secs["stall"] > 0.0
    time.sleep(0.01)
    flight.record("exception", "test_site")
    secs = goodput.snapshot()["seconds"]
    assert secs["fault_recovery"] > 0.0


def test_disable_detaches_hooks():
    telemetry.enable()
    goodput.enable()
    goodput.disable()
    telemetry.mark_phase("fused_step", 0.25)
    assert goodput.snapshot()["seconds"]["productive"] == 0.0


# -- MFU / HFU gauges -------------------------------------------------------

class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_peak_table_is_keyed_by_device_kind():
    assert goodput.peak_flops(_Dev("tpu", "TPU v5 lite")) == 197e12
    # no honest CPU peak: nothing to divide by
    assert goodput.peak_flops(_Dev("cpu", "cpu")) is None
    # an accelerator that is not in the table is an error, not a default
    with pytest.raises(KeyError, match="PEAK_FLOPS_BY_KIND"):
        goodput.peak_flops(_Dev("tpu", "TPU v99"))


def test_mfu_hfu_gauge_math(monkeypatch):
    telemetry.enable()
    goodput.enable()
    model_f, hw_f = 2.5e11, 5.0e11
    # the suite runs on the CPU platform, where there is no peak and
    # so no MFU/HFU at all
    goodput.note_train_step(1.0, model_flops=model_f, hw_flops=hw_f)
    assert not [k for k in telemetry.snapshot()["gauges"]
                if k.startswith(("goodput_mfu", "goodput_hfu"))]
    # on a chip in the table, the denominator is step time x the chips
    # the STEP spans x that chip's peak
    monkeypatch.setattr(goodput, "peak_flops", lambda device=None: 197e12)
    goodput.note_train_step(1.0, chips=4)
    denom = 1.0 * 4 * 197e12
    mfu = telemetry.read_gauge("goodput_mfu", flops_source="analytic")
    hfu = telemetry.read_gauge("goodput_hfu",
                               flops_source="cost_analysis")
    assert mfu is not None and math.isclose(mfu, model_f / denom,
                                            rel_tol=1e-9)
    assert hfu is not None and math.isclose(hfu, hw_f / denom,
                                            rel_tol=1e-9)


def test_tokens_per_sec_per_chip_gauge():
    telemetry.enable()
    goodput.enable()
    goodput.note_tokens("serve", 500)
    time.sleep(0.01)
    goodput.publish()
    tps = telemetry.read_gauge("goodput_serve_tokens_per_sec_per_chip")
    assert tps is not None and tps > 0.0


# -- per-process ledgers merge over the registry-delta plane ----------------

def test_ledger_counters_merge_across_processes():
    """Two simulated processes publish goodput_seconds_total deltas;
    _merge_registry must SUM the per-category counters — the fleet view
    is additive chip-seconds."""
    blobs = {}
    for pid, secs in ((0, 2.0), (1, 3.0)):
        telemetry.enable()
        goodput.enable()
        goodput.charge_span("compile", secs,
                            end=goodput.ledger().t0 + secs)
        goodput.publish()
        blobs[pid], _ = telemetry.registry_delta(None)
        goodput.reset()
        telemetry.disable()
        telemetry.reset()
    merged = telemetry._merge_registry(blobs)
    fam = merged["goodput_seconds_total"]
    by_cat = {dict(k).get("category"): ch.value
              for k, ch in fam.children.items()}
    assert math.isclose(by_cat["compile"], 5.0, abs_tol=1e-6), by_cat


# -- checkpoint round-trip (in-process) -------------------------------------

def test_goodput_state_rides_checkpoint_manifest(tmp_path):
    import mxnet_tpu as mx
    from mxnet_tpu.checkpoint import Checkpointer

    telemetry.enable()
    goodput.enable()
    net = mx.gluon.nn.Dense(4)
    net.initialize()
    mx.nd.waitall()
    time.sleep(0.05)
    goodput.charge_gap("productive")
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(1, net=net)
    ck.close()
    before = goodput.snapshot()["seconds"]["productive"]

    ck2 = Checkpointer(str(tmp_path / "ck"))
    meta = ck2.restore(net=net)
    ck2.close()
    assert meta is not None
    snap = goodput.snapshot()
    # restore merges the saved ledger's seconds on top of the live one
    assert snap["seconds"]["productive"] >= before + 0.04
    assert snap["seconds"]["checkpoint_restore"] >= 0.0
    total = sum(snap["seconds"].values())
    assert math.isclose(total, snap["elapsed_s"], abs_tol=1e-3)


# -- PoolForecaster ---------------------------------------------------------

def test_forecaster_eta_and_health_fire_before_exhaustion():
    fc = PoolForecaster(critical_s=5.0, name="kv_pool")
    for i in range(10):
        fc.add(i * 0.1, 100.0 - 10.0 * i)     # -100 blocks/s
    eta = fc.exhaust_in_s()
    assert eta is not None and math.isclose(eta, 0.1, rel_tol=0.2)
    ok, reason = fc.health()
    assert not ok and "exhaustion forecast" in reason
    # the alarm fires while blocks are STILL free — before, not after
    assert fc.health_detail()["blocks_free"] > 0


def test_forecaster_stable_pool_and_thin_window():
    fc = PoolForecaster(critical_s=5.0)
    fc.add(0.0, 50.0)
    fc.add(0.1, 50.0)
    assert fc.exhaust_in_s() is None          # thin window
    for i in range(2, 12):
        fc.add(i * 0.1, 50.0)
    assert fc.exhaust_in_s() is None          # flat trend
    ok, _ = fc.health()
    assert ok


def test_forecaster_registers_as_health_source():
    telemetry.enable()
    fc = PoolForecaster(critical_s=60.0, name="test_pool")
    for i in range(10):
        fc.add(i * 0.1, 100.0 - 10.0 * i)
    telemetry.register_health_source(fc)
    try:
        ok, reason = telemetry.health()
        assert not ok and "test_pool" in reason
    finally:
        telemetry.unregister_health_source(fc)
    ok, _ = telemetry.health()
    assert ok


# -- router: long prompts divert away from forecast exhaustion --------------

class _FakeReplica:
    """Minimal LocalReplica stand-in: healthy, instant decode, with a
    programmable exhaust_in_s in its heartbeat."""

    def __init__(self, name, exhaust=None):
        self.name = name
        self.dead = False
        self.exhaust = exhaust
        self.got = []

    def probe(self, now):
        return {"ok": True, "reason": "", "t": now,
                "slots": 4, "queued": 0, "active": 0,
                "blocks_free": 50, "block_size": 8,
                "queue_age_p95_s": 0.0, "prefill_backlog_tokens": 0,
                "exhaust_in_s": self.exhaust,
                "clock": {"perf": time.perf_counter(),
                          "unix": time.time()}}

    def submit(self, fr, attempt_key, deadline_s):
        self.got.append(np.asarray(fr.prompt))
        return object()

    def drive(self):
        return 0

    def poll(self, sub):
        return {"status": "ok", "tokens": [1],
                "finish_reason": "length", "ttft": 0.01}

    def cancel(self, sub):
        pass

    def discard(self, sub):
        pass

    def begin_drain(self):
        pass

    def end_drain(self):
        pass

    def restart(self):
        pass


def test_router_diverts_long_prompts_from_at_risk_replica():
    from mxnet_tpu.serving.router import FleetRouter

    telemetry.enable()
    r0 = _FakeReplica("tight", exhaust=2.0)    # inside the window
    r1 = _FakeReplica("roomy", exhaust=None)   # no exhaustion in sight
    fleet = FleetRouter([r0, r1], affinity_blocks=0, block_size=8,
                        exhaust_window_s=30.0, long_prompt_blocks=2)
    longs = [fleet.submit(np.arange(16, dtype=np.int32), 4)
             for _ in range(3)]
    short = fleet.submit(np.arange(4, dtype=np.int32), 4)
    fleet.run(max_ticks=50)
    assert all(fr.status == "ok" for fr in longs + [short])
    assert all(len(p) < 16 for p in r0.got), \
        [len(p) for p in r0.got]               # no long prompt landed
    assert sum(len(p) >= 16 for p in r1.got) == 3
    div = telemetry.snapshot()["counters"].get(
        "router_exhaust_diverted_total", 0)
    assert div >= 3


def test_router_availability_wins_when_all_replicas_at_risk():
    from mxnet_tpu.serving.router import FleetRouter

    r0 = _FakeReplica("a", exhaust=1.0)
    r1 = _FakeReplica("b", exhaust=2.0)
    fleet = FleetRouter([r0, r1], affinity_blocks=0, block_size=8,
                        exhaust_window_s=30.0, long_prompt_blocks=2)
    fr = fleet.submit(np.arange(16, dtype=np.int32), 4)
    fleet.run(max_ticks=50)
    assert fr.status == "ok"                   # served, not starved


# -- KV-cache fragmentation / parked-blocks gauges --------------------------

def _cache(**kw):
    from mxnet_tpu.serving.kv_cache import PagedKVCache
    base = dict(num_layers=2, num_kv_heads=2, head_dim=8, num_blocks=9,
                block_size=4, batch_slots=3, max_blocks_per_seq=4)
    base.update(kw)
    return PagedKVCache(**base)


def test_fragmentation_zero_on_contiguous_free_list():
    c = _cache()
    assert c.fragmentation() == 0.0
    assert c.parked_blocks() == 0
    st = c.stats()
    assert st["fragmentation"] == 0.0
    assert st["parked_blocks"] == 0


def test_fragmentation_after_interleaved_free():
    c = _cache()
    for slot in (0, 1, 2):
        assert c.alloc(slot, 8)    # 2 blocks each, LIFO from the end
    c.free_slot(1)                 # punch a hole mid-range
    # free ids {1,2} ∪ slot-1's pair: two runs of 2 in 4 free blocks
    assert math.isclose(c.fragmentation(), 0.5, abs_tol=1e-9)
    c.check()


def test_parked_blocks_counts_registered_free_blocks():
    c = _cache(prefix_cache=True)
    assert c.alloc(0, 8)
    toks = np.arange(8, dtype=np.int32)
    c.register_prefix(0, toks)
    c.free_slot(0)
    assert c.parked_blocks() == 2   # free but content-addressable
    assert c.stats()["parked_blocks"] == 2
    c.check()


# -- SIGKILL + restart: badput attribution survives the process -------------

GOODPUT_WORKER = textwrap.dedent("""
    import json, sys, os
    sys.path.insert(0, {repo!r})
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import goodput, telemetry
    from mxnet_tpu.checkpoint import Checkpointer

    ckdir, total, outp = sys.argv[1], int(sys.argv[2]), sys.argv[3]

    telemetry.enable()
    goodput.enable()
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(16, activation="relu"))
    net.add(mx.gluon.nn.Dense(4))
    net.initialize(init=mx.init.Xavier())
    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {{"learning_rate": 0.1}})

    rs = np.random.RandomState(42)
    X = mx.nd.array(rs.rand(8, 10).astype(np.float32))
    Y = mx.nd.array(rs.randint(0, 4, 8), dtype="int32")
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    ck = Checkpointer(ckdir)
    meta = ck.restore(net=net, trainer=tr, missing_ok=True)
    start = int(meta["step"]) if meta else 0
    for s in range(start + 1, total + 1):
        with mx.autograd.record():
            l = loss_fn(net(X), Y).mean()
        l.backward()
        tr.step(1)              # step.kill fires here when armed
        ck.save(s, net=net, trainer=tr)
    ck.close()
    with open(outp, "w") as f:
        json.dump(goodput.snapshot(), f)
    print("GOODPUT_WORKER_DONE", start, total)
""")


def _run_worker(script, args, fault=None, timeout=150):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_TPU_FAULTS", None)
    env.pop("MXNET_TPU_GOODPUT", None)
    if fault:
        env["MXNET_TPU_FAULTS"] = fault
    p = subprocess.Popen(
        [sys.executable, "-u", str(script)] + [str(a) for a in args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    try:
        out, _ = p.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        p.kill()
        pytest.fail("goodput worker hung")
    return p.returncode, out


@pytest.mark.slow
def test_sigkill_restart_attributes_dead_window_to_fault_recovery(
        tmp_path):
    """A worker is SIGKILLed mid-step; the restarted worker restores
    the goodput ledger from the checkpoint manifest, charges the dead
    window (kill → restart, including respawn + import) to
    fault_recovery, and the merged ledger still conserves."""
    script = tmp_path / "worker.py"
    script.write_text(GOODPUT_WORKER.format(repo=REPO))
    outp = tmp_path / "snap.json"
    rc, out = _run_worker(script, [tmp_path / "ck", 5, outp],
                          fault="step.kill:at=3")
    assert rc == -signal.SIGKILL, (rc, out)
    rc, out = _run_worker(script, [tmp_path / "ck", 5, outp])
    assert rc == 0 and "GOODPUT_WORKER_DONE 2 5" in out, out
    snap = json.loads(outp.read_text())
    secs = snap["seconds"]
    assert secs["fault_recovery"] > 0.0, secs
    assert secs["checkpoint_save"] > 0.0, secs
    total = sum(secs.values())
    assert math.isclose(total, snap["elapsed_s"], rel_tol=1e-3,
                        abs_tol=0.05), (total, snap["elapsed_s"])
