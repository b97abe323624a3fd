"""Unified training telemetry (mxnet_tpu/telemetry.py): metric family
semantics, the per-step timeline wired through Trainer / FusedTrainStep
/ KVStore / DataLoader / block compile cache, chrome-trace export, and
the near-zero-cost disabled contract. Runs on the 8-virtual-device CPU
mesh (conftest)."""
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tm
from mxnet_tpu.gluon.parameter import Parameter
from mxnet_tpu.parallel.data_parallel import FusedTrainStep


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts disabled with an empty registry and leaves the
    process the same way (telemetry state is process-global)."""
    tm.disable()
    tm.reset()
    yield
    tm.disable()
    tm.reset()


# -- metric model ------------------------------------------------------------

def test_counter_semantics():
    tm.enable()
    c = tm.counter("requests", "help text")
    c.labels(route="a").inc()
    c.labels(route="a").inc(2)
    c.labels(route="b").inc(5)
    snap = tm.snapshot()
    assert snap["counters"]["requests{route=a}"] == 3.0
    assert snap["counters"]["requests{route=b}"] == 5.0
    with pytest.raises(ValueError):
        c.labels(route="a").inc(-1)


def test_gauge_semantics():
    tm.enable()
    g = tm.gauge("depth")
    g.labels().set(4)
    g.labels().inc()
    g.labels().dec(2)
    assert tm.snapshot()["gauges"]["depth"] == 3.0


def test_metric_kind_conflict_raises():
    tm.enable()
    tm.counter("x_total")
    with pytest.raises(TypeError):
        tm.gauge("x_total")


def test_histogram_stats_and_percentiles():
    tm.enable()
    h = tm.histogram("lat").labels()
    for v in [1.0] * 50 + [8.0] * 45 + [512.0] * 5:
        h.observe(v)
    st = h.stats()
    assert st["count"] == 100
    assert st["min"] == 1.0 and st["max"] == 512.0
    assert st["mean"] == pytest.approx((50 + 8 * 45 + 512 * 5) / 100)
    # p50 lands in the 1.0 run, p95 in the 8.0 run, p99 in the tail;
    # log2 buckets give geometric interpolation, so assert the bucket
    assert st["p50"] <= 1.0 + 1e-9
    assert 4.0 < st["p95"] <= 8.0
    assert 256.0 < st["p99"] <= 512.0


def test_histogram_exact_power_of_two_lower_bucket():
    tm.enable()
    h = tm.histogram("pow2").labels()
    h.observe(8.0)  # (4, 8] -> exponent bucket 3
    assert h.buckets == {3: 1}


def test_histogram_zero_and_negative():
    tm.enable()
    h = tm.histogram("z").labels()
    h.observe(0.0)
    h.observe(-2.0)
    h.observe(4.0)
    assert h.zeros == 2 and h.count == 3
    assert h.percentile(0.01) == 0.0  # clamped at max(0, min)


def test_labels_order_insensitive():
    tm.enable()
    f = tm.counter("lbl")
    f.labels(a="1", b="2").inc()
    f.labels(b="2", a="1").inc()
    assert tm.snapshot()["counters"]["lbl{a=1,b=2}"] == 2.0


def test_prometheus_exposition():
    tm.enable()
    tm.inc("hits_total", 2, route="x")
    tm.observe("lat_seconds", 0.5)
    text = tm.to_prometheus()
    assert "# TYPE hits_total counter" in text
    assert "hits_total{route=x} 2" in text
    assert "# TYPE lat_seconds histogram" in text
    assert "lat_seconds_bucket{le=0.5} 1" in text
    assert "lat_seconds_count 1" in text


# -- disabled-path contract --------------------------------------------------

def test_disabled_records_nothing():
    assert not tm.enabled()
    tm.inc("nope")
    tm.set_gauge("nope_g", 1)
    tm.observe("nope_h", 1.0)
    tm.mark_phase("forward", 0.1)
    with tm.phase("backward"):
        pass
    tm.step_done(32)
    assert tm.snapshot() == {}
    assert tm.to_prometheus() == ""
    assert len(tm._TRACE_EVENTS) == 0
    assert len(tm._REGISTRY) == 0
    assert tm.breakdown_table() == "telemetry disabled"


def test_disabled_instrumented_step_records_nothing():
    p = Parameter("p0", shape=(4,))
    p.initialize()
    tr = mx.gluon.Trainer({"p0": p}, "sgd", {"learning_rate": 0.1},
                          kvstore="device")
    x = mx.nd.ones((4,))
    with mx.autograd.record():
        loss = (p.data() * x).sum()
    loss.backward()
    tr.step(1)
    assert tm.snapshot() == {}
    assert len(tm._TRACE_EVENTS) == 0


# -- per-step timeline: eager Trainer.step(zero=2) ---------------------------

def _make_params(shapes, seed=0):
    rs = np.random.RandomState(seed)
    params = {}
    for i, s in enumerate(shapes):
        p = Parameter(f"p{i}", shape=s)
        p.initialize()
        p.set_data(rs.randn(*s).astype(np.float32))
        params[f"p{i}"] = p
    return params


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices")
def test_eager_zero2_step_breakdown_and_wire_bytes():
    tm.enable()
    params = _make_params([(4, 8), (8,), (16, 3)])
    kv = mx.kvstore.create("tpu_sync")
    tr = mx.gluon.Trainer(params, "adam", {"learning_rate": 1e-3},
                          kvstore=kv,
                          compression_params={"type": "2bit"}, zero=2)
    x = mx.nd.ones((4,)) * 0.5
    with mx.autograd.record():
        loss = sum((p.data() * p.data()).sum()
                   for p in params.values())
    loss.backward()
    tr.step(4)

    snap = tm.snapshot()
    bd = snap["step_time_breakdown"]
    for phase in ("forward", "backward", "grad_comm", "optimizer",
                  "weight_gather"):
        assert bd.get(phase, {}).get("count", 0) >= 1, phase
        assert bd[phase]["sum"] > 0.0
    assert snap["counters"]["steps_total"] == 1.0

    logical = snap["counters"][
        "comm_bytes_reduced{kind=logical,store=tpu_sync}"]
    wire = snap["counters"][
        "comm_bytes_reduced{kind=wire,store=tpu_sync}"]
    assert logical > 0 and wire > 0
    assert wire < logical  # 2-bit quantization: ~16x smaller
    assert wire <= logical / 8

    assert "resident_bytes" in snap and "total" in snap["resident_bytes"]


def test_kvstore_wire_vs_logical_bytes_direct():
    tm.enable()
    kv = mx.kvstore.create("device")
    kv.set_gradient_compression({"type": "2bit"})
    v = mx.nd.ones((256,))
    kv.init(0, v)
    kv.pushpull(0, mx.nd.ones((256,)), out=v)
    snap = tm.snapshot()
    logical = snap["counters"][
        "comm_bytes_reduced{kind=logical,store=device}"]
    wire = snap["counters"]["comm_bytes_reduced{kind=wire,store=device}"]
    assert logical == 256 * 4
    assert wire == 256 * 2 // 8  # ceil(256 * 2 bits / 8)

    # uncompressed pull direction: wire == logical
    out = mx.nd.zeros((256,))
    kv.pull(0, out=out)
    snap = tm.snapshot()
    assert snap["counters"][
        "comm_bytes_gathered{kind=logical,store=device}"] == \
        snap["counters"]["comm_bytes_gathered{kind=wire,store=device}"]


def test_kvstore_push_counts_uncompressed():
    tm.enable()
    kv = mx.kvstore.create("device")
    kv.init("w", mx.nd.ones((32,)))
    kv.push("w", mx.nd.ones((32,)))
    snap = tm.snapshot()
    assert snap["counters"][
        "comm_bytes_pushed{kind=logical,store=device}"] == 128
    assert snap["counters"][
        "comm_bytes_pushed{kind=wire,store=device}"] == 128


# -- per-step timeline: FusedTrainStep ---------------------------------------

def _fused_step(seed=0):
    net = mx.gluon.nn.Dense(8, in_units=4)
    net.initialize()
    def loss_fn(pred, label):
        return ((pred - label) ** 2).mean()
    opt = mx.optimizer.SGD(learning_rate=0.1)
    return net, FusedTrainStep(net, loss_fn, opt, mesh=None)


def test_fused_step_breakdown_and_speedometer():
    tm.enable()
    net, step = _fused_step()
    x = mx.nd.ones((4, 4))
    y = mx.nd.ones((4, 8))
    step(x, y)
    step(x, y)
    snap = tm.snapshot()
    bd = snap["step_time_breakdown"]
    assert bd.get("data", {}).get("count", 0) >= 2
    assert bd.get("fused_step", {}).get("count", 0) == 2
    assert snap["counters"]["steps_total"] == 2.0
    assert snap["samples_per_sec"] > 0.0


def test_compile_stats_in_snapshot():
    tm.enable()
    mx.tracing.reset_cache_stats()
    net = mx.gluon.nn.Dense(3, in_units=2)
    net.initialize()
    net.hybridize()
    x = mx.nd.ones((2, 2))
    net(x)        # fresh -> compile
    net(x)        # cache hit
    snap = tm.snapshot()
    comp = snap["compile"]
    assert comp["compiles"] == 1 and comp["hits"] == 1
    assert comp["compile_seconds"] > 0.0
    assert comp["hit_rate"] == 0.5  # backward-compatible key
    per = comp["per_block"]
    assert per["dense"]["compiles"] == 1
    assert per["dense"]["hits"] == 1
    assert per["dense"]["compile_seconds"] > 0.0
    assert snap["counters"]["compiles_total{block=dense}"] == 1.0
    assert snap["histograms"][
        "compile_seconds{block=dense}"]["count"] == 1


def test_cache_stats_backward_compatible_shape():
    mx.tracing.reset_cache_stats()
    st = mx.tracing.cache_stats()
    # the pre-telemetry keys keep their exact names and types
    assert st["compiles"] == 0 and st["hits"] == 0
    assert st["hit_rate"] == 0.0
    assert st["per_block"] == {}


# -- chrome-trace export -----------------------------------------------------

def test_export_chrome_trace_host_and_device_pids(tmp_path):
    tm.enable()
    net, step = _fused_step()
    x = mx.nd.ones((4, 4))
    y = mx.nd.ones((4, 8))
    step(x, y)
    p = tmp_path / "trace.json"
    tm.export_chrome_trace(str(p))
    blob = json.loads(p.read_text())
    evs = blob["traceEvents"]
    xpids = {e["pid"] for e in evs if e.get("ph") == "X"}
    assert tm.HOST_PID in xpids     # host phase events
    assert tm.DEVICE_PID in xpids   # sync-measured device span
    names = {e["name"] for e in evs if e.get("ph") == "X"}
    assert "fused_step" in names and "data" in names


def test_phase_events_per_step():
    tm.enable()
    params = _make_params([(4,)])
    tr = mx.gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                          kvstore="device")
    for _ in range(3):
        with mx.autograd.record():
            loss = (params["p0"].data() ** 2).sum()
        loss.backward()
        tr.step(1)
    # >= one host phase event per step in the trace buffer
    host_events = [e for e in tm._TRACE_EVENTS
                   if e["pid"] == tm.HOST_PID]
    assert len(host_events) >= 3


# -- dataloader metrics ------------------------------------------------------

def test_dataloader_queue_and_wait_metrics():
    tm.enable()
    data = mx.gluon.data.ArrayDataset(
        mx.nd.array(np.arange(32, dtype=np.float32).reshape(16, 2)),
        mx.nd.array(np.arange(16, dtype=np.float32)))
    loader = mx.gluon.data.DataLoader(data, batch_size=4, num_workers=2)
    n = sum(1 for _ in loader)
    assert n == 4
    snap = tm.snapshot()
    assert snap["step_time_breakdown"]["data"]["count"] == 4
    assert snap["histograms"][
        "dataloader_worker_wait_seconds"]["count"] == 4
    assert "dataloader_queue_depth" in snap["gauges"]


def test_dataloader_serial_data_phase():
    tm.enable()
    data = mx.gluon.data.ArrayDataset(
        mx.nd.array(np.ones((8, 2), dtype=np.float32)),
        mx.nd.array(np.ones(8, dtype=np.float32)))
    loader = mx.gluon.data.DataLoader(data, batch_size=2, num_workers=0)
    assert sum(1 for _ in loader) == 4
    assert tm.snapshot()["step_time_breakdown"]["data"]["count"] == 4


# -- speedometer / dump ------------------------------------------------------

def test_step_done_speedometer():
    tm.enable()
    for _ in range(4):
        tm.step_done(16)
    snap = tm.snapshot()
    assert snap["counters"]["steps_total"] == 4.0
    assert snap["samples_per_sec"] > 0.0


def test_dump_json_roundtrip(tmp_path):
    tm.enable()
    tm.inc("c", 3)
    p = tmp_path / "snap.json"
    out = tm.dump_json(str(p))
    assert out == str(p)
    blob = json.loads(p.read_text())
    assert blob["counters"]["c"] == 3.0
    # no path -> the JSON string itself
    blob2 = json.loads(tm.dump_json())
    assert blob2["counters"]["c"] == 3.0


def test_breakdown_table_renders():
    tm.enable()
    tm.mark_phase("forward", 0.002)
    tm.mark_phase("optimizer", 0.001)
    tm.step_done(8)
    tm.step_done(8)
    table = tm.breakdown_table()
    assert "forward" in table and "optimizer" in table
    assert "p95_ms" in table


def test_reset_clears_registry_keeps_enabled():
    tm.enable()
    tm.inc("c")
    tm.mark_phase("forward", 0.001)
    tm.reset()
    assert tm.enabled()
    assert tm.snapshot()["counters"] == {}
    assert len(tm._TRACE_EVENTS) == 0


# -- satellite: profiler.dump fix --------------------------------------------

def test_profiler_dump_honors_config_and_finished(tmp_path):
    prof = mx.profiler
    fname = str(tmp_path / "profile.json")
    prof.set_config(filename=fname, aggregate_stats=True)
    prof.set_state("run")
    with prof.scope("work"):
        pass
    out = prof.dump(finished=False)
    blob = json.loads(open(out).read())
    assert blob["traceEvents"], "scope event missing"
    assert blob["aggregateStats"]["work"]["calls"] == 1
    assert "residentBytes" in blob
    # finished=False left the session running + events intact
    assert prof._STATE["running"] and prof._EVENTS

    prof.dump(finished=True)
    assert not prof._STATE["running"]
    # collected data survives the dump (dumps(reset=True) clears it)
    assert "work" in prof.dumps(reset=True)
    assert not prof._EVENTS and not prof._AGG

    prof.set_config(filename="profile.json",
                    aggregate_stats=True)  # restore default


def test_profiler_dump_without_aggregate(tmp_path):
    prof = mx.profiler
    fname = str(tmp_path / "p.json")
    prof.set_config(filename=fname, aggregate_stats=False)
    try:
        prof.set_state("run")
        with prof.scope("s"):
            pass
        blob = json.loads(open(prof.dump()).read())
        assert "aggregateStats" not in blob
        assert "residentBytes" not in blob
    finally:
        prof.set_config(filename="profile.json", aggregate_stats=True)
        prof.set_state("stop")
        prof._EVENTS.clear()
        prof._AGG.clear()


def test_profiler_scope_feeds_telemetry():
    tm.enable()
    prof = mx.profiler
    prof.set_state("run")
    try:
        with prof.scope("hot"):
            pass
    finally:
        prof.set_state("stop")
        prof._EVENTS.clear()
        prof._AGG.clear()
    snap = tm.snapshot()
    assert snap["histograms"]["profiler_scope_seconds{scope=hot}"][
        "count"] == 1


# -- satellite: Monitor weight/grad stats ------------------------------------

def test_monitor_records_weight_and_grad_stats():
    net = mx.gluon.nn.Dense(4, in_units=3)
    net.initialize()
    mon = mx.monitor.Monitor(1).install(net)
    x = mx.nd.ones((2, 3))
    mon.tic()
    with mx.autograd.record():
        out = net(x)
        loss = out.sum()
    loss.backward()
    recs = dict(mon.toc())
    kinds = {k.rsplit("_", 1)[-1] for k in recs}
    assert "weight" in kinds, recs
    assert "grad" in kinds, recs
    weight_keys = [k for k in recs if k.endswith("_weight")]
    assert any("weight" in k or "bias" in k for k in weight_keys)
    # activations still recorded (pre-existing behavior)
    assert any(k.endswith("_output0") for k in recs)


def test_monitor_pattern_filters_params():
    net = mx.gluon.nn.Dense(4, in_units=3)
    net.initialize()
    mon = mx.monitor.Monitor(1, pattern=".*bias.*").install(net)
    mon.tic()
    net(mx.nd.ones((2, 3)))
    recs = dict(mon.toc())
    assert all("bias" in k for k in recs), recs


# -- satellite: Estimator TelemetryHandler -----------------------------------

def test_telemetry_handler_logs_breakdown():
    from mxnet_tpu.gluon.estimator import TelemetryHandler
    tm.enable()
    tm.mark_phase("forward", 0.001)
    lines = []
    h = TelemetryHandler(interval=2, printer=lines.append)

    class _Est:
        global_batch = 0
    est = _Est()
    h.train_begin(est)
    for b in range(1, 5):
        est.global_batch = b
        h.batch_end(est)
    assert len(lines) == 2  # batches 2 and 4
    assert "forward" in lines[0]
    h.train_end(est)
    assert "final" in lines[-1]


def test_telemetry_handler_silent_when_disabled():
    from mxnet_tpu.gluon.estimator import TelemetryHandler
    lines = []
    h = TelemetryHandler(interval=1, printer=lines.append)

    class _Est:
        global_batch = 1
    h.train_begin(_Est())
    h.batch_end(_Est())
    h.train_end(_Est())
    assert lines == []


# -- K-step flush speedometer / /metrics endpoint (ISSUE 8) ------------------

def test_step_done_k_step_flush():
    """One run_steps(K) flush counts K steps and K*batch samples — the
    speedometer must not under-report by K when the host only regains
    control at window boundaries."""
    tm.enable()
    import time
    tm.step_done(samples=32, steps=4)
    time.sleep(0.01)
    tm.step_done(samples=32, steps=4)
    snap = tm.snapshot()
    assert snap["counters"]["steps_total"] == 8.0
    assert snap["samples_per_sec"] > 0.0


def test_metrics_server_serves_prometheus():
    import urllib.request
    tm.enable()
    tm.inc("steps_total", 5)
    srv = tm.start_metrics_server()
    try:
        assert srv.port > 0
        body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
        assert "steps_total 5" in body
        hz = urllib.request.urlopen(
            srv.url.replace("/metrics", "/healthz"), timeout=5).read()
        rep = json.loads(hz)
        assert rep["ok"] is True and rep["reason"] == "ok"
        assert isinstance(rep["sources"], list)
        with pytest.raises(Exception):
            urllib.request.urlopen(
                srv.url.replace("/metrics", "/nope"), timeout=5)
        assert tm.start_metrics_server() is srv  # idempotent singleton
    finally:
        tm.stop_metrics_server()
    with pytest.raises(Exception):
        urllib.request.urlopen(srv.url, timeout=2)  # actually closed


def test_metrics_server_env_gate(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_METRICS_PORT", raising=False)
    assert tm.maybe_start_metrics_server() is None  # opt-in: default off
    monkeypatch.setenv("MXNET_TPU_METRICS_PORT", "0")
    srv = tm.maybe_start_metrics_server()
    try:
        assert srv is not None and srv.port > 0
        assert tm._ENABLED  # the env gate also enables collection
    finally:
        tm.stop_metrics_server()


def test_metrics_server_live_counters():
    """The endpoint reflects counters incremented after startup — it
    snapshots per scrape, not at server start."""
    import urllib.request
    tm.enable()
    srv = tm.start_metrics_server()
    try:
        tm.inc("train_loop_dispatches_total")
        tm.set_gauge("train_loop_k", 8)
        body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
        assert "train_loop_dispatches_total 1" in body
        assert "train_loop_k 8" in body
    finally:
        tm.stop_metrics_server()


# -- cross-process aggregation + health (ISSUE 10) --------------------------

def test_registry_state_roundtrip_and_merge():
    """_registry_state serializes the full registry; merging the same
    blob for two fake processes sums counters, merges histogram
    buckets, and splits gauges under proc labels."""
    import json
    tm.enable()
    tm.inc("steps_total", 3)
    tm.inc("comm_bytes_reduced", 128, store="device")
    tm.set_gauge("queue_depth", 7)
    for v in (0.5, 1.5, 0.0):
        tm.observe("tick_seconds", v)
    state = json.loads(json.dumps(tm._registry_state()))  # wire trip
    merged = tm._merge_registry({0: state, 1: state})
    flat = {}
    for fam in merged.values():
        for key, ch in fam.children.items():
            flat[fam.name + tm._label_suffix(key)] = ch
    assert flat["steps_total"].value == 6.0
    assert flat["comm_bytes_reduced{store=device}"].value == 256.0
    # gauges: one child per process, no unlabeled child
    assert flat["queue_depth{proc=0}"].value == 7.0
    assert flat["queue_depth{proc=1}"].value == 7.0
    assert "queue_depth" not in flat
    h = flat["tick_seconds"]
    assert h.count == 6 and h.sum == 4.0 and h.zeros == 2
    assert h.min == 0.0 and h.max == 1.5


def test_aggregate_snapshot_single_process():
    tm.enable()
    tm.inc("steps_total", 2)
    tm.set_gauge("train_loop_k", 8)
    agg = tm.aggregate_snapshot()
    assert agg["processes"] == [0]
    assert agg["counters"]["steps_total"] == 2.0
    assert agg["gauges"]["train_loop_k{proc=0}"] == 8.0
    tm.disable()
    assert tm.aggregate_snapshot() == {}


def test_publish_snapshot_noop_single_process():
    tm.enable()
    tm.inc("steps_total")
    assert tm.publish_snapshot() is False   # nothing to coordinate with
    tm.disable()
    assert tm.publish_snapshot() is False


def test_to_prometheus_merged_proc_labels():
    tm.enable()
    tm.inc("steps_total", 4)
    tm.set_gauge("step_time_seconds", 0.25)
    body = tm.to_prometheus_merged()
    assert "steps_total 4" in body
    assert 'step_time_seconds{proc=0} 0.25' in body
    tm.disable()
    assert tm.to_prometheus_merged() == ""


def test_step_time_skew_single_process():
    tm.enable()
    assert tm.step_time_skew() == 0.0       # nothing published yet
    tm.publish_step_time(0.125)
    assert tm.step_times() == {0: 0.125}
    assert tm.step_time_skew() == 1.0       # one proc: max == median
    assert tm.snapshot()["gauges"]["step_time_skew_ratio"] == 1.0
    assert tm.stragglers() == []            # needs >= 2 contributors
    tm.disable()
    assert tm.step_times() == {} and tm.stragglers() == []


def test_metrics_server_honors_host_env(monkeypatch):
    tm.enable()
    monkeypatch.setenv("MXNET_TPU_METRICS_HOST", "0.0.0.0")
    srv = tm.start_metrics_server()
    try:
        assert srv.host == "0.0.0.0"
    finally:
        tm.stop_metrics_server()
    # explicit host beats the env
    srv = tm.start_metrics_server(host="127.0.0.1")
    try:
        assert srv.host == "127.0.0.1"
    finally:
        tm.stop_metrics_server()


def test_metrics_server_default_is_loopback(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_METRICS_HOST", raising=False)
    tm.enable()
    srv = tm.start_metrics_server()
    try:
        assert srv.host == "127.0.0.1"
    finally:
        tm.stop_metrics_server()


class _StubHealth:
    def __init__(self):
        self.ok = True
        self.reason = "ok"

    def health(self):
        return self.ok, self.reason


def test_health_aggregates_sources():
    stub = _StubHealth()
    tm.register_health_source(stub)
    try:
        assert tm.health() == (True, "ok")
        stub.ok, stub.reason = False, "draining: admission stopped"
        ok, reason = tm.health()
        assert not ok and reason == "draining: admission stopped"
    finally:
        tm.unregister_health_source(stub)
    assert tm.health() == (True, "ok")


def test_health_source_weakref_drops():
    import gc
    stub = _StubHealth()
    stub.ok = False
    tm.register_health_source(stub)
    assert tm.health()[0] is False
    del stub
    gc.collect()
    assert tm.health() == (True, "ok")


def test_healthz_endpoint_503(monkeypatch):
    import urllib.request
    import urllib.error
    tm.enable()
    stub = _StubHealth()
    stub.ok, stub.reason = False, "stalled: watchdog"
    tm.register_health_source(stub)
    srv = tm.start_metrics_server()
    try:
        hz = srv.url.replace("/metrics", "/healthz")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(hz, timeout=5)
        assert ei.value.code == 503
        assert b"stalled: watchdog" in ei.value.read()
        stub.ok = True
        rep = json.loads(urllib.request.urlopen(hz, timeout=5).read())
        assert rep["ok"] is True
        # the bare-health stub has no health_detail(): its (ok, reason)
        # pair still shows up as a structured source entry
        assert any(s.get("reason") for s in rep["sources"])
    finally:
        tm.stop_metrics_server()
        tm.unregister_health_source(stub)


# -- fleet observability primitives (ISSUE 14) -------------------------------

def test_read_gauge_and_remove_series():
    tm.enable()
    tm.set_gauge("router_replica_health", 0, replica="w0")
    tm.set_gauge("router_replica_health", 2, replica="w1")
    assert tm.read_gauge("router_replica_health", replica="w0") == 0.0
    assert tm.read_gauge("router_replica_health", replica="w1") == 2.0
    # absent child / family / wrong kind -> default, never created
    assert tm.read_gauge("router_replica_health", replica="nope") is None
    assert tm.read_gauge("no_such_gauge", default=-1.0) == -1.0
    tm.inc("a_counter")
    assert tm.read_gauge("a_counter", default="x") == "x"
    fam = tm._REGISTRY["router_replica_health"]
    assert len(fam.children) == 2   # read_gauge created nothing

    assert tm.remove_series("router_replica_health", replica="w0")
    assert not tm.remove_series("router_replica_health", replica="w0")
    assert not tm.remove_series("no_such_gauge", replica="w0")
    assert tm.read_gauge("router_replica_health", replica="w0") is None
    assert tm.read_gauge("router_replica_health", replica="w1") == 2.0
    # the family survives for the remaining children
    assert "router_replica_health{replica=w1}" \
        in tm.snapshot()["gauges"]


def test_registry_delta_encodes_changes_and_tombstones():
    tm.enable()
    tm.inc("steps_total", 3)
    tm.set_gauge("queue_depth", 7)
    delta, acked = tm.registry_delta(None)
    assert set(delta) == {"steps_total", "queue_depth"}
    assert delta == {k: acked[k] for k in delta}
    # no change: empty delta, acked unchanged
    d2, a2 = tm.registry_delta(acked)
    assert d2 == {} and a2 == acked
    # one family changes: only it ships
    tm.inc("steps_total")
    d3, a3 = tm.registry_delta(a2)
    assert set(d3) == {"steps_total"}
    # reset: vanished families ship as None tombstones
    tm.reset()
    d4, a4 = tm.registry_delta(a3)
    assert d4 == {"steps_total": None, "queue_depth": None}
    assert a4 == {}


def test_registry_delta_defers_over_budget_families():
    tm.enable()
    tm.inc("tiny_total")
    h = tm.histogram("big_histogram").labels()
    for i in range(64):
        h.observe(2.0 ** (i % 40))
    small = len(json.dumps({"tiny_total": tm._registry_state()
                            ["tiny_total"]}))
    delta, acked = tm.registry_delta(None, max_bytes=small + 4)
    # the first family always ships; the big one is deferred, stays
    # un-acked, and arrives on the next (unbounded) beat
    assert len(delta) >= 1
    deferred = {"tiny_total", "big_histogram"} - set(delta)
    assert deferred and not (deferred & set(acked))
    d2, a2 = tm.registry_delta(acked)
    assert deferred <= set(d2)
    assert set(a2) == {"tiny_total", "big_histogram"}
    # absolute states: re-applying the same delta is idempotent
    merged1 = tm._merge_registry({0: dict(a2)})
    merged2 = tm._merge_registry({0: dict(a2)})
    for name in ("tiny_total", "big_histogram"):
        c1 = list(merged1[name].children.values())[0]
        c2 = list(merged2[name].children.values())[0]
        if name == "tiny_total":
            assert c1.value == c2.value == 1.0
        else:
            assert c1.count == c2.count == 64


def test_merge_registry_replica_label():
    tm.enable()
    tm.set_gauge("serving_active_slots", 3)
    state = json.loads(json.dumps(tm._registry_state()))
    merged = tm._merge_registry({"w0": state, "w1": state},
                                label="replica")
    fam = merged["serving_active_slots"]
    keys = set(fam.children)
    assert (("replica", "w0"),) in keys
    assert (("replica", "w1"),) in keys


def test_export_chrome_trace_deterministic_bytes(tmp_path):
    """Same recorded spans -> byte-identical JSON, including a fleet
    trace source: the chrome-trace diffing workflow (and the repo's
    own merge-determinism tests) depend on it."""

    class _Src:
        def fleet_traces(self):
            return [{"request_id": 7, "events": [
                {"name": "queued", "t": 10.0, "src": "router",
                 "dur_s": 0.5},
                {"name": "attempt 0", "t": 10.5, "src": "router",
                 "dur_s": 1.0, "replica": "w0", "outcome": "won"},
                {"name": "prefill", "t": 10.6, "src": "w0",
                 "dur_s": 0.2},
                {"name": "decode", "t": 10.8, "src": "w0",
                 "dur_s": 0.7}]}]

    tm.enable()
    src = _Src()
    tm.register_fleet_trace_source(src)
    tm.mark_phase("forward", 0.001, t0=1.0)
    tm.mark_phase("backward", 0.002, t0=1.001)
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    tm.export_chrome_trace(str(p1))
    tm.export_chrome_trace(str(p2))
    b1, b2 = p1.read_bytes(), p2.read_bytes()
    assert b1 == b2
    evs = json.loads(b1)["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert {tm.HOST_PID, tm.ROUTER_PID, tm.REPLICA_PID_BASE} <= pids
    procs = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"fleet: router", "fleet: replica w0"} <= procs
    # spans are ordered deterministically: metadata first, then by
    # (pid, ts) -- a second export after re-registering in a different
    # order still matches
    tm._FLEET_TRACE_SOURCES.clear()
    tm.register_fleet_trace_source(src)
    p3 = tmp_path / "c.json"
    tm.export_chrome_trace(str(p3))
    assert p3.read_bytes() == b1
