"""The reductions over the program's own spans: on a synthetic
timeline, on a trace of a tiny server recorded here on the CPU (spans
and counts, no device), and on a small trace recorded on the chip,
``perfbench/tests/data/tiny_mx_v5e.xplane.pb``: three ticks of a
one-layer ``InferenceServer`` (one prefill) and three fused train steps
under ``pb.window``, the device's operations beside the ``mx.*`` spans.
The profiler wrote 709 KB; the file was then cut to what the reduction
reads (the ``/host:metadata`` plane with the HLO protos, the device's
other lines, the per-op statistics and the HLO text after `` = `` in
each operation's name are gone, where ``xtrace.base_name`` cuts it
too). No start and no duration was touched."""
import importlib
import os

import pytest

from perfbench import harness, mxspans, xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_mx_v5e.xplane.pb")
TICK = "mx.serve_tick"
PARTS = ["mx.serve_dispatch", "mx.serve_wait", "mx.serve_emit"]


class Ctx:
    """What a reader looks at, for a trace loaded by hand."""

    def __init__(self, trace, spans, counters=None):
        self.trace = trace
        self._mxspans = spans
        self.counters = counters or {}


def metric(name, ctx):
    """A per-layer metric of ``BENCHMARK.json`` by its own file."""
    spec = harness.load_json(harness.HERE, "metrics", name + ".json")
    return importlib.import_module(
        "perfbench.readers." + spec["reader"]).read(spec, ctx)


# -- a synthetic timeline ----------------------------------------------------
#
#  window 0 .. 1000 (pb.window); device busy 100-300, 520-700, 990-1000
#  tick A 50-400:  admit 60-120 (prefill 70-90, prefill 95-115),
#                  blocks 122-128, decode 130-320 = dispatch 130-160 + wait 160-320,
#                  emit 320-400
#  tick B 500-800: admit 505-510, blocks 511-513, decode 515-720 = dispatch 515-530
#                  + wait 530-720, emit 720-800
#  tick C 950-1100: cut by the window's end (dispatch 960-1100)

def synthetic():
    ops = [("fusion.1", 100, 200), ("fusion.2", 520, 180),
           ("fusion.3", 990, 50)]
    mods = [("jit_counted_serving_prefill(1)", 100, 20),
            ("jit_counted_serving_prefill(1)", 120, 20),
            ("jit_counted_serving_decode(2)", 140, 160),
            ("jit_counted_serving_decode(2)", 520, 180),
            ("jit_counted_serving_decode(2)", 990, 50)]
    trace = xtrace.Trace({0: ops}, {0: mods},
                         [("pb.window", 0, 1000)]).windowed()
    thread = [
        (TICK, 50, 350, {}),
        ("mx.serve_admit", 60, 60, {}),
        ("mx.serve_prefill", 70, 20, {"tokens": 5, "padded": 8}),
        ("mx.serve_prefill", 95, 20, {"tokens": 7, "padded": 8}),
        ("mx.serve_blocks", 122, 6, {}),
        ("mx.serve_decode", 130, 190, {}),
        ("mx.serve_dispatch", 130, 30, {"active": 2}),
        ("mx.serve_wait", 160, 160, {}),
        ("mx.serve_emit", 320, 80, {}),
        (TICK, 500, 300, {}),
        ("mx.serve_admit", 505, 5, {}),
        ("mx.serve_blocks", 511, 2, {}),
        ("mx.serve_decode", 515, 205, {}),
        ("mx.serve_dispatch", 515, 15, {"active": 1}),
        ("mx.serve_wait", 530, 190, {}),
        ("mx.serve_emit", 720, 80, {}),
        (TICK, 950, 150, {}),
        ("mx.serve_dispatch", 960, 140, {"active": 1}),
    ]
    other = [("mx.data", 10, 5, {})]     # another thread's span
    return trace, mxspans.build([thread, other], trace)


def test_nesting_is_rebuilt_by_containment():
    _, sp = synthetic()
    ticks = sp.named(TICK)
    assert [t.whole for t in ticks] == [True, True, False]
    assert (ticks[2].start, ticks[2].end) == (950, 1000)
    assert [c.name for c in ticks[0].children] == [
        "mx.serve_admit", "mx.serve_blocks", "mx.serve_decode",
        "mx.serve_emit"]
    assert [c.name for c in ticks[0].children[2].children] == [
        "mx.serve_dispatch", "mx.serve_wait"]
    assert len(sp.inside(ticks[0], "mx.serve_prefill")) == 2
    assert sp.inside(ticks[1], "mx.serve_prefill") == []
    # the other thread's span belongs to no tick
    assert all(c.name != "mx.data" for t in ticks
               for c in t.descendants())


def test_self_time_is_the_span_less_named_children():
    _, sp = synthetic()
    a, b, _ = sp.named(TICK)
    assert sp.self_ns(a) == 350
    assert sp.self_ns(a, ["mx.serve_wait"]) == 350 - 160
    assert sp.self_ns(a, ["mx.serve_decode", "mx.serve_wait"]) == 350 - 190
    assert sp.self_ns(b, ["mx.serve_wait"]) == 300 - 190


def test_idle_inside_spans_and_outside_every_tick():
    _, sp = synthetic()
    # idle: 0-100, 300-520, 700-990
    assert sp.idle == [[0, 100], [300, 520], [700, 990]]
    assert sp.idle_ns(sp.cover("mx.serve_dispatch")) == 0 + 5 + 30
    assert sp.idle_ns(sp.cover("mx.serve_wait")) == 20 + 20
    assert sp.idle_ns(sp.cover("mx.serve_emit")) == 80 + 80
    assert sp.idle_ns(sp.cover(TICK, PARTS)) == 50 + 15 + 10
    assert sp.idle_ns(sp.outside(TICK)) == 50 + 100 + 150


def test_the_five_idle_parts_add_up_to_the_windows_idle():
    trace, sp = synthetic()
    ctx = Ctx(trace, sp)
    parts = [metric(f"idle_ms_per_tick.{p}.tpot", ctx)
             for p in ("admit", "dispatch", "wait", "emit", "caller")]
    ticks = len(sp.named(TICK))
    idle_s = (1.0 - trace.busy_s() / 1000e-9) * 1000e-9
    assert sum(parts) * ticks * 1e-3 == pytest.approx(idle_s, rel=1e-9)
    assert parts[-1] == pytest.approx(300e-6 / 3)       # ms a tick


def test_count_readers_on_the_synthetic_timeline():
    trace, sp = synthetic()
    ctx = Ctx(trace, sp, {"slots": 2})
    assert sp.count_mean("mx.serve_dispatch", "active") \
        == pytest.approx(4 / 3)
    assert metric("slots_active_share.serve", ctx) \
        == pytest.approx(100 * 4 / 3 / 2)
    # one admit holds two prefills, the other none: only the first
    # counts
    assert metric("prefills_per_admit_p90", ctx) == 2.0
    assert metric("modules_per_tick.tpot", ctx) == pytest.approx(5 / 3)
    # whole ticks only: A 350 - 160, B 300 - 190 ns
    assert metric("tick_host_ms_p50.serve", ctx) \
        == pytest.approx((190 + 110) / 2 * 1e-6)
    # blocks: 6 and 2 ns; 12 prompt tokens in 16 padded positions
    assert metric("blocks_host_ms_p50.tpot", ctx) \
        == pytest.approx((6 + 2) / 2 * 1e-6)
    assert metric("prefill_padding_share", ctx) == pytest.approx(25.0)


def test_a_count_shortfall_skips_spans_that_lack_either_count():
    trace, _ = synthetic()
    sp = mxspans.build([[("mx.serve_prefill", 10, 5, {"tokens": 3}),
                         ("mx.serve_prefill", 20, 5, {"tokens": 6,
                                                      "padded": 8}),
                         ("mx.serve_prefill", 30, 5, {})]], trace)
    ctx = Ctx(trace, sp)
    assert metric("prefill_padding_share", ctx) == pytest.approx(25.0)
    none = mxspans.build([[("mx.serve_prefill", 10, 5, {"tokens": 3})]],
                         trace)
    assert metric("prefill_padding_share", Ctx(trace, none)) is None


def reads_the_programs_spans(name):
    """Whether the metric's reader takes anything from ``mxspans``: a
    reader that imports the module is one that does."""
    spec = harness.load_json(harness.HERE, "metrics", name + ".json")
    reader = importlib.import_module("perfbench.readers." + spec["reader"])
    return getattr(reader, "mxspans", None) is mxspans


def test_a_program_without_the_spans_reads_as_nothing():
    trace, _ = synthetic()
    ctx = Ctx(trace, mxspans.build([], trace), {"slots": 2})
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    names = [m["name"] for m in bm["per_layer"]
             if reads_the_programs_spans(m["name"])]
    # whatever the list has grown to, it holds a metric of every kind
    # of span reader and none of the host clock's or the device's own
    assert {"tick_host_ms_p50.serve", "idle_ms_per_tick.wait.tpot",
            "modules_per_step.train", "prefill_padding_share",
            "moe_experts_roofline.afmoe",
            "ssm_state_update_roofline.jamba"} <= set(names)
    assert not {"gen_late_p99_ms", "device_idle_share.serve",
                "step_ms_p50", "mfu.train"} & set(names)
    for name in names:
        assert metric(name, ctx) is None, name


def test_no_device_in_the_trace_gives_no_idle_time():
    trace = xtrace.Trace({}, {}, [("pb.window", 0, 100)]).windowed()
    sp = mxspans.build([[(TICK, 10, 50, {})]], trace)
    assert sp.idle is None
    ctx = Ctx(trace, sp)
    assert metric("idle_ms_per_tick.wait.serve", ctx) is None
    assert metric("modules_per_tick.serve", ctx) is None
    assert metric("tick_host_ms_p50.serve", ctx) == pytest.approx(50e-6)


# -- a tiny server traced here, on the CPU -----------------------------------

def test_spans_and_counts_of_a_tiny_server_traced_on_the_cpu(tmp_path):
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.serving import InferenceServer

    mx.random.seed(0)
    net = mx.models.get_model("llama_tiny")
    net.initialize()
    net(mx.nd.array(np.zeros((1, 4)), dtype="int32"))
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    server.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    server.run()                                    # warm
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("pb.window"):
        for n in (3, 6):
            server.submit(np.arange(n, dtype=np.int32), max_new_tokens=4)
        ticks = 0
        while server.queue or server._active.any():
            server.step()
            ticks += 1
    jax.profiler.stop_trace()
    path = xtrace.find_xplane(str(tmp_path))
    trace = xtrace.load(path).windowed()
    sp = mxspans.build(mxspans.read_threads(path), trace)
    assert len(sp.named(TICK)) == ticks
    ctx = Ctx(trace, sp, {"slots": 2})
    assert metric("slots_active_share.tpot", ctx) == pytest.approx(100.0)
    assert metric("prefills_per_admit_p90", ctx) == 2.0
    assert 0 < metric("tick_host_ms_p50.tpot", ctx) < 1000
    prefill = sp.named("mx.serve_prefill")
    assert sorted(p.counts["tokens"] for p in prefill) == [3, 6]
    assert metric("prefill_padding_share", ctx) \
        == pytest.approx(100 * (1 - 9 / 16))
    assert 0 < metric("blocks_host_ms_p50.tpot", ctx) \
        < metric("tick_host_ms_p50.tpot", ctx)
    for t in sp.named(TICK):
        assert sp.self_ns(t, PARTS + ["mx.serve_admit", "mx.serve_blocks",
                                      "mx.serve_decode"]) < 0.5 * t.ns


# -- the trace recorded on the chip ------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    if not os.path.exists(DATA):
        pytest.skip("no recorded trace in perfbench/tests/data")
    trace = xtrace.load(DATA).windowed()
    return trace, mxspans.build(mxspans.read_threads(DATA), trace)


def test_recorded_trace_holds_spans_and_device_on_one_clock(tiny):
    trace, sp = tiny
    assert trace.devices == [0]
    ticks = sp.named(TICK)
    assert len(ticks) >= 3 and sp.named("mx.train_step")
    for t in ticks:
        # the decode executable of a tick starts while the host is in
        # that tick: the two clocks are one
        kids = [c.name for c in t.children]
        assert kids[0] == "mx.serve_admit" and kids[-1] == "mx.serve_emit"
    # the program gives no count that no metric reads
    assert {k for s in sp.spans for k in s.counts} \
        == {"tokens", "padded", "active"}
    starts = sorted(s for n, s, _ in trace.device_modules[0]
                    if "serving_decode" in n)
    assert starts
    for s in starts:
        assert any(t.start <= s < t.end for t in ticks)


def test_recorded_trace_conserves_idle_time(tiny):
    trace, sp = tiny
    ctx = Ctx(trace, sp, {"slots": 2})
    t0, t1 = trace.window()
    parts = [metric(f"idle_ms_per_tick.{p}.serve", ctx)
             for p in ("admit", "dispatch", "wait", "emit", "caller")]
    assert all(p is not None and p >= 0 for p in parts)
    idle_s = (t1 - t0) * 1e-9 - trace.busy_s()
    assert sum(parts) * len(sp.named(TICK)) * 1e-3 \
        == pytest.approx(idle_s, rel=1e-6)
    # a tiny model: the host, not the device, owns the tick
    assert metric("modules_per_tick.serve", ctx) >= 1
    assert 0 < metric("slots_active_share.serve", ctx) <= 100
    assert 0 < metric("dispatch_host_ms_p50.train", ctx) \
        < metric("step_host_ms_p50.train", ctx)
    assert 0 < metric("blocks_host_ms_p50.serve", ctx) \
        < metric("tick_host_ms_p50.serve", ctx)
    assert 0 <= metric("prefill_padding_share", ctx) < 100
    assert metric("modules_per_step.train", ctx) >= 1
