"""The program's own spans on the profiler's clock: every
`telemetry.phase` / `telemetry.span` is a `jax.profiler.TraceAnnotation`
named `mx.<name>`, written into the `.xplane.pb` of whatever profiler
session is open (the CPU backend's too), its counts as the event's
stats. Checks the span tables of the serve tick and the train step,
the off path (no session, telemetry disabled) and the enabled path
(histogram, goodput hook, chrome event unchanged)."""
import glob
import os
import time

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import faults, telemetry as tm
from mxnet_tpu.parallel.data_parallel import FusedTrainStep
from mxnet_tpu.serving import InferenceServer
from mxnet_tpu.serving import executables as exe


@pytest.fixture(autouse=True)
def _telemetry_off():
    tm.disable()
    tm.reset()
    yield
    tm.disable()
    tm.reset()


class Span:
    def __init__(self, ev):
        self.name = ev.name
        self.start = int(ev.start_ns)
        self.end = int(ev.start_ns) + int(ev.duration_ns)
        self.stats = dict(ev.stats)

    def holds(self, other):
        return other is not self and self.start <= other.start \
            and other.end <= self.end


def record(body, logdir):
    """Run `body()` under a profiler session; the `mx.*` events of the
    calling thread's line, in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(logdir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            mine = [Span(e) for e in line.events
                    if e.name.startswith("mx.")]
            if mine:
                assert not spans, "mx.* spans on two host threads"
                spans = mine
    return sorted(spans, key=lambda s: (s.start, -s.end))


def named(spans, name):
    return [s for s in spans if s.name == name]


def children(spans, parent):
    """Spans directly inside `parent` (no other span between)."""
    inside = [s for s in spans if parent.holds(s)]
    return [s for s in inside
            if not any(o.holds(s) for o in inside)]


# -- the serve tick ----------------------------------------------------------

@pytest.fixture(scope="module")
def net():
    mx.random.seed(0)
    n = mx.models.get_model("llama_tiny")
    n.initialize()
    n(mx.nd.array(np.zeros((1, 4)), dtype="int32"))  # materialize
    return n


PROMPTS = (5, 3, 7)


@pytest.fixture(scope="module")
def serve_trace(net, tmp_path_factory):
    """Three requests through a two-slot server: the third waits in
    the queue for a slot. Also keeps what the server itself counted."""
    rs = np.random.RandomState(7)
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    reqs = []
    seen = []   # (queued, active) at the entry of each tick

    def body():
        for n in PROMPTS:
            reqs.append(server.submit(
                rs.randint(0, 256, n).astype(np.int32),
                max_new_tokens=4))
        while server.queue or server._active.any():
            queued, active = len(server.queue), int(server._active.sum())
            server.step()
            seen.append((queued, active))
        server.step()               # idle: nothing queued, none active

    spans = record(body, tmp_path_factory.mktemp("serve"))
    assert all(r.status == "ok" for r in reqs)
    assert not server._flights
    return spans, reqs, seen, server.stats()


#: what a tick holds between its admit and its decode: the blocks of
#: the tick it queues; from an idle server also the blocks and the
#: launch of the tick it hands over; nothing when every slot's last
#: token is already in flight
BEFORE_DECODE = {
    "queues one": ["mx.serve_blocks"],
    "from idle": ["mx.serve_blocks", "mx.serve_dispatch",
                  "mx.serve_blocks"],
    "queues none": [],
}


def test_serve_tick_holds_every_span_of_the_table(serve_trace):
    spans, _, seen, _ = serve_trace
    ticks = named(spans, "mx.serve_tick")
    assert len(ticks) == len(seen) + 1
    busy = [t for t in ticks if named(children(spans, t),
                                      "mx.serve_decode")]
    assert len(busy) == len(seen)
    shapes = []
    for t in busy:
        kids = [s.name for s in children(spans, t)]
        assert kids[0] == "mx.serve_admit"
        assert kids[-2:] == ["mx.serve_decode", "mx.serve_emit"]
        shapes.append(next(k for k, v in BEFORE_DECODE.items()
                           if v == kids[1:-2]))
    # two requests of four tokens fill the two slots, the third waits
    # for one: twice from idle, two more launches, then the hand-over
    # of the last tick with nothing left to queue
    assert shapes == ["from idle", "queues one", "queues one",
                      "queues none"] * 2
    # every span of the tick's table lies inside some tick
    for s in spans:
        if s.name.startswith("mx.serve_") and s.name != "mx.serve_tick":
            assert any(t.holds(s) for t in ticks), s.name


def test_serve_prefill_is_inside_admit_with_its_counts(serve_trace):
    spans, reqs, _, _ = serve_trace
    prefills = named(spans, "mx.serve_prefill")
    assert len(prefills) == len(PROMPTS)
    admits = named(spans, "mx.serve_admit")
    for p in prefills:
        assert sum(a.holds(p) for a in admits) == 1
    # the first admit takes two prompts into the two slots
    assert len(named(children(spans, admits[0]),
                     "mx.serve_prefill")) == 2
    # admitted in the order submitted; no count is given that no
    # metric reads (PERF.md section 3 names the reader of each)
    assert [p.stats for p in prefills] == [
        {"tokens": n, "padded": 8} for n in PROMPTS]


def test_dispatch_and_wait_tile_decode(serve_trace):
    """The launch of the tick being queued, then the read of the tick
    before it; the launch is missing where nothing is left to queue."""
    spans, _, _, _ = serve_trace
    decodes = named(spans, "mx.serve_decode")
    assert decodes
    both = 0
    for d in decodes:
        kids = children(spans, d)
        assert [k.name for k in kids] in (
            ["mx.serve_dispatch", "mx.serve_wait"], ["mx.serve_wait"])
        if len(kids) == 1:
            continue
        both += 1
        disp, wait = kids
        assert disp.stats["ahead"] == 1
        # in this order inside the phase, and no other span of the
        # program's between or around them (`children` lists every
        # span directly inside). Their share of the phase's wall time
        # is the host's to decide and is not held: a stall between
        # two annotations of a 2-6 ms phase failed this test on a
        # busy machine
        assert d.start <= disp.start <= disp.end <= wait.start \
            <= wait.end <= d.end
        assert not children(spans, disp) and not children(spans, wait)
    assert both == 6


def test_dispatch_counts_the_slots_the_server_batched(serve_trace):
    spans, _, seen, stats = serve_trace
    # the tick itself carries no count
    assert all(t.stats == {} for t in named(spans, "mx.serve_tick"))
    # every tick is launched once and handed over once, a step() each
    disp = named(spans, "mx.serve_dispatch")
    assert len(disp) == len(seen) == stats["ticks"]
    # the dispatch counts the rows of the tick it queues, AFTER this
    # step's admits: two while the first two requests run, one once
    # only the third does; a slot whose last token is in flight has
    # no row, though the step still opens with it active
    assert [s.stats["active"] for s in disp] == [2] * 4 + [1] * 4
    assert [active for _, active in seen] == [0, 2, 2, 2, 0, 1, 1, 1]
    # `ahead`: whether a tick was in flight at the launch: not for the
    # first of the two an idle server launches
    ahead = [s.stats["ahead"] for s in disp]
    assert ahead == [0, 1, 1, 1] * 2
    assert sum(ahead) == stats["ticks_ahead"]


def test_a_wait_carries_the_tick_its_dispatch_carried(serve_trace):
    """One tick queued ahead: a `step()` launches tick n and reads
    tick n - 1, the one the `step()` before it launched."""
    spans, _, seen, stats = serve_trace
    disp = named(spans, "mx.serve_dispatch")
    waits = named(spans, "mx.serve_wait")
    # every launch numbered in order, from idle (two launches in one
    # step) and across the idle gap between the two waves alike
    assert [d.stats["tick"] for d in disp] == list(range(len(seen)))
    # every tick launched is read once, in the order launched
    assert [w.stats["tick"] for w in waits] == list(range(len(seen)))
    for d in named(spans, "mx.serve_decode"):
        kids = children(spans, d)
        if len(kids) == 2:
            assert kids[1].stats["tick"] == kids[0].stats["tick"] - 1
    # each wait comes after the dispatch of its own number
    launched = {d.stats["tick"]: d for d in disp}
    assert all(launched[w.stats["tick"]].end <= w.start for w in waits)
    # `late` is the device's to decide here (a tiny tick may be over
    # before the next launch); what the server counted is what it wrote
    late = [d.stats["late"] for d in disp]
    assert set(late) <= {0, 1} and sum(late) == stats["ticks_late"]
    assert all(a >= b for a, b in zip(
        (d.stats["ahead"] for d in disp), late))


def test_speculation_reads_the_tick_it_just_launched(net, tmp_path):
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8,
                             speculative=2)
    server.submit(np.arange(5, dtype=np.int32), max_new_tokens=6)
    spans = record(server.run, tmp_path)
    decodes = named(spans, "mx.serve_decode")
    assert decodes
    for i, d in enumerate(decodes):
        disp, wait = children(spans, d)
        assert disp.stats["tick"] == wait.stats["tick"] == i
        assert disp.stats["ahead"] == disp.stats["late"] == 0
    assert server.stats()["ticks_late"] == 0


class _StillRunning:
    """A tick's tokens that say they are not there yet."""

    def __init__(self, tok):
        self.tok = tok

    def is_ready(self):
        return False

    def __array__(self, *args, **kwargs):
        return np.asarray(self.tok)


@pytest.mark.parametrize("traced", [True, False])
def test_late_is_a_launch_behind_a_tick_already_done(net, tmp_path,
                                                     traced):
    """`late` on the span and `stats()["ticks_late"]`, which is all an
    untraced run (every judged one) has. The flag is read BEFORE the
    tick's own uploads: it says the tick in flight was done when
    `_dispatch` began, not that the launch itself was prompt."""
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8)
    req = server.submit(np.arange(5, dtype=np.int32), max_new_tokens=6)
    server.step()                       # from idle: two launches
    before = server.stats()

    def body():
        # behind a tick that is still running
        server._flights[0].tok = _StillRunning(server._flights[0].tok)
        server.step()
        assert server.stats()["ticks_late"] == before["ticks_late"]
        # behind one whose tokens are on the host's side already
        server._flights[0].tok.block_until_ready()
        server.step()

    if traced:
        spans = record(body, tmp_path)
        disp = named(spans, "mx.serve_dispatch")
        assert [d.stats["late"] for d in disp] == [0, 1]
        assert [d.stats["ahead"] for d in disp] == [1, 1]
        assert [d.stats["tick"] for d in disp] == [2, 3]
    else:
        body()
    after = server.stats()
    assert after["ticks_late"] == before["ticks_late"] + 1
    assert after["ticks_ahead"] == before["ticks_ahead"] + 2
    server.run()
    assert req.status == "ok" and len(req.output_tokens) == 6


def test_tick_numbers_run_on_across_early_returns(net, tmp_path):
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8,
                             watchdog_ticks=50)

    def body():
        server.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
        server.run()
        server.step()                   # idle: early return
        faults.inject("serving.stall")
        try:
            server.step()               # injected dead tick
        finally:
            faults.clear()
        server.submit(np.arange(6, dtype=np.int32), max_new_tokens=3)
        server.run()

    spans = record(body, tmp_path)
    disp = [d.stats["tick"] for d in named(spans, "mx.serve_dispatch")]
    assert disp == list(range(server.ticks)) and len(disp) == 6
    assert [w.stats["tick"] for w in named(spans, "mx.serve_wait")] \
        == disp


def test_a_decoder_with_counts_puts_them_on_its_spans(tmp_path):
    """afmoe: `ctx` / `window_ctx` on mx.serve_dispatch (the positions
    the full and the sliding layers' sweeps read), the expert layers'
    `pairs` / `touched` on mx.serve_emit, a prefill's on the emit of the
    tick that admitted it; they add up to what the server counted. The
    Llama block's spans carry none of them."""
    moe = mx.models.get_model("afmoe_tiny", held_experts=(0, 8))
    moe.initialize()
    server = InferenceServer(moe, batch_slots=2, max_len=64,
                             block_size=8, max_prompt_len=48)
    rs = np.random.RandomState(3)

    def body():
        for n in (40, 6, 9):
            server.submit(rs.randint(0, 256, n).astype(np.int32),
                          max_new_tokens=5)
        server.run()

    spans = record(body, tmp_path)
    disp = named(spans, "mx.serve_dispatch")
    emit = named(spans, "mx.serve_emit")
    assert len(disp) == len(emit) == server.ticks
    total = lambda ss, k: sum(s.stats.get(k, 0) for s in ss)  # noqa: E731
    stats = server.compile_stats()
    assert total(disp, "ctx") == stats["context_tokens"] > 0
    assert total(disp, "window_ctx") == stats["window_context_tokens"]
    assert stats["window_context_tokens"] < stats["context_tokens"]
    assert all(0 < s.stats["window_ctx"] <= 32 * s.stats["active"]
               for s in disp)
    for key in ("pairs", "touched", "prefill_pairs", "prefill_touched"):
        assert total(emit, key) == stats[key] > 0, key
    assert all({"pairs", "touched"} <= set(s.stats) for s in emit)
    # three prefills, each counted on the emit of its own tick
    assert sum("prefill_pairs" in s.stats for s in emit) == 2
    assert all(s.stats["touched"] <= 4 * 8 for s in emit)


def test_a_recurrent_decoder_counts_its_rows_and_scanned_tokens(
        tmp_path):
    """Jamba: `ssm_rows` (rows whose state the tick steps) and `ctx` on
    mx.serve_dispatch, `scan_tokens` (valid prompt tokens) beside
    `tokens` / `padded` on mx.serve_prefill; they add up to what the
    server counted. No `window_ctx`: there is no sliding layer."""
    net = mx.models.get_model("jamba_tiny")
    net.initialize()
    server = InferenceServer(net, batch_slots=2, max_len=64,
                             block_size=8, max_prompt_len=48)
    rs = np.random.RandomState(3)
    prompts = (40, 6, 9)

    def body():
        for n in prompts:
            server.submit(rs.randint(0, 256, n).astype(np.int32),
                          max_new_tokens=5)
        server.run()

    spans = record(body, tmp_path)
    disp = named(spans, "mx.serve_dispatch")
    pre = named(spans, "mx.serve_prefill")
    assert len(disp) == server.ticks and len(pre) == 3
    assert all(set(s.stats) == {"tick", "active", "ahead", "late",
                                "ctx", "ssm_rows"} for s in disp)
    assert all(s.stats["ssm_rows"] == s.stats["active"] for s in disp)
    assert sum(s.stats["ssm_rows"] for s in disp) == 3 * 5
    stats = server.compile_stats()
    assert sum(s.stats["ctx"] for s in disp) \
        == stats["context_tokens"] > 0
    assert [s.stats["scan_tokens"] for s in pre] == list(prompts)
    assert all(s.stats["scan_tokens"] == s.stats["tokens"]
               and s.stats["padded"] == 48 for s in pre)
    assert server.stats()["state_pool_bytes"] \
        == server.cache.stats()["state_pool_bytes"] > 0


#: what each description's spans carry beside `tick`, `active`, `ahead`
#: and `late` (mx.serve_dispatch), on mx.serve_emit, and beside `tokens`
#: and `padded` (mx.serve_prefill): a new description adds its own and
#: none to another's tick
DESCRIPTION_COUNTS = {
    "llama_tiny": ({}, set(), set(), set()),
    "afmoe_tiny": ({"held_experts": (0, 8)}, {"ctx", "window_ctx"},
                   {"pairs", "touched"}, set()),
    "jamba_tiny": ({}, {"ctx", "ssm_rows"}, set(), {"scan_tokens"}),
    "sarvam_mla_tiny": ({"held_experts": (0, 8)}, {"ctx"},
                        {"pairs", "touched"}, set()),
}


@pytest.mark.parametrize("model", list(DESCRIPTION_COUNTS))
def test_every_description_carries_its_own_counts_and_no_others(
        model, tmp_path):
    """Sarvam (latent layers): `ctx` on mx.serve_dispatch, the cached
    rows the latent sweep reads, summed over slots, and the expert
    layers' `pairs` / `touched` (a prefill's as `prefill_*`) on
    mx.serve_emit. A Llama, an afmoe and a Jamba tick carry exactly what
    they carried before it came."""
    kw, on_dispatch, on_emit, on_prefill = DESCRIPTION_COUNTS[model]
    net = mx.models.get_model(model, **kw)
    net.initialize()
    server = InferenceServer(net, batch_slots=2, max_len=64,
                             block_size=8, max_prompt_len=48)
    rs = np.random.RandomState(3)

    def body():
        for n in (40, 6, 9):
            server.submit(rs.randint(0, 256, n).astype(np.int32),
                          max_new_tokens=5)
        server.run()

    spans = record(body, tmp_path)
    disp = named(spans, "mx.serve_dispatch")
    emit = named(spans, "mx.serve_emit")
    assert len(disp) == len(emit) == server.ticks
    assert all(set(s.stats) == {"tick", "active", "ahead", "late"}
               | on_dispatch for s in disp)
    assert all(set(s.stats) - {"prefill_" + k for k in on_emit}
               == on_emit for s in emit)
    assert all(set(s.stats) == {"tokens", "padded"} | on_prefill
               for s in named(spans, "mx.serve_prefill"))
    assert all(set(s.stats) == {"tick"}
               for s in named(spans, "mx.serve_wait"))
    stats = server.compile_stats()
    if on_dispatch:
        assert sum(s.stats["ctx"] for s in disp) \
            == stats["context_tokens"] > 0
    for key in on_emit:
        assert sum(s.stats[key] for s in emit) == stats[key] > 0
        assert sum(s.stats.get("prefill_" + key, 0) for s in emit) \
            == stats["prefill_" + key] > 0
    latent = model == "sarvam_mla_tiny"
    assert ("latent_pool_bytes" in server.stats()) == latent
    if latent:
        # 3 layers x 17 blocks x 8 positions x a row of 128 float32
        assert server.stats()["latent_pool_bytes"] \
            == 3 * 17 * 8 * 128 * 4
        assert server.stats()["latent_pool_tokens"] == 16 * 8


def test_the_llama_block_adds_no_count(serve_trace):
    spans, _, _, _ = serve_trace
    assert all(set(s.stats) == {"tick", "active", "ahead", "late"}
               for s in named(spans, "mx.serve_dispatch"))
    assert all(set(s.stats) == {"tick"}
               for s in named(spans, "mx.serve_wait"))
    assert all(s.stats == {} for s in named(spans, "mx.serve_emit"))
    assert all(set(s.stats) == {"tokens", "padded"}
               for s in named(spans, "mx.serve_prefill"))


def test_early_return_ticks_close_serve_tick(net, tmp_path):
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=8,
                             watchdog_ticks=50)
    server.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)

    def body():
        faults.inject("serving.stall")
        try:
            server.step()           # injected dead tick
            server.step()
        finally:
            faults.clear()
        server.run()
        server.step()               # nothing queued, nothing active

    spans = record(body, tmp_path)
    ticks = named(spans, "mx.serve_tick")
    kids = [[s.name for s in children(spans, t)] for t in ticks]
    assert kids[0] == [] and kids[1] == []          # stalled
    assert kids[-1] == ["mx.serve_admit"]           # idle
    assert "mx.serve_decode" in kids[2]
    # every tick closed: none holds another
    assert not any(a.holds(b) for a in ticks for b in ticks)


def test_chunked_prefill_spans_carry_chunk_counts(net, tmp_path):
    server = InferenceServer(net, batch_slots=2, max_len=32,
                             block_size=8, max_prompt_len=16,
                             prefill_chunk_tokens=4)
    req = server.submit(np.arange(10, dtype=np.int32), max_new_tokens=2)
    spans = record(server.run, tmp_path)
    assert req.status == "ok"
    chunks = named(spans, "mx.serve_prefill")
    assert [c.stats["tokens"] for c in chunks] == [4, 4, 2]
    assert {c.stats["padded"] for c in chunks} == {4}
    ticks = named(spans, "mx.serve_tick")
    admits = named(spans, "mx.serve_admit")
    for c in chunks:
        assert any(t.holds(c) for t in ticks)
        assert not any(a.holds(c) for a in admits)


def test_executables_are_named_in_the_trace():
    prog = exe.Program("serving_decode", lambda x: x + 1)
    assert prog(1.0) == 2.0
    assert "jit_counted_serving_decode" in \
        prog._jit.lower(1.0).as_text()
    assert prog.name == "serving_decode"      # accounting key unchanged


# -- the train step ----------------------------------------------------------

def _fused_step():
    net = mx.gluon.nn.Dense(8, in_units=4)
    net.initialize()

    def loss_fn(pred, label):
        return ((pred - label) ** 2).mean()

    opt = mx.optimizer.SGD(learning_rate=0.1)
    return FusedTrainStep(net, loss_fn, opt, mesh=None)


def test_train_step_spans(tmp_path):
    step = _fused_step()
    x, y = mx.nd.ones((4, 4)), mx.nd.ones((4, 8))
    step(x, y)                          # build outside the session

    def body():
        for _ in range(3):
            step(x, y)

    spans = record(body, tmp_path)
    steps = named(spans, "mx.train_step")
    assert len(steps) == 3
    for s in steps:
        assert [k.name for k in children(spans, s)] == [
            "mx.data", "mx.train_dispatch"]
    # telemetry is off: nothing synced, nothing recorded on the host
    # clock
    assert not tm._TRACE_EVENTS and not tm._REGISTRY


def test_run_steps_is_one_train_step_span_a_window(tmp_path):
    step = _fused_step()
    batch = (mx.nd.ones((4, 4)), mx.nd.ones((4, 8)))
    step.run_steps([batch] * 3)         # build outside the session
    spans = record(lambda: step.run_steps([batch] * 3), tmp_path)
    steps = named(spans, "mx.train_step")
    assert len(steps) == 1
    assert [k.name for k in children(spans, steps[0])] == [
        "mx.data", "mx.train_dispatch"]
    # K=1 without skip or loss-scale law is a single dispatch: one
    # span, from __call__, and none around it
    spans = record(lambda: step.run_steps([batch]), tmp_path / "k1")
    assert len(named(spans, "mx.train_step")) == 1


# -- off and on --------------------------------------------------------------

def test_off_path_stamps_no_time_and_touches_no_registry(monkeypatch):
    def forbidden(*a, **k):
        raise AssertionError("the disabled path must not come here")

    monkeypatch.setattr(tm.time, "perf_counter", forbidden)
    monkeypatch.setattr(tm, "histogram", forbidden)
    monkeypatch.setattr(tm, "_family", forbidden)
    with tm.span("serve_dispatch", active=2):
        with tm.phase("serve_decode"):
            with tm.phase("data", device=True, rows=4):
                pass
    assert not tm._TRACE_EVENTS and not tm._REGISTRY


def test_a_span_with_no_session_open_is_cheap():
    n = 20000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with tm.span("serve_dispatch", active=3):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    # 0.5 us here (PERF.md section 6 has the chip host's reading); the
    # limit only catches a span that grew a clock read or a lock
    assert best < 10e-6


def test_enabled_phase_records_what_it_always_did(monkeypatch, tmp_path):
    noted = []
    monkeypatch.setattr(tm, "_goodput_note",
                        lambda name, seconds, t0: noted.append(name))
    tm.enable()

    def body():
        with tm.phase("data"):
            with tm.span("inner"):          # a span feeds no ledger
                pass
        with tm.phase("fused_step", device=True):
            pass

    spans = record(body, tmp_path)
    assert noted == ["data", "fused_step"]
    bd = tm.snapshot()["step_time_breakdown"]
    assert bd["data"]["count"] == 1 and bd["fused_step"]["count"] == 1
    assert "inner" not in bd
    events = {e["name"]: e["pid"] for e in tm._TRACE_EVENTS}
    assert events == {"data": tm.HOST_PID, "fused_step": tm.DEVICE_PID}
    # and the same phases are on the profiler's clock, names unchanged
    assert [s.name for s in spans] == ["mx.data", "mx.inner",
                                       "mx.fused_step"]


def test_export_chrome_trace_holds_no_device_trace_pids(tmp_path):
    """The exporter's device half is gone: host-clock pids only."""
    tm.enable()
    tm.mark_phase("forward", 0.001)
    tm.mark_phase("fused_step", 0.002, device=True)
    import json
    blob = json.loads(open(tm.export_chrome_trace(
        str(tmp_path / "t.json"))).read())
    pids = {e["pid"] for e in blob["traceEvents"]}
    # (a server of this module that is still alive adds its request
    # lanes under REQUEST_PID)
    assert {tm.HOST_PID, tm.DEVICE_PID} <= pids \
        <= {tm.HOST_PID, tm.DEVICE_PID, tm.REQUEST_PID}
    assert not hasattr(tm, "note_device_trace")
