"""The join of a decode tick to its launch, its module on the device and
its read-back (``perfbench/ticklag.py``), and the metrics that read it,
on synthetic timelines of spans and modules on one clock: the healthy
look-ahead, the serial mode (every read-back lands after the NEXT
module has ended), one tick in which the host stood still; then what
the join refuses, the table a person reads after a slow run, and the
two recorded traces, whose programs number no tick."""
import importlib
import os

import pytest

from perfbench import harness, mxspans, ticklag, xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
DECODE = "jit_counted_serving_decode(2)"
PREFILL = "jit_counted_serving_prefill(1)"
P50, P99 = "read_after_done_ms_p50.serve", "read_after_done_ms_p99.serve"
MAX, HOST_MAX = "read_after_done_ms_max.serve", "tick_host_ms_max.serve"
LAG = "launch_lag_ms_p99.serve"
JOINED = [P50, P99, MAX, LAG, "read_after_done_ms_p50.tpot",
          "read_after_done_ms_p99.tpot", "read_after_done_ms_max.tpot",
          "launch_lag_ms_p99.tpot"]


class Ctx:
    """What a reader looks at, for a timeline built by hand."""

    def __init__(self, trace, spans):
        self.trace = trace
        self._mxspans = spans
        self.counters = {}
        self.phases = harness.Phases()


def metric(name, ctx):
    spec = harness.load_json(harness.HERE, "metrics", name + ".json")
    return importlib.import_module(
        "perfbench.readers." + spec["reader"]).read(spec, ctx)


def context(thread, modules, window):
    """Spans of one host thread and the device's modules, each decode
    module holding one ``flash_decode_paged`` call, cut to ``window``."""
    ops = [("flash_decode_paged.1", s + d // 4, d // 2)
           for n, s, d in modules if n == DECODE]
    ops += [("fusion.9", s, d) for n, s, d in modules if n != DECODE]
    trace = xtrace.Trace({0: ops}, {0: modules},
                         [("pb.window", window[0],
                           window[1] - window[0])]).windowed()
    return Ctx(trace, mxspans.build([thread], trace))


def simulate(n, length=10 * MS, read_back=3 * MS // 10, serial=False,
             stall_at=None, stall_ns=300 * MS, prefill_in=None,
             prefill_ns=25 * MS):
    """``n`` calls of ``step()`` on one clock with the device. Tick 0 is
    in flight (module 0 from time 0); step ``k`` spends 0.5 ms on admit
    and blocks, launches tick ``k + 1`` in a 1 ms dispatch (its module
    starts 0.05 ms after the launch or when the device is free), reads
    tick ``k`` until ``read_back`` after its module ended, and emits
    for 0.1 ms. ``serial``: the read of tick ``k`` returns only 0.2 ms
    after tick ``k + 1`` has ENDED. ``stall_at``: the host stands still
    for ``stall_ns`` in that step's emit. ``prefill_in``: that step's admit launches a
    prompt's prefill behind the tick in flight.
    Returns ``(thread, modules)``."""
    thread, modules = [], [(DECODE, 0, length)]
    ends = [length]                     # end of tick k's module
    free = length                       # when the device has no work
    # the serial mode is entered with tick 0 done and not yet read
    t = length + MS // 5 if serial else 4 * MS // 10
    for k in range(n):
        if k == prefill_in:
            modules.append((PREFILL, max(free, t + MS // 5), prefill_ns))
            free = modules[-1][1] + prefill_ns
        disp, wait = t + MS // 2, t + 3 * MS // 2
        late = int(ends[k] <= disp)
        start = max(free, wait + MS // 20)
        modules.append((DECODE, start, length))
        ends.append(start + length)
        free = start + length
        wait_end = max(ends[k] + read_back, wait + MS // 100)
        if serial:
            wait_end = max(wait_end, ends[k + 1] + MS // 5)
        end = wait_end + MS // 10 + (stall_ns if k == stall_at else 0)
        thread += [
            ("mx.serve_tick", t, end - t, {}),
            ("mx.serve_decode", disp, wait_end - disp, {}),
            ("mx.serve_dispatch", disp, MS,
             {"tick": k + 1, "active": 2, "ahead": 1, "late": late}),
            ("mx.serve_wait", wait, wait_end - wait, {"tick": k}),
            ("mx.serve_emit", wait_end, end - wait_end, {}),
        ]
        t = end
    return thread, modules


def decodes(modules):
    return [(s, s + d) for n, s, d in modules if n == DECODE]


def whole(thread, modules):
    return (-MS, max(e[1] + e[2] for e in thread + modules) + MS)


def test_healthy_look_ahead_reads_back_at_once():
    thread, modules = simulate(40)
    ctx = context(thread, modules, whole(thread, modules))
    ticks = ticklag.of(ctx, ctx._mxspans, "jit_counted",
                       "flash_decode_paged")
    # launches 1..40 in the trace (tick 0's was before it): each joined
    # to the module it ran as
    assert [t.seq for t in ticks] == list(range(1, 41))
    assert [t.module for t in ticks] == decodes(modules)[1:]
    assert all(t.launch_lag == 0 for t in ticks)
    # the last launch is read by no step of the window
    assert [t.read_after_done for t in ticks] \
        == [3 * MS // 10] * 39 + [None]
    assert metric(P50, ctx) == pytest.approx(0.3)
    assert metric(P99, ctx) == metric(MAX, ctx) == pytest.approx(0.3)
    assert metric(LAG, ctx) == 0.0
    assert metric("ticks_late_share.serve", ctx) == 0.0
    assert metric("tick_host_ms_p99.serve", ctx) \
        == pytest.approx(metric("tick_host_ms_p50.serve", ctx)) \
        == pytest.approx(metric(HOST_MAX, ctx))
    # worked out once, kept on the context
    assert [r[0] for r in ctx.phases.rows].count("read.ticklag") == 1


def guess_from_the_last_module_ended(ctx):
    """What a reader without the numbers would do: charge a wait to the
    last decode module that ended before the wait did. Median, ms."""
    ends = sorted(s + d for n, s, d in ctx.trace.device_modules[0]
                  if n == DECODE)
    xs = sorted(w.end - max(e for e in ends if e <= w.end)
                for w in ctx._mxspans.named("mx.serve_wait", whole=True))
    return xs[len(xs) // 2] / MS


def test_the_serial_mode_reads_a_whole_tick_late_where_a_guess_reads_none():
    length = 70 * MS
    thread, modules = simulate(30, length, serial=True)
    ctx = context(thread, modules, whole(thread, modules))
    got = metric(P50, ctx)
    assert got == pytest.approx(length / MS, rel=0.05) and got > 70
    assert metric(P99, ctx) == pytest.approx(got)
    assert metric("ticks_late_share.serve", ctx) == 100.0
    # the device started each tick 0.05 ms after its launch
    assert metric(LAG, ctx) == pytest.approx(0.05)
    # the guess reads the mode as health
    assert guess_from_the_last_module_ended(ctx) == pytest.approx(0.2)
    # and on the healthy timeline the two agree
    thread, modules = simulate(30)
    ctx = context(thread, modules, whole(thread, modules))
    assert guess_from_the_last_module_ended(ctx) \
        == pytest.approx(metric(P50, ctx))


def test_one_stalled_tick_shows_in_the_tail_alone():
    thread, modules = simulate(40, stall_at=20)
    ctx = context(thread, modules, whole(thread, modules))
    assert metric("tick_host_ms_p50.serve", ctx) < 2.0
    assert metric("tick_host_ms_p99.serve", ctx) > 100.0
    # admit and blocks 0.5, dispatch 1, emit 0.1 and the stall
    assert metric(HOST_MAX, ctx) == pytest.approx(301.6)
    assert metric("tick_host_ms_max.tpot", ctx) == metric(HOST_MAX, ctx)
    # the device ran out of work once: the launch behind the stall is
    # the late one, and tick 21's read-back waited for the host
    assert metric("ticks_late_share.serve", ctx) \
        == pytest.approx(100.0 / 40)
    assert metric(P50, ctx) == pytest.approx(0.3)
    assert metric(P99, ctx) > 100.0
    # the stall and the next step's way to its wait, less what tick
    # 21's module had still to run when the stall began; the p99 of 40
    # is an interpolation, of 2,500 it would read the 0.3
    assert metric(MAX, ctx) == pytest.approx(291.91)
    # the join holds through the stall: every tick its own module
    ticks = ticklag.of(ctx, ctx._mxspans, "jit_counted",
                       "flash_decode_paged")
    assert [t.module for t in ticks] == decodes(modules)[1:]
    # the one tick launched to an idle device started 0.05 ms later
    assert sorted(t.launch_lag for t in ticks)[-2:] == [0, MS // 20]


def test_the_table_names_the_tick(capsys):
    thread, modules = simulate(40, stall_at=20)
    ctx = context(thread, modules, whole(thread, modules))
    ticks = ticklag.of(ctx, ctx._mxspans, "jit_counted",
                       "flash_decode_paged")
    got = ticklag.rows(ticks, ctx._mxspans, n=3)
    first = got["read_back"][0]
    assert (first["tick"], first["late"]) == (21, 0)
    assert first["read_after_done"] == pytest.approx(291.91)
    assert first["module"] == 10.0 and first["launch_lag"] == 0.0
    assert [r["read_after_done"] for r in got["read_back"][1:]] \
        == [0.3, 0.3]
    # the step that stood still, by its parts: the emit held it
    assert got["host"][0] == {
        "self": 301.6, "serve_decode": 9.4, "serve_dispatch": 1.0,
        "serve_wait": 8.4, "serve_emit": 300.1}
    assert got["host"][1]["self"] == 1.6
    assert len(got["host"]) == 3


def test_a_launch_behind_a_prefill_does_not_shift_the_join():
    """Step 2's admit launches a prompt behind tick 2; step 3 launches
    tick 4 while the device is still in the prompt and tick 3 has not
    started: the first module to start after THAT dispatch began is tick
    3's, not its own. A window that opens on it must not anchor there:
    the ticks behind it bind the offset."""
    thread, modules = simulate(12, prefill_in=2)
    launch4 = next(e for e in thread if e[3].get("tick") == 4
                   and e[0] == "mx.serve_dispatch")
    prefill = next((s, s + d) for n, s, d in modules if n == PREFILL)
    tick3 = decodes(modules)[3]
    assert prefill[0] < launch4[1] < prefill[1] <= tick3[0]
    ctx = context(thread, modules,
                  (launch4[1] - MS // 10, whole(thread, modules)[1]))
    ticks = ticklag.of(ctx, ctx._mxspans, "jit_counted",
                       "flash_decode_paged")
    assert ticks[0].seq == 4
    assert [t.module for t in ticks] == decodes(modules)[4:]
    # tick 3 waited for the prompt and tick 4 for tick 3: work before
    # them, no lag; seen whole, the same
    assert metric(LAG, ctx) == 0.0
    ctx = context(thread, modules, whole(thread, modules))
    assert metric(LAG, ctx) == 0.0 and metric(P99, ctx) \
        == pytest.approx(0.3)


def test_the_windows_edge_cuts_the_first_dispatch():
    thread, modules = simulate(12)
    # tick 1's dispatch runs 0.9-1.9 ms: the window opens inside it
    ctx = context(thread, modules, (MS, whole(thread, modules)[1]))
    ticks = ticklag.of(ctx, ctx._mxspans, "jit_counted",
                       "flash_decode_paged")
    assert [t.seq for t in ticks] == list(range(2, 13))
    assert [t.module for t in ticks] == decodes(modules)[2:]
    assert metric(P50, ctx) == pytest.approx(0.3)
    # and a window that closes inside the wait of tick 11: it has its
    # module and a launch lag but no read-back; tick 12 is launched and
    # its module starts past the edge
    cut = context(thread, modules, (-MS, decodes(modules)[11][0] + 5 * MS))
    ticks = ticklag.of(cut, cut._mxspans, "jit_counted",
                       "flash_decode_paged")
    assert ticks[-1].seq == 12 and ticks[-1].wait is None
    assert [t.read_after_done for t in ticks[-3:]] \
        == [3 * MS // 10, None, None]
    assert [t.launch_lag for t in ticks[-3:]] == [0, 0, None]
    assert ticks[-1].module is None


def test_a_missing_number_starts_a_new_run():
    thread, modules = simulate(12)
    thread = [e for e in thread
              if not (e[0] == "mx.serve_dispatch" and e[3]["tick"] == 5)]
    ctx = context(thread, modules, whole(thread, modules))
    ticks = ticklag.of(ctx, ctx._mxspans, "jit_counted",
                       "flash_decode_paged")
    assert [t.seq for t in ticks] == [1, 2, 3, 4] + list(range(6, 13))
    assert all(t.module == decodes(modules)[t.seq] for t in ticks)


def test_a_module_the_trace_lost_is_not_replaced_by_its_neighbour():
    thread, modules = simulate(12)
    lost = modules[:5] + modules[6:]
    ctx = context(thread, lost, whole(thread, modules))
    ticks = {t.seq: t for t in ticklag.of(
        ctx, ctx._mxspans, "jit_counted", "flash_decode_paged")}
    # tick 5's place in the order is taken by tick 6's module, which
    # ends after tick 5 was read: refused, and the rest anchored anew
    assert ticks[5].module is None
    assert ticks[5].read_after_done is None \
        and ticks[5].launch_lag is None
    assert all(t.module == decodes(modules)[k]
               for k, t in ticks.items() if k != 5)
    assert metric(P50, ctx) == pytest.approx(0.3)


def test_what_cannot_be_anchored_reads_as_nothing():
    thread, modules = simulate(6)
    win = whole(thread, modules)
    # no decode module in the trace (the prefill's alone)
    ctx = context(thread, [(PREFILL, 0, 5 * MS)], win)
    assert all(metric(m, ctx) is None for m in JOINED)
    # the numbers of two servers in one window
    ctx = context(thread + thread, modules, win)
    assert all(metric(m, ctx) is None for m in JOINED)
    # spans without the counts: a program from before them
    bare = [(n, s, d, {k: v for k, v in c.items()
                       if k not in ("tick", "late")})
            for n, s, d, c in thread]
    ctx = context(bare, modules, win)
    assert all(metric(m, ctx) is None for m in JOINED)
    assert metric("ticks_late_share.serve", ctx) is None
    assert metric("ticks_late_share.tpot", ctx) is None
    assert not ctx.phases.rows          # and the trace was not walked
    # the host's own tail needs no number: PR 24's spans are enough
    assert metric(HOST_MAX, ctx) >= metric("tick_host_ms_p99.serve", ctx)


def test_the_train_steps_tail():
    steps = [("mx.train_step", k * 10 * MS, 6 * MS, {})
             for k in range(50)]
    steps[30] = ("mx.train_step", 300 * MS, 9 * MS + 40 * MS, {})
    steps = steps[:31] + [(n, s + 40 * MS, d, c)
                          for n, s, d, c in steps[31:]]
    ctx = context(steps, [("jit_step", 0, 600 * MS)], (-MS, 600 * MS))
    assert metric("step_host_ms_p50.train", ctx) == pytest.approx(6.0)
    assert 25.0 < metric("step_host_ms_p99.train", ctx) < 49.0
    assert metric(HOST_MAX, ctx) is None    # no server in this process


@pytest.mark.parametrize("name, numbered", [
    ("tiny_mx_v5e.xplane.pb", True), ("tiny_v5e.xplane.pb", False)])
def test_the_recorded_traces_number_no_tick(name, numbered):
    """``tiny_mx_v5e``: ticks and steps of a program from before the
    counts; ``tiny_v5e``: no span of the program's at all."""
    path = os.path.join(HERE, "data", name)
    if not os.path.exists(path):
        pytest.skip("no recorded trace in perfbench/tests/data")
    trace = xtrace.load(path).windowed()
    ctx = Ctx(trace, mxspans.build(mxspans.read_threads(path), trace))
    for m in JOINED + ["ticks_late_share.serve", "ticks_late_share.tpot"]:
        assert metric(m, ctx) is None, m
    tails = [metric(m, ctx) for m in (
        "tick_host_ms_max.serve", "tick_host_ms_max.tpot",
        "tick_host_ms_p99.serve", "step_host_ms_p99.train")]
    if numbered:
        assert tails[0] == tails[1] >= tails[2] \
            >= metric("tick_host_ms_p50.serve", ctx) > 0
        assert tails[3] >= metric("step_host_ms_p50.train", ctx) > 0
    else:
        assert tails == [None] * 4
    assert ticklag.main(["ticklag", path]) == 1     # nothing to join
