"""The trace-to-metrics reduction: interval arithmetic on a synthetic
timeline, then the same functions on a small recorded TPU trace
(``perfbench/tests/data/tiny_v5e.xplane.pb``: three steps of a jitted
program holding one ``flash_attention_fwd`` Pallas call and a matmul,
with ``pb.*`` host spans around them)."""
import os
import random

import pytest

from perfbench import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "tiny_v5e.xplane.pb")
DATA_MX = os.path.join(HERE, "data", "tiny_mx_v5e.xplane.pb")


def test_union_and_subtract():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) \
        == [[0, 3], [5, 8]]
    assert xtrace.total(xtrace.union([(0, 2), (1, 3)])) == 3
    assert xtrace.subtract([[0, 10]], [[2, 3], [5, 7]]) \
        == [[0, 2], [3, 5], [7, 10]]
    assert xtrace.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert xtrace.subtract([[0, 4]], []) == [[0, 4]]


def test_base_name_and_kernel_match():
    assert xtrace.base_name("%fusion.123") == "fusion"
    assert xtrace.base_name("all-reduce-start.1.2") == "all-reduce-start"
    line = ("jvp_flash_attention_fwd_.20 = custom-call(copy-done.1224, "
            "bitcast.147), custom_call_target=\"tpu_custom_call\"")
    assert xtrace.base_name(line) == "jvp_flash_attention_fwd_"
    evs = [("flash_attention_fwd.3", 0, 10), ("flash_attention_dq", 10, 5),
           ("flash_attention_fwd_q8.1", 20, 7), ("fusion.1", 30, 1),
           (line, 40, 3),
           ("transpose_jvp_flash_attention_dq__.2 = custom-call()", 50, 2)]
    got = xtrace.kernel_events(evs, "flash_attention_fwd")
    assert [e[1] for e in got] == [0, 40]
    got = xtrace.kernel_events(evs, "flash_attention_dq")
    assert [e[1] for e in got] == [10, 50]


def test_leaf_events_drop_enclosing_ops():
    # a while op spanning its body: only the body counts by name
    evs = [("while.1", 0, 100), ("fusion.1", 0, 40), ("copy.2", 50, 30),
           ("fusion.7", 120, 10)]
    by = xtrace.time_by_name(evs)
    assert by == {"fusion": pytest.approx(50e-9),
                  "copy": pytest.approx(30e-9)}
    # busy is the union whatever the nesting
    assert xtrace.total(xtrace.union((s, s + d) for _, s, d in evs)) == 110


def test_exposed_collective_time():
    # all-reduce 0-100; compute covers 20-60 and 90-130
    evs = [("all-reduce.1", 0, 100), ("fusion.1", 20, 40),
           ("fusion.2", 90, 40)]
    exposed, total = xtrace.exposed_collective_s(evs)
    assert total == pytest.approx(100e-9)
    assert exposed == pytest.approx((20 + 30) * 1e-9)


def test_idle_gaps_are_charged_to_the_host_span_over_them():
    ops = [("fusion.1", 10, 10), ("fusion.2", 50, 10)]   # busy 10-20, 50-60
    spans = [("pb.server.step", 0, 45), ("pb.sleep", 60, 40)]
    gaps = xtrace.idle_gaps(ops, spans, 0, 100)
    # 0-10 and 20-50 under server.step (30 of its 45 overlap the second
    # gap), 60-100 under sleep
    assert gaps == {"pb.server.step": pytest.approx(40e-9),
                    "pb.sleep": pytest.approx(40e-9)}
    assert xtrace.idle_gaps(ops, [], 0, 100) == {
        "pb.unattributed": pytest.approx(80e-9)}


# -- the sweep against the walk it replaced -----------------------------------

def walk_idle_gaps(events, host_spans, t0, t1, visits=None):
    """``xtrace.idle_gaps`` as it stood until PR 37, kept here as the
    oracle: for every gap, every span from the first that starts before
    the gap's end (gaps x spans / 2 steps). ``visits`` counts them."""
    busy = xtrace.union((s, s + d) for _, s, d in events)
    gaps = xtrace.subtract([[t0, t1]], busy)
    spans = sorted((e for e in host_spans if e[0] != xtrace.WINDOW_SPAN),
                   key=lambda e: e[1])
    acc = {}
    for gs, ge in gaps:
        best, best_ov = "pb.unattributed", 0
        for name, s, d in spans:
            if visits is not None:
                visits[0] += 1
            if s >= ge:
                break
            ov = min(ge, s + d) - max(gs, s)
            if ov > best_ov:
                best, best_ov = name, ov
        acc[best] = acc.get(best, 0.0) + (ge - gs) * 1e-9
    return acc


def random_timeline(seed):
    """Device events and host spans on a coarse grid, so that overlaps
    tie; the shapes the sweep has to get right are each drawn often:
    nested and overlapping spans, a span over several gaps, a gap over
    several spans, gaps before the first and after the last span, spans
    of no length, ``pb.window`` over it all, no spans, no gaps."""
    rng = random.Random(seed)
    grid = rng.choice([1, 5, 10])
    t1 = rng.choice([200, 1000, 5000])

    def at(lo, hi):
        return rng.randrange(lo // grid, hi // grid + 1) * grid

    kind = seed % 10
    n_ops = 0 if kind == 0 else rng.randrange(1, 60)
    ops = []
    for i in range(n_ops):
        s = at(0, t1)
        ops.append((f"fusion.{i}", s, at(0, t1 // max(4, n_ops)) + grid))
    if kind == 1:                       # no gaps: busy all through
        ops = [("while.1", -10, t1 + 20)] + ops
    names = ["pb.a", "pb.b", "pb.c", "pb.a.inner"]
    spans = []
    lo, hi = (t1 // 4, 3 * t1 // 4) if kind in (2, 3) else (0, t1)
    for _ in range(0 if kind == 4 else rng.randrange(1, 40)):
        s = at(lo, hi)
        d = rng.choice([0, grid, grid, at(0, t1 // 10), at(0, t1 // 2)])
        spans.append((rng.choice(names), s, d))
        if rng.random() < 0.3:          # a child, or an equal twin
            spans.append((rng.choice(names), at(s, s + d),
                          at(0, max(d, grid)) // 2))
    if kind == 5:                       # one span over the whole window
        spans.append(("pb.over", -5, t1 + 10))
    if seed % 2:
        spans.append((xtrace.WINDOW_SPAN, 0, t1))
    rng.shuffle(spans)
    return ops, spans, 0, t1


@pytest.mark.parametrize("block", range(10))
def test_the_sweep_charges_every_gap_as_the_walk_did(block):
    seen = set()
    for seed in range(block * 40, block * 40 + 40):
        ops, spans, t0, t1 = random_timeline(seed)
        want = walk_idle_gaps(ops, spans, t0, t1)
        assert xtrace.idle_gaps(ops, spans, t0, t1) == want, seed
        tr = xtrace.Trace({0: ops}, {0: []},
                          spans + [(xtrace.WINDOW_SPAN, t0, t1 - t0)])
        cut = xtrace.clip(ops, t0, t1)
        assert tr.windowed().idle_gaps() == (walk_idle_gaps(
            cut, xtrace.clip(spans, t0, t1), t0, t1) if cut else {})
        seen |= set(want)
    # the draws reach every outcome: a named span and no span at all
    assert "pb.unattributed" in seen and len(seen) >= 3


def test_the_sweep_keeps_the_walks_rule_on_ties_and_nesting():
    ops = [("fusion.1", 10, 10), ("fusion.2", 40, 10), ("fusion.3", 80, 5)]
    # gaps 0-10, 20-40, 50-80, 85-100
    # equal overlap: the earliest-starting span wins, and of two that
    # start together the one given first
    spans = [("pb.late", 25, 10), ("pb.early", 22, 10),
             ("pb.twin.b", 55, 20), ("pb.twin.a", 55, 20)]
    assert xtrace.idle_gaps(ops, spans, 0, 100) == {
        "pb.unattributed": pytest.approx(25e-9),
        "pb.early": pytest.approx(20e-9),
        "pb.twin.b": pytest.approx(30e-9)}
    # a long span over a short one that ended long ago hides nothing:
    # the gap at 85 still finds the span that starts after both
    spans = [("pb.long", 0, 84), ("pb.short", 2, 3), ("pb.last", 84, 16)]
    got = xtrace.idle_gaps(ops, spans, 0, 100)
    assert got == walk_idle_gaps(ops, spans, 0, 100)
    assert got["pb.last"] == pytest.approx(15e-9)
    # a span of no length, or one that only touches a gap, owns nothing
    spans = [("pb.none", 30, 0), ("pb.touch", 10, 10), ("pb.window", 0, 100)]
    assert xtrace.idle_gaps(ops, spans, 0, 100) == {
        "pb.unattributed": pytest.approx(75e-9)}


def ticks_timeline(ticks, ops_a_tick=40, tick_ns=1000):
    """A serving window: a device event every slot with a gap after it,
    one ``pb.server.step`` a tick."""
    slot = tick_ns // ops_a_tick
    ops = [(f"fusion.{j}", i * tick_ns + j * slot, slot - 3)
           for i in range(ticks) for j in range(ops_a_tick)]
    spans = [("pb.server.step", i * tick_ns + 7, tick_ns - 20)
             for i in range(ticks)]
    return ops, spans, ticks * tick_ns


def sweep_visits(ticks):
    ops, spans, t1 = ticks_timeline(ticks)
    busy = xtrace.union((s, s + d) for _, s, d in ops)
    acc, visits = xtrace.charge_gaps(
        xtrace.subtract([[0, t1]], busy), xtrace.host_spans_by_start(spans))
    return acc, visits, len(busy) + 1 + len(spans)


def test_the_sweeps_cost_grows_with_the_ticks_not_their_square():
    """Counted in span visits, not seconds: four times the ticks cost
    the walk sixteen times the visits and the sweep four."""
    walk = []
    for ticks in (50, 200):
        ops, spans, t1 = ticks_timeline(ticks)
        n = [0]
        want = walk_idle_gaps(ops, spans, 0, t1, visits=n)
        acc, visits, size = sweep_visits(ticks)
        assert acc == want
        walk.append((n[0], visits, size))
    (w1, s1, n1), (w4, s4, n4) = walk
    assert w4 > 14 * w1
    assert s4 <= 5 * s1
    # and in absolute terms: a few visits a gap and a span
    assert s4 <= 3 * n4


def test_a_window_at_the_rate_the_copy_pr_brings_is_swept_in_one_pass():
    # 2,500 ticks a window; 60 events a tick here keep the test short
    # (600 on the chip: the sweep is linear in them, the walk was too)
    acc, visits, size = sweep_visits(2500)
    assert visits <= 3 * size
    assert set(acc) == {"pb.server.step", "pb.unattributed"}


def test_clip_cuts_to_the_window():
    evs = [("a", 0, 10), ("b", 8, 10), ("c", 30, 5)]
    assert xtrace.clip(evs, 5, 12) == [("a", 5, 5), ("b", 8, 4)]


@pytest.fixture(scope="module")
def tiny():
    if not os.path.exists(DATA):
        pytest.skip("no recorded trace in perfbench/tests/data")
    return xtrace.load(DATA)


def test_recorded_trace_has_the_planes_the_reduction_reads(tiny):
    assert tiny.devices == [0]
    names = {n for n, _, _ in tiny.host_spans}
    assert {"pb.window", "pb.tiny.step", "pb.tiny.sleep"} <= names
    assert len([n for n, _, _ in tiny.host_spans
                if n == "pb.tiny.step"]) == 3


def test_recorded_trace_reduces(tiny):
    w = tiny.windowed()
    t0, t1 = w.window()
    window_s = (t1 - t0) * 1e-9
    busy = w.busy_s()
    assert 0 < busy < window_s
    # three steps, one flash-attention forward each. The device's
    # clock runs about 0.1 ms ahead of the host's in this trace, so the
    # first step's operations fall just before ``pb.window`` opens and
    # the cut leaves two (over a real window that offset is nothing).
    assert len(tiny.module_durations_s("tiny_step")) == 3
    seconds, calls = w.kernel_seconds("flash_attention_fwd")
    assert calls == 2 and 0 < seconds < busy
    assert len(w.module_durations_s("tiny_step")) == 2
    assert len(w.module_durations_s("tiny_step",
                                    contains="flash_attention_fwd")) == 2
    assert w.module_durations_s("tiny_step", contains="no_such_op") == []
    by = w.op_seconds()
    assert sum(by.values()) == pytest.approx(busy, rel=0.05)
    gaps = w.idle_gaps()
    assert sum(gaps.values()) == pytest.approx(window_s - busy, rel=1e-6)
    # the sleeps are the longest idle stretches and are charged as such
    assert gaps.get("pb.tiny.sleep", 0) >= 3 * 0.002 * 0.9
    assert w.exposed_collective_s() == (0.0, 0.0)
    bd = w.breakdown()
    assert len(bd["device_ops"]) <= 10 and bd["device_ops"][0][1] > 0


@pytest.mark.parametrize("path", [DATA, DATA_MX])
def test_recorded_traces_reduce_as_under_the_walk(path):
    """On what the chip recorded: the sweep's whole answer is the
    walk's, and what a ``Trace`` keeps between readers is what the
    plain functions give."""
    if not os.path.exists(path):
        pytest.skip("no recorded trace in perfbench/tests/data")
    for tr in (xtrace.load(path), xtrace.load(path).windowed()):
        t0, t1 = tr.window()
        dev = tr.devices[0]
        ops = tr.device_ops[dev]
        want = walk_idle_gaps(ops, tr.host_spans, t0, t1)
        assert len(want) >= 2
        assert tr.idle_gaps() == want
        assert tr.idle_gaps() is tr.idle_gaps()
        assert tr.breakdown()["idle_gaps"] == [
            [n, v] for n, v in sorted(want.items(), key=lambda kv: -kv[1])]
        assert tr.busy_s() == xtrace.total(xtrace.union(
            (s, s + d) for _, s, d in ops)) * 1e-9
        assert tr.op_seconds() == xtrace.time_by_name(ops)
        for kernel in ("flash_attention_fwd", "flash_decode_paged",
                       "no_such_kernel"):
            evs = xtrace.kernel_events(ops, kernel)
            assert tr.kernel_seconds(kernel) == (
                sum(d for _, _, d in evs) * 1e-9, len(evs))
        assert tr.exposed_collective_s() == xtrace.exposed_collective_s(ops)
