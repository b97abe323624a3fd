"""The trace-to-metrics reduction: interval arithmetic on a synthetic
timeline, then the same functions on a small recorded TPU trace
(``perfbench/tests/data/tiny_v5e.xplane.pb``: three steps of a jitted
program holding one ``flash_attention_fwd`` Pallas call and a matmul,
with ``pb.*`` host spans around them)."""
import os

import pytest

from perfbench import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_v5e.xplane.pb")


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
