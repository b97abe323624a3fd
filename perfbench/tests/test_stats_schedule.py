"""Percentile and TPOT-gap arithmetic on a synthetic timeline, and the
schedule's determinism."""
import copy
import json

import pytest

from perfbench import harness, schedule, stats


def test_percentile_interpolates_linearly():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 80) == pytest.approx(42.0)
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile([7.0], 80) == 7.0
    assert stats.percentile([], 80) is None
    # order does not matter
    assert stats.percentile(xs[::-1], 80) == pytest.approx(42.0)


def test_ttft_counts_from_due_time():
    # due at 10.0, submitted late at 10.2, first token at 10.5
    assert stats.ttft_ms(10.0, 10.5) == pytest.approx(500.0)


def test_tpot_counts_only_gaps_inside_the_window():
    # tokens every 0.1 s from 9.5 to 11.4; window [10, 11]
    times = [9.5 + 0.1 * i for i in range(20)]
    v = stats.tpot_ms(times, 10.0, 11.0, min_gaps=5)
    assert v == pytest.approx(100.0)
    # 11 tokens inside -> 10 gaps; asking for 16 leaves the request out
    assert stats.tpot_ms(times, 10.0, 11.0, min_gaps=16) is None
    # a request admitted in the lead-in counts with its in-window gaps
    lead_in = [8.0 + 0.25 * i for i in range(14)]   # 8.0 .. 11.25
    assert stats.tpot_ms(lead_in, 10.0, 11.0, 4) == pytest.approx(250.0)
    # a stall shows: one 0.5 s gap among 0.1 s gaps
    stall = [10.0, 10.1, 10.2, 10.7, 10.8]
    assert stats.tpot_ms(stall, 10.0, 11.0, 4) == pytest.approx(200.0)


def chat():
    return harness.load_json(harness.HERE, "traffic", "chat.json")


def test_schedule_ignores_the_run_seed_and_follows_traffic_seed():
    t = chat()
    a = schedule.open_loop(t, 51)
    b = schedule.open_loop(copy.deepcopy(t), 51)
    # byte-identical: nothing but the traffic file goes in (the run's
    # --seed is not even an argument)
    assert json.dumps(a) == json.dumps(b)
    t2 = dict(t, traffic_seed=t["traffic_seed"] + 1)
    c = schedule.open_loop(t2, 51)
    assert json.dumps(a) != json.dumps(c)


def test_schedule_is_prefix_stable_and_split_by_the_window():
    t = chat()
    long = schedule.open_loop(t, 51)
    short = schedule.open_loop(t, 10)
    assert long[:len(short)] == short
    assert all(-t["lead_in_s"] <= r["due"] < 51 for r in long)
    assert [r["due"] for r in long] == sorted(r["due"] for r in long)
    lead = [r for r in long if r["due"] < 0]
    win = [r for r in long if r["due"] >= 0]
    s = schedule.open_loop_summary(long, 51)
    assert s["requests_lead_in"] == len(lead) > 0
    assert s["requests_window"] == len(win) > 0
    for r in long:
        assert t["prompt_tokens"]["min"] <= r["prompt"] \
            <= t["prompt_tokens"]["max"]
        assert t["output_tokens"]["min"] <= r["output"] \
            <= t["output_tokens"]["max"]
        assert r["prompt"] + r["output"] <= t["server"]["max_len"]


def test_closed_loop_sessions_fit_the_server_and_repeat():
    t = harness.load_json(harness.HERE, "traffic", "reason.json")
    a, b = schedule.closed_loop(t), schedule.closed_loop(t)
    assert json.dumps(a) == json.dumps(b)
    assert len(a["initial"]) == t["clients"] == t["server"]["batch_slots"]
    pool_tokens = (t["server"]["num_blocks"] - 1) * 16
    assert sum(s["context"] for s in a["initial"]) < pool_tokens
    for s in a["initial"] + a["replacements"]:
        assert s["context"] <= t["server"]["max_prompt_len"]
        assert s["context"] + s["remaining"] <= t["server"]["max_len"]
    fin = a["initial"][:t["finishing"]]
    assert all(not s["sampled"] for s in fin)
    assert all(s["remaining"] <= t["finishing_remaining"]["max"]
               for s in fin)
