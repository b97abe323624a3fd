"""The afmoe cost functions and the reader that hands them the program's
own counts: shares of a roofline can never pass 100% by construction of
the kernel, so a reading above it means a function here counts too
much."""
import types

import pytest

from perfbench import costs, costs_afmoe, harness, peaks
from perfbench.readers import kernel_roofline_counted as reader


@pytest.fixture(scope="module")
def cfg():
    return harness.load_json(harness.HERE, "configs", "trinity_large.json")


def test_moe_experts_counts_weights_once_a_touched_expert(cfg):
    one = costs_afmoe.moe_experts(cfg, {"pairs": 1, "touched": 1})
    d = i = 3072
    assert one["flops"] == 6 * d * i
    assert one["bytes"] == 2 * (3 * d * i + 2 * (d + i))
    # a second row on the same expert adds its rows, not the weights
    two = costs_afmoe.moe_experts(cfg, {"pairs": 2, "touched": 1})
    assert two["bytes"] - one["bytes"] == 2 * 2 * (d + i)
    both = costs_afmoe.moe_experts(cfg, {"pairs": 1, "touched": 1,
                                         "prefill_pairs": 1,
                                         "prefill_touched": 1})
    assert both["flops"] == 2 * one["flops"]
    assert both["bytes"] == 2 * one["bytes"]
    # decode is held to the HBM, a long prefill to the MXU
    pk = peaks.peaks_for("TPU v5 lite")
    assert costs.roofline_seconds(costs_afmoe.moe_experts(
        cfg, {"pairs": 24, "touched": 17}), pk)[1] == "hbm"
    assert costs.roofline_seconds(costs_afmoe.moe_experts(
        cfg, {"prefill_pairs": 32 * 2000, "prefill_touched": 32}),
        pk)[1] == "compute"
    assert costs_afmoe.moe_experts(cfg, {}) == {"flops": 0, "bytes": 0}


def test_windowed_decode_reads_the_window_on_sliding_layers(cfg):
    kv = costs_afmoe.kv_bytes_per_token_layer(cfg)
    assert kv == 4096                       # 2 x 8 heads x 128 x bf16
    long = costs_afmoe.flash_decode_paged_windowed(
        cfg, {"ctx": 10000, "window_ctx": 4096})
    assert long["bytes"] == kv * (4 * 4096 + 10000)
    assert long["flops"] == 4 * 48 * 128 * (4 * 4096 + 10000)
    short = costs_afmoe.flash_decode_paged_windowed(
        cfg, {"ctx": 1000, "window_ctx": 1000})
    assert short["bytes"] == kv * 5 * 1000
    # the accepted cost function counts the whole context in every
    # layer: on this model it would read 5 x 10000 where 26384 are read
    whole = costs.flash_decode_paged(
        dict(cfg, hidden_size=48 * 128, num_attention_heads=48),
        {"context_tokens": 10000})
    assert whole["bytes"] > 1.8 * long["bytes"]


class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def kernel_seconds(self, name):
        return self.seconds.get(name, 0.0), 1


class _Span:
    def __init__(self, counts):
        self.counts = counts


class _Spans:
    def __init__(self, by_name):
        self.by_name = by_name

    def named(self, name, whole=False):
        return self.by_name.get(name, [])


SPEC = {"kernels": ["moe_grouped_matmul"], "costs": "costs_afmoe",
        "cost": "moe_experts",
        "counts": {"pairs": ["mx.serve_emit", "pairs"],
                   "touched": ["mx.serve_emit", "touched"],
                   "prefill_pairs": ["mx.serve_emit", "prefill_pairs"]}}


def _ctx(cfg, seconds, spans):
    ctx = types.SimpleNamespace(
        config=cfg, chips=1, trace=_Trace(seconds),
        peaks=peaks.peaks_for("TPU v5 lite"))
    ctx._mxspans = _Spans(spans)
    return ctx


def test_reader_sums_the_spans_counts_over_the_window(cfg):
    ticks = [_Span({"pairs": 24, "touched": 17})] * 10 \
        + [_Span({"pairs": 20, "touched": 15, "prefill_pairs": 100})]
    need = costs_afmoe.moe_experts(cfg, {
        "pairs": 260, "touched": 185, "prefill_pairs": 100})
    least = need["bytes"] / 819e9
    ctx = _ctx(cfg, {"moe_grouped_matmul": 2 * least},
               {"mx.serve_emit": ticks})
    assert reader.read(SPEC, ctx) == pytest.approx(50.0)
    # at the HBM's speed the share is 100, never more
    ctx = _ctx(cfg, {"moe_grouped_matmul": least},
               {"mx.serve_emit": ticks})
    assert reader.read(SPEC, ctx) == pytest.approx(100.0)


def test_reader_finds_nothing_on_a_program_without_the_kernel(cfg):
    # the parent of the PR that brought the kernel: no kernel time
    assert reader.read(SPEC, _ctx(cfg, {}, {})) is None
    # the kernel ran but the spans carry no count
    assert reader.read(SPEC, _ctx(cfg, {"moe_grouped_matmul": 1.0},
                                  {"mx.serve_emit": [_Span({})]})) is None


def test_metric_files_name_the_costs_and_spans_that_exist():
    for name in ("moe_experts_roofline.afmoe",
                 "flash_decode_paged_roofline.afmoe"):
        spec = harness.load_json(harness.HERE, "metrics", name + ".json")
        assert spec["reader"] == "kernel_roofline_counted"
        assert spec["cost"] in costs_afmoe.COSTS
        for span, _ in spec["counts"].values():
            assert span in ("mx.serve_emit", "mx.serve_dispatch")
