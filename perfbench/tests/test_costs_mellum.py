"""``perfbench/costs_mellum.py`` on hand-counted tiny cases: the
window's visible-pair count below, at and above the window; the step,
the attention kernels and the grouped products; and no cost above what
a dense, unwindowed count gives."""
import pytest

from perfbench import costs_mellum as cm

CFG = {"hidden_size": 8, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 4,
       "moe_intermediate_size": 6, "num_experts_published": 5,
       "vocab_size": 10, "sliding_window": 3, "torch_dtype": "bfloat16",
       "layer_types": ["sliding_attention", "full_attention"]}


def brute(T, window):
    return sum(1 for i in range(T) for j in range(T)
               if j <= i and (window is None or j > i - window))


@pytest.mark.parametrize("T,window", [(2, 3), (3, 3), (4, 3), (9, 3),
                                      (5, None), (1, 1), (8, 1)])
def test_visible_pairs_below_at_and_above_the_window(T, window):
    assert cm.visible_pairs(T, window) == brute(T, window)
    assert cm.visible_pairs(T, window) <= T * (T + 1) // 2


def test_the_cells_average_keys_a_query():
    """At 8,192 positions and a window of 1,024 a sliding layer's query
    sees 960 keys on average, a full layer's 4,096.5 (the issue's
    arithmetic)."""
    assert cm.visible_pairs(8192, 1024) / 8192 == pytest.approx(960.06,
                                                                abs=0.01)
    assert cm.visible_pairs(8192) / 8192 == 4096.5


def test_attention_hand_count():
    # two sequences of 4: sliding (window 3) 1+2+3+3 = 9, full 10
    work = {"tokens": 8, "sequences": 2, "predicted": 6}
    assert cm.attention_pairs(CFG, work) == 2 * (9 + 10)
    # QK^T and PV, 2 FLOPs a multiply-add, head_dim 4, 2 query heads
    assert cm.attention_fwd_flops(CFG, work) == 4 * 2 * 4 * 38
    cost = cm.flash_attention_train(CFG, work)
    assert cost["flops"] == 12 * 2 * 4 * 38
    # rows x layers x head_dim x (5 x 2 query + 6 x 1 kv heads) x 2 B
    assert cost["bytes"] == 8 * 2 * 4 * 16 * 2


def test_grouped_products_hand_count():
    counts = {"pairs": 7, "touched": 3}
    fwd_dgrad, wgrad = cm.moe_experts_train(CFG, counts), \
        cm.moe_wgrad(CFG, counts)
    assert fwd_dgrad["flops"] == 2 * wgrad["flops"] == 12 * 8 * 6 * 7
    assert wgrad["bytes"] == 2 * (3 * 8 * 6 * 3 + 3 * 14 * 7)
    assert fwd_dgrad["bytes"] == 2 * 2 * (3 * 8 * 6 * 3 + 2 * 14 * 7)
    assert cm.moe_wgrad(CFG, {}) == {"flops": 0, "bytes": 0}


def test_step_hand_count_and_the_dense_ceiling():
    work = {"tokens": 8, "sequences": 2, "predicted": 6, "pairs": 7}
    dense = 2 * 8 * 4 * (2 + 1) + 8 * 5      # q, o, k, v and the router
    fwd = (2 * dense * 2 * 8 + 4 * 2 * 4 * 38 + 6 * 8 * 6 * 7
           + 2 * 8 * 10 * 6)
    assert cm.train_step(CFG, work)["flops"] == 3 * fwd
    # no cost exceeds a dense, unwindowed count: every pair of every
    # token on a held expert, every layer full, every position predicted
    top = dict(work, pairs=8 * 2 * 5, predicted=8)
    full = dict(CFG, layer_types=["full_attention"] * 2)
    assert cm.train_step(CFG, work)["flops"] \
        < cm.train_step(full, top)["flops"]
    assert cm.flash_attention_train(CFG, work)["flops"] \
        < cm.flash_attention_train(full, work)["flops"]


def test_the_cells_step_is_the_issues_arithmetic():
    """497 MFLOP a token forward at the cell's shapes with 2 of a
    token's 8 pairs held: projections 170, windowed scores 47 + full
    67, held experts 99, head slice 113."""
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = json.load(open(os.path.join(here, "configs",
                                      "mellum2_12b.json")))
    tokens = 4 * 8192
    work = {"tokens": tokens, "sequences": 4, "predicted": tokens - 4,
            "pairs": tokens * 2 * 4}
    per_token = cm.train_step(cfg, work)["flops"] / 3 / tokens
    assert per_token == pytest.approx(497e6, rel=0.005)


# -- the readers that lay the program's counts over the generator's work ----

class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def kernel_seconds(self, kernel):
        return (self.seconds.get(kernel, 0.0), 1)


def _ctx(counts_by_span, kernel_s=None, window_s=2.0):
    import types
    from perfbench import mxspans
    spans = [mxspans.Span("mx.train_step", 10 * i, 10 * i + 5, c)
             for i, c in enumerate(counts_by_span)]
    return types.SimpleNamespace(
        config=CFG, work={"tokens": 8, "sequences": 2, "predicted": 6},
        window_s=window_s, chips=1, trace=_Trace(kernel_s or {}),
        peaks={"flops_bf16": 1e3, "hbm_bytes_per_s": 1e9},
        _mxspans=mxspans.Spans([spans], (0, 100), None))


def test_readers_lay_span_counts_over_the_work():
    from perfbench.readers import (kernel_roofline_mixed,
                                   model_flops_share_counted,
                                   span_count_ratio)
    steps = [{"moe_pairs": 3, "moe_pairs_max": 2, "counted_steps": 1},
             {}, {"moe_pairs": 4, "moe_pairs_max": 3, "counted_steps": 2}]
    ctx = _ctx(steps, {"flash_attention_fwd": 1.0,
                       "flash_attention_dkv": 3.0})
    spec = {"costs": "costs_mellum", "cost": "train_step",
            "counts": {"pairs": ["mx.train_step", "moe_pairs"]}}
    want = cm.train_step(CFG, dict(ctx.work, pairs=7))["flops"]
    assert model_flops_share_counted.read(spec, ctx) == pytest.approx(
        100.0 * want / (2.0 * 1e3))
    # the attention kernels need no count: shapes alone
    spec = {"kernels": ["flash_attention_fwd", "flash_attention_dkv"],
            "costs": "costs_mellum", "cost": "flash_attention_train"}
    flops = cm.flash_attention_train(CFG, ctx.work)["flops"]
    assert kernel_roofline_mixed.read(spec, ctx) == pytest.approx(
        100.0 * (flops / 1e3) / 4.0)
    ratio = {"span": "mx.train_step", "count": "moe_pairs_max",
             "of": "moe_pairs", "scale": 16.0}
    assert span_count_ratio.read(ratio, ctx) == pytest.approx(16 * 5 / 7)
    per_step = {"span": "mx.train_step", "count": "moe_pairs",
                "of": "counted_steps", "scale": 0.5}
    assert span_count_ratio.read(per_step, ctx) == pytest.approx(7 / 3 / 2)


def test_readers_find_nothing_in_a_program_without_the_counts():
    """The parent's trace: no kernel of that name, no count on the
    spans. Every reader returns None and raises nothing."""
    from perfbench.readers import (kernel_roofline_mixed,
                                   model_flops_share_counted,
                                   span_count_ratio)
    ctx = _ctx([{}, {}])
    counted = {"costs": "costs_mellum", "cost": "train_step",
               "counts": {"pairs": ["mx.train_step", "moe_pairs"]}}
    assert model_flops_share_counted.read(counted, ctx) is None
    assert kernel_roofline_mixed.read(
        dict(counted, kernels=["moe_grouped_matmul"]), ctx) is None
    assert span_count_ratio.read(
        {"span": "mx.train_step", "count": "moe_pairs_max",
         "of": "moe_pairs"}, ctx) is None
