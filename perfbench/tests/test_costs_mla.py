"""``perfbench/costs_mla.py`` against hand counts at the published
widths: a cached row is read once, at its published width; a padded or
a doubled pool reads a LOWER share of the roofline, never one above
100%."""
from perfbench import costs, costs_mla, harness, peaks


def cfg():
    return harness.load_json(harness.HERE, "configs", "sarvam_105b.json")


def test_a_cached_row_is_counted_once_at_its_published_width():
    c = cfg()
    assert costs_mla.latent_row_bytes(c) == (512 + 64) * 2 == 1152
    got = costs_mla.COSTS["flash_decode_paged_latent"](c, {"ctx": 1000})
    # 5 layers x 1000 cached positions; every one of 64 heads scores
    # the row's 576 and mixes its 512, 2 FLOPs each
    assert got["bytes"] == 5 * 1000 * 1152
    assert got["flops"] == 5 * 1000 * 2 * 64 * (576 + 512)
    assert got["flops"] / got["bytes"] == 2 * 64 * 1088 / 1152
    assert round(got["flops"] / got["bytes"]) == 121
    assert costs_mla.COSTS["flash_decode_paged_latent"](c, {}) \
        == {"flops": 0, "bytes": 0}


def test_the_sweep_is_held_to_hbm_and_a_wider_pool_reads_lower():
    """At 121 FLOP/byte the v5e's HBM is the longer leg (ridge 240). A
    kernel that moves exactly the counted bytes at the peak rate reads
    100%; one whose pool is padded to 640 moves 10/9 of them and reads
    90%, one that stored values beside keys 50%: never above 100."""
    c = cfg()
    pk = peaks.peaks_for("TPU v5 lite")
    cost = costs_mla.COSTS["flash_decode_paged_latent"](c, {"ctx": 10 ** 6})
    least, leg = costs.roofline_seconds(cost, pk)
    assert leg == "hbm"
    assert abs(least - cost["bytes"] / 819e9) < 1e-12
    assert cost["flops"] / 197e12 < 0.51 * least     # the MXU at half
    for width, share in ((576, 100.0), (640, 90.0), (1152, 50.0)):
        moved = 5 * 10 ** 6 * width * 2
        assert abs(100.0 * least / (moved / 819e9) - share) < 1e-9


def test_the_cells_metric_files_name_costs_that_exist():
    import importlib

    for name in ("flash_decode_latent_roofline.mla",
                 "moe_experts_roofline.mla"):
        spec = harness.load_json(harness.HERE, "metrics", name + ".json")
        table = importlib.import_module("perfbench." + spec["costs"]).COSTS
        out = table[spec["cost"]](cfg(), {k: 7 for k in spec["counts"]})
        assert out["bytes"] > 0 and out["flops"] > 0
    per = harness.load_json(harness.HERE, "metrics",
                            "kv_latent_bytes_per_token.mla.json")
    assert per["over"] == ["latent_pool_bytes"]
    assert per["under"] == ["latent_pool_tokens"]
