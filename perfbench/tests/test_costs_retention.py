"""``perfbench/costs_retention.py`` against hand counts at the published
widths: the state is counted at the 8,256 distinct products the
mathematics needs, so the program's padded layout (8,320) reads a LOWER
share of the roofline, never one above 100%."""
from perfbench import costs, costs_retention, harness, peaks


def cfg():
    return harness.load_json(harness.HERE, "configs", "brumby_14b.json")


def test_the_state_is_counted_at_the_maps_own_width():
    c = cfg()
    assert costs_retention.feature_width(c) == 128 * 129 // 2 == 8256
    # 8 kv heads x 8,256 x (128 values + the normaliser's 1) x float32
    assert costs_retention.retention_state_bytes(c) \
        == 8 * 8256 * 129 * 4 == 34_080_768


def test_a_step_moves_the_state_twice_and_is_held_to_hbm():
    c = cfg()
    got = costs_retention.COSTS["power_retention_step"](c, {"ssm_rows": 20})
    rows = 20 * 6
    small = (2 * 40 + 2 * 8) * 128 * 2 + 4 * 8
    assert got["bytes"] == rows * (2 * 34_080_768 + small)
    assert got["flops"] == rows * 2 * 8256 * 128 * 48
    assert round(got["bytes"] / rows / 1e6, 1) == 68.2
    pk = peaks.peaks_for("TPU v5 lite")
    least, leg = costs.roofline_seconds(got, pk)
    assert leg == "hbm"
    # a kernel that moves the padded layout (8,320 rows of phi, z's 72
    # rows of 128) at the peak rate reads under 100%
    moved = rows * (2 * 8 * (65 * 128 * 128 + 72 * 128) * 4 + small)
    assert 99.0 < 100.0 * least / (moved / 819e9) < 100.0
    assert costs_retention.COSTS["power_retention_step"](c, {}) \
        == {"flops": 0, "bytes": 0}


def test_the_chunked_form_is_held_to_the_mxu():
    c = cfg()
    got = costs_retention.COSTS["power_retention_chunked"](
        c, {"scan_tokens": 1000})
    assert got["flops"] == 6 * 1000 * 2 * 8256 * 128 * (40 + 8)
    assert round(got["flops"] / 6 / 1000 / 1e6) == 101
    assert got["bytes"] == 6 * 1000 * ((2 * 40 + 2 * 8) * 128 * 2 + 32)
    least, leg = costs.roofline_seconds(got, peaks.peaks_for("TPU v5 lite"))
    assert leg == "compute"


def test_the_cells_metric_files_name_costs_that_exist():
    import importlib

    for name in ("power_retention_step_roofline.retn",
                 "power_retention_chunked_roofline.retn"):
        spec = harness.load_json(harness.HERE, "metrics", name + ".json")
        table = importlib.import_module("perfbench." + spec["costs"]).COSTS
        out = table[spec["cost"]](cfg(), {k: 7 for k in spec["counts"]})
        assert out["bytes"] > 0 and out["flops"] > 0
    gib = harness.load_json(harness.HERE, "metrics",
                            "retention_state_gib.retn.json")
    assert gib["over"] == ["state_pool_bytes"]
    for twin in ("decode_dev_ms_p50", "read_after_done_ms_p50",
                 "read_after_done_ms_p99", "read_after_done_ms_max",
                 "launch_lag_ms_p99"):
        mine = harness.load_json(harness.HERE, "metrics",
                                 twin + ".retn.json")
        theirs = harness.load_json(harness.HERE, "metrics",
                                   twin + ".serve.json")
        assert mine.pop("contains") == "power_retention_step"
        assert theirs.pop("contains") == "flash_decode_paged"
        assert mine == theirs
