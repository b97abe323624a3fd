"""``BENCHMARK.json`` against the contract's static rules, and the
data-driven layout: every cell, configuration, traffic mix and metric
is found by its name in files of its own."""
import importlib
import json
import os
import re

import pytest

from perfbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


@pytest.fixture(scope="module")
def bm():
    return harness.load_json(harness.ROOT, "BENCHMARK.json")


def test_top_level_keys_and_sizes(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(harness.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024
    assert bm["paths"] == ["perfbench"]
    assert isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51
    # a full check with 24 cells has to fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (bm["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(bm["workloads"]) <= 24
    assert 1 <= len(bm["per_layer"]) <= 128
    assert 1 <= len(bm["end_to_end"]) <= 16


def test_names_units_and_lines(bm):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bm[group]]
        assert len(names) == len(set(names)), group
        for n in names:
            assert NAME.match(n), n
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bm["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bm["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for e in bm["workloads"] + bm["configs"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] \
            and "\t" not in e["why"]
    for c in bm["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not k.endswith(("_dim", "_rank")) and "hidden_size" \
                not in k and "intermediate" not in k


def test_cells_configs_and_chips(bm):
    cells = bm["workloads"]
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert all(w["chips"] in (1, 4) for w in cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in bm["configs"]}
    files = [c["file"] for c in bm["configs"]]
    assert len(files) == len(set(files))
    for c in bm["configs"]:
        assert c["file"].startswith("perfbench/")
        cfg = harness.load_json(harness.ROOT, c["file"])
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced_why"])


def test_every_cell_reports_setup_one_more_and_a_layer_metric(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in bm["workloads"]:
        cell = harness.Cell(w["name"], bm)
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names, (w["name"], m["name"])
        # the traffic file says how each end-to-end metric is derived
        for n in names:
            assert n == "setup_s" or n in cell.traffic["end_to_end"]


def test_files_are_found_by_name(bm):
    for w in bm["workloads"]:
        cell = harness.Cell(w["name"], bm)
        assert cell.family() and cell.generator()
        importlib.import_module("perfbench.reference."
                                + cell.config["family"])
    layers = {}
    for m in bm["per_layer"]:
        spec = harness.load_json(harness.HERE, "metrics",
                                 m["name"] + ".json")
        importlib.import_module("perfbench.readers." + spec["reader"])
        layers.setdefault(m["layer"], []).append(m["name"])
    assert len(layers) >= 5


def test_no_benchmark_code_branches_on_a_cell_or_config_name(bm):
    names = [w["name"] for w in bm["workloads"]] \
        + [c["name"] for c in bm["configs"]] \
        + [w["traffic"] for w in bm["workloads"]]
    for root, _, files in os.walk(harness.HERE):
        if os.sep + "tests" in root:
            continue
        for f in files:
            if not f.endswith(".py"):
                continue
            text = open(os.path.join(root, f)).read()
            code = re.sub(r'"""[\s\S]*?"""', "", text)
            code = re.sub(r"#.*", "", code)
            for n in names:
                assert f'"{n}"' not in code and f"'{n}'" not in code, \
                    (f, n)


def test_file_names_use_only_name_characters():
    for root, dirs, files in os.walk(harness.HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), harness.ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_every_cell_has_a_rehearsal_preset(bm):
    """``rehearse.py`` without ``--workload`` reaches every cell: the
    first four by configuration and traffic in ``rehearsal.json``, a
    later one by the file of its own that names it."""
    from perfbench import rehearse

    for w in bm["workloads"]:
        real = harness.Cell(w["name"], bm)
        tiny = rehearse.tiny_cell(w["name"], bm)
        assert tiny.config["hidden_size"] < real.config["hidden_size"]
        assert tiny.traffic != real.traffic
    own = {harness.load_json(harness.HERE, f)["workload"]
           for f in os.listdir(harness.HERE)
           if re.match(r"^rehearsal\..+\.json$", f)}
    assert own <= {w["name"] for w in bm["workloads"]}


# -- what a run says beside its metrics ---------------------------------------

def test_phases_take_an_inner_part_out_of_the_outer_one():
    import time

    ph = harness.Phases()
    t0 = time.perf_counter()
    with ph.timed("read.outer"):
        with ph.timed("read.inner"):
            time.sleep(0.05)
        with ph.timed("read.mxspans"):
            time.sleep(0.02)
    whole = time.perf_counter() - t0
    ph.add("reference", 2.0)
    rows = dict(ph.rows)
    assert list(rows) == ["read.inner", "read.mxspans", "read.outer",
                          "reference"]
    # what ran inside is not counted twice: the parts add up
    assert rows["read.inner"] >= 0.05 and 0 <= rows["read.outer"] < 0.03
    assert sum(rows.values()) - 2.0 == pytest.approx(whole, abs=0.01)
    ph.rows[:0] = [["setup", 90.0], ["window", 51.0]]
    ph.add("read.idle_gaps", 1.25)
    line = ph.line(150.0)
    assert line.startswith("[phases] total_s: 150.00, setup_s: 90.00, "
                           "window_s: 51.00, reference_s: 2.00, "
                           "read.idle_gaps_s: 1.25, read.rest_s: 0.")
    # a reduction under a second is summed, never a part of the run
    assert "read.inner" not in line and "other_s: 5.6" in line


def test_checks_go_into_the_result_line_each_beside_its_limit():
    checks = harness.Checks()
    checks.at_most("mean_gap", 0.004, 0.0075)
    checks.equal("preemptions", 0, 0)
    checks.at_least("tokens", 3, 4)
    assert not checks.ok
    assert json.loads(json.dumps(checks.as_dict())) == {
        "mean_gap": [0.004, "<= 0.0075"], "preemptions": [0, "== 0"],
        "tokens": [3, ">= 4"]}
