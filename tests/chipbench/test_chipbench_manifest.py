"""BENCHMARK.json is well-formed and every file it names is there."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in manifest[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in manifest["end_to_end"])


def test_every_cell_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for c in configs.values():
        assert os.path.isfile(os.path.join(ROOT, c["file"])), c["file"]
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
    used = set()
    for w in manifest["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        with open(os.path.join(ROOT, "chipbench", "workloads",
                               w["name"] + ".json")) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["traffic"] == w["traffic"]
        with open(os.path.join(ROOT, "chipbench", "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        for kind, name in (("jobs", cell["job"]),
                           ("generators", traffic["generator"])):
            assert os.path.isfile(os.path.join(ROOT, "chipbench", kind,
                                               name + ".py"))
        used.add(w["config"])
    assert used == set(configs)


def test_each_per_layer_metric_moves_a_metric_its_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:      # setup_s, one more end-to-end, one per-layer
        assert sum(cell in ws for ws in e2e.values()) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_four_chip_cells_are_at_most_a_quarter_or_one(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in manifest["workloads"])
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_every_per_layer_metric_has_one_reader(manifest):
    import sys
    sys.path.insert(0, ROOT)
    from chipbench.run import readers
    found = readers()
    assert {m["name"] for m in manifest["per_layer"]} <= set(found)
