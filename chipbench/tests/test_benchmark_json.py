"""BENCHMARK.json against the shape the benchmark's contract sets, and
every file it names found where the harness looks for it."""
import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51


def test_configs(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
        assert NAME.match(c["name"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert key in cfg["published"]


def test_cells_name_their_files(bench):
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    four = 0
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
        for sub in ("traffic", "limits"):
            key = w["traffic"] if sub == "traffic" else w["name"]
            assert os.path.isfile(os.path.join(BENCH, sub, key + ".json"))
    assert four <= max(1, len(bench["workloads"]) // 2)
    assert {w["config"] for w in bench["workloads"]} == configs


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        layers.add(m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for cell in cells:
        assert any(cell in m["workloads"] for m in bench["per_layer"])


def test_step_memory_metric_and_its_two_parts(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    step = e2e["step_hbm_gib"]
    assert (step["unit"], step["better"], step["source"]) == (
        "GiB", "lower", "host_clock")
    assert "workloads" not in step          # every cell reports it
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in ("state_gib", "transient_gib"):
        m = per_layer[name]
        # read from the compiler by the benchmark itself, as the sum
        assert (m["layer"], m["moves"], m["unit"], m["source"]) == (
            "device memory", "step_hbm_gib", "GiB", "host_clock")
        assert m["workloads"] == cells


def test_each_cell_reports_what_its_per_layer_metrics_move(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads",
                                                    m["workloads"]))


def test_collectives_are_read_only_in_cells_with_several_chips(bench):
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    (coll,) = [m for m in bench["per_layer"]
               if m["name"] == "collective_ms_per_step"]
    assert all(chips[c] > 1 for c in coll["workloads"])


def test_one_pod_h4_traffic_is_the_pod_cells_on_one_pod():
    def load(name):
        with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
            return json.load(f)
    one, four = load("ace-h4-1pod"), load("ace-h4")
    assert (one["pods"], four["pods"]) == (1, 4)
    assert dict(one, pods=4) == four
