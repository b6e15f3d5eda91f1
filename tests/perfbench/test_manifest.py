"""``BENCHMARK.json`` keeps to the contract's letters, and everything it
names is a file of its own under ``paths``."""

import importlib
import json
import os
import re

import pytest

from toybench import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    M = json.load(f)
CELLS = {w["name"]: w for w in M["workloads"]}
E2E = {m["name"]: m for m in M["end_to_end"]}


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1


@pytest.mark.parametrize("entry", M["configs"] + M["workloads"]
                         + M["end_to_end"] + M["per_layer"],
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for key in ("why", "layer"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                and "\t" not in entry[key]


def test_names_are_unique():
    for group in (M["configs"], M["workloads"],
                  M["end_to_end"] + M["per_layer"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_bounds_and_sources(metric):
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")
    for cell in metric.get("workloads", []):
        assert cell in CELLS


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_cells_report_the_metric_it_moves(metric):
    assert "bound" not in metric
    moved = E2E[metric["moves"]]
    assert metric["workloads"]
    for cell in metric["workloads"]:
        assert cell in CELLS and reports(moved, cell)


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader_is_a_file_of_its_own(metric):
    path = os.path.join(REPO, "perfbench", "metrics", f"{metric['name']}.py")
    assert os.path.isfile(path)
    assert callable(importlib.import_module(
        f"perfbench.metrics.{metric['name']}").read)


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell_is_made_of_files_and_reports_enough(cell):
    configs = {c["name"]: c for c in M["configs"]}
    cfg = configs[cell["config"]]
    assert cfg["file"].startswith("perfbench/")
    for rel in (cfg["file"], f"perfbench/traffic/{cell['traffic']}.json",
                f"perfbench/limits/{cell['name']}.json"):
        assert os.path.isfile(os.path.join(REPO, rel)), rel
    assert cell["chips"] in (1, 4)
    others = [m for m in M["end_to_end"]
              if m["name"] != "setup_s" and reports(m, cell["name"])]
    layers = [m for m in M["per_layer"] if reports(m, cell["name"])]
    assert others and layers and reports(E2E["setup_s"], cell["name"])


def test_rooflines_have_a_whole_step_mfu_beside_them():
    for m in M["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            beside = [o for o in M["per_layer"]
                      if "mfu" in o["name"] and o["moves"] == m["moves"]
                      and set(m["workloads"]) <= set(o["workloads"])]
            assert beside, m["name"]


def test_every_configuration_is_used_and_has_its_reference():
    used = {w["config"] for w in M["workloads"]}
    for c in M["configs"]:
        assert c["name"] in used
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for part in ("adapters", "reference", "counts", "weights"):
            assert os.path.isfile(os.path.join(
                REPO, "perfbench", part, f"{cfg['arch']}.py"))


def test_command_and_paths_stay_inside_the_benchmark():
    assert M["command"] == ["python3", "perfbench/run.py"]
    for p in M["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
        assert not p.startswith("/") and ".." not in p
