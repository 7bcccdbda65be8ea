"""``BENCHMARK.json`` against the files it names and the contract's
characters."""

import importlib
import json
import os
import re

import pytest

from chipbench.setup_sources import CHECKOUT

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")

with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json")) < 65536


def test_names_units_and_lines():
    names = ([m["name"] for m in METRICS]
             + [w["name"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]])
    for name in names:
        assert NAME.fullmatch(name), name
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        assert len({x["name"] for x in group}) == len(group)
    for m in METRICS:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for entry in BENCH["workloads"] + BENCH["configs"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_entries_have_just_the_contracts_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_file_a_cell_needs_exists_and_agrees(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    config = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert config["file"].startswith("chipbench/configs/")
    with open(os.path.join(CHECKOUT, config["file"])) as f:
        sizes = json.load(f)
    with open(os.path.join(CHECKOUT, "chipbench", "workloads",
                           f"{cell}.json")) as f:
        own = json.load(f)
    for key in ("config", "traffic", "chips"):
        assert own[key] == entry[key]
    family = importlib.import_module(f"chipbench.families.{sizes['family']}")
    assert callable(family.build) and "config" in family.REHEARSAL
    spelling = importlib.import_module(
        f"chipbench.spellings.{own['spelling']}")
    assert callable(spelling.build)
    # the cell reports setup_s, another end-to-end metric and a per-layer
    # metric, and a per-layer metric only where the metric it moves is
    mine = lambda group: {m["name"] for m in BENCH[group]
                          if cell in m.get("workloads", [cell])}
    assert "setup_s" in mine("end_to_end") and len(mine("end_to_end")) > 1
    assert mine("per_layer")
    for m in BENCH["per_layer"]:
        if m["name"] in mine("per_layer"):
            assert m["moves"] in mine("end_to_end"), (cell, m["name"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_its_reader(metric):
    package = "layer_metrics" if "layer" in metric else "e2e_metrics"
    module = importlib.import_module(f"chipbench.{package}.{metric['name']}")
    assert module.UNIT == metric["unit"] and callable(module.read)
    if "layer" in metric:
        assert module.LAYER == metric["layer"]
        assert module.MOVES == metric["moves"]


def test_layers_are_perf_mds():
    with open(os.path.join(CHECKOUT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert f"| {layer} |" in perf, layer


def test_nothing_of_the_repos_scripts_is_imported():
    root = os.path.join(CHECKOUT, "chipbench")
    for folder, _, files in os.walk(root):
        if os.path.basename(folder) == "tests":
            continue
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                assert not re.search(
                    r"^\s*(import|from)\s+(bench|chip_smoke|__graft_entry__"
                    r"|benchmarks)\b", text, re.M), name


def test_run_py_holds_no_cell_family_or_metric_name():
    with open(os.path.join(CHECKOUT, "chipbench", "run.py")) as f:
        text = f.read()
    names = ([m["name"] for m in METRICS if m["name"] != "setup_s"]
             + [w["name"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]]
             + ["gpt", "resnet", "gspmd", "shard_map"])
    for name in names:
        assert not re.search(rf"\b{re.escape(name)}\b", text), name
