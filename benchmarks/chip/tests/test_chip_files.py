"""Every cell, configuration, traffic and metric of ``BENCHMARK.json`` is
found by name in files of its own."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_loads_by_name(run_mod, cell):
    c = run_mod.find_cell(cell)
    assert os.path.exists(os.path.join(BENCH, "drivers", c.driver + ".py"))
    assert c.limits and all(v >= 0 for v in c.limits.values())
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports at least one per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names


@pytest.mark.parametrize("conf", BENCHMARK["configs"], ids=lambda c: c["name"])
def test_config_file_and_reduced_keys(conf):
    data = json.load(open(os.path.join(ROOT, conf["file"])))
    assert sorted(data["reduced"]) == sorted(conf["reduced"])
    assert data["source"] and data["deployment"] and data["assumed"]
    for key in conf["reduced"]:
        assert key in data, f"{key} is cut but the file does not state it"
    files = [c["file"] for c in BENCHMARK["configs"]]
    assert files.count(conf["file"]) == 1


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(run_mod, metric):
    mod = run_mod.load_module(os.path.join(BENCH, "metrics", metric["name"] + ".py"), "m")
    assert callable(mod.read)


def test_names_and_units():
    entries = BENCHMARK["configs"] + BENCHMARK["workloads"] + BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m["unit"]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCHMARK[kind]]
        assert len(names) == len(set(names))
