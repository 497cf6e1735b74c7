"""BENCHMARK.json keeps to the contract's shapes and names."""
import importlib
import json
import os
import re

import pytest

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= len(bench["command"]) <= 32
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_and_units(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(bench["paths"][0] + "/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    all_names = names + [w["name"] for w in bench["workloads"]] + [
        m["name"] for m in metrics]
    assert len(all_names) == len(set(all_names))
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_every_name_finds_its_file(bench):
    from chipbench import cells as C
    used = set()
    for w in bench["workloads"]:
        cell = C.find_cell(w["name"], bench)
        used.add(w["config"])
        assert cell.geometry.max_seq > 0
        importlib.import_module(
            f"chipbench.configs.{cell.config['reference']}")
    assert used == {c["name"] for c in bench["configs"]}
    for m in bench["per_layer"]:
        assert callable(importlib.import_module(
            f"chipbench.metrics.{m['name']}").read)


def test_rooflines_are_named_for_their_kernel(bench):
    for m in bench["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
            assert m["name"].endswith("_roofline") or "mfu" in m["name"]
