"""BENCHMARK.json against the contract's shape, and every name it uses
resolving to a file of its own."""
import json
import os
import re

import pytest

from bench import harness

MAN = harness.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in MAN["paths"])
    assert len(json.dumps(MAN)) < 64 * 1024


@pytest.mark.parametrize("key", ["configs", "workloads", "end_to_end",
                                 "per_layer"])
def test_names_and_units(key):
    names = [e["name"] for e in MAN[key]]
    assert len(names) == len(set(names))
    for e in MAN[key]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k]


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert w in moved.get("workloads", cells), (m["name"], w)


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for w in MAN["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(MAN, w["name"],
                                                       "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(MAN, w["name"], "per_layer")


def test_every_name_is_a_file_of_its_own():
    bench = harness.BENCH
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    for c in MAN["configs"]:
        cfg = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert os.path.isfile(os.path.join(bench, "entries",
                                           cfg["entry"] + ".py"))
        assert all(NAME.match(k) for k in c["reduced"])
        assert "check" in cfg
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in MAN["workloads"]:
        assert w["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(bench, "traffic",
                                           w["traffic"] + ".json"))
    for m in MAN["per_layer"]:
        mod = harness.load_module("metrics", m["name"])
        assert callable(mod.read)
