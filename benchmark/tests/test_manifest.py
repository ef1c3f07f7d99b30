"""BENCHMARK.json against the contract's limits and against the files the
harness finds by its names."""

import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_is_within_the_contract_and_matches_the_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmark"] and 1 <= m["run_seconds"] <= 51
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"]: w for w in m["workloads"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
        assert 1 <= len(c["why"]) <= 200
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in m["workloads"])
    pairs = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        with open(os.path.join(ROOT, "benchmark", "workloads",
                               w["name"] + ".json")) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        importlib.import_module("benchmark.drivers." + cell["driver"])
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = set()
    for kind, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                       "source"}),
                       ("per_layer", {"name", "unit", "better", "source",
                                      "layer", "moves"})):
        for x in m[kind]:
            assert set(x) - {"workloads"} == keys, x
            assert NAME.match(x["name"]) and UNIT.match(x["unit"])
            assert x["better"] in ("lower", "higher")
            assert x["name"] not in names
            names.add(x["name"])
            assert set(x.get("workloads", cells)) <= set(cells)
            with open(os.path.join(ROOT, "benchmark", "metrics",
                                   x["name"] + ".json")) as f:
                spec = json.load(f)
            importlib.import_module("benchmark.readers." + spec["reader"])
            if kind == "end_to_end":
                assert 0.01 <= x["bound"] <= 0.1
                assert x["source"] in ("host_clock", "device_trace")
            else:
                assert x["moves"] in e2e
                assert 1 <= len(x["layer"]) <= 200
                # every listed cell reports the end-to-end metric it moves
                moved = e2e[x["moves"]].get("workloads", list(cells))
                assert set(x["workloads"]) <= set(moved)
    for w in cells:      # setup_s, another end-to-end metric, a layer metric
        assert any(w in x.get("workloads", [w]) and x["name"] != "setup_s"
                   for x in m["end_to_end"])
        assert any(w in x.get("workloads", [w]) for x in m["per_layer"])
    assert len(json.dumps(m)) < 64 * 1024
