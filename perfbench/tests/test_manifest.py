"""BENCHMARK.json against the contract's letter, and every name in it
against the files the harness finds by that name."""

import json
import os
import re

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PB = os.path.join(ROOT, "perfbench")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units():
    m = manifest()
    assert sorted(m) == ["command", "configs", "end_to_end", "paths",
                         "per_layer", "run_seconds", "workloads"]
    assert 1 <= m["run_seconds"] <= 51
    names = set()
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[sec]:
            assert NAME.match(e["name"]), e["name"]
            assert (sec, e["name"]) not in names
            names.add((sec, e["name"]))
    for sec in ("end_to_end", "per_layer"):
        for e in m[sec]:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    assert any(e["name"] == "setup_s" for e in m["end_to_end"])
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)


def test_every_name_resolves_to_its_files():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {e["name"] for e in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    for c in m["configs"]:
        assert c["file"].startswith("perfbench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert os.path.exists(os.path.join(ROOT, cfg["net"]))
        assert os.path.exists(os.path.join(
            PB, "reference", cfg["reference"] + ".py"))
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert any(w["config"] == c["name"] for w in m["workloads"])
    pairs = set()
    for w in m["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        with open(os.path.join(PB, "traffic", w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(PB, "windows", kind + ".py"))
        with open(os.path.join(PB, "cells", w["name"] + ".json")) as f:
            assert "limits" in json.load(f)
    for e in m["end_to_end"] + m["per_layer"]:
        assert os.path.exists(os.path.join(PB, "metrics",
                                           e["name"] + ".py")), e["name"]
        assert set(e.get("workloads", [])) <= cells
    for e in m["per_layer"]:
        assert e["moves"] in e2e
    # a retired cell leaves no orphan: the files under cells/ are the cells
    assert {fn[:-len(".json")] for fn in os.listdir(
        os.path.join(PB, "cells"))} == cells
    for path, _, files in os.walk(PB):
        if "__pycache__" in path:
            continue
        for fn in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", fn), (path, fn)
