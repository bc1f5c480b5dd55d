"""BENCHMARK.json against the benchmark's contract, and discovery by name
of every piece a cell needs (configuration, traffic, answer kind, limits,
per-layer metric readers)."""

import json
import os
import re

import pytest

import _chipbench as cb

harness = cb.harness
SPEC = harness.benchmark_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert os.path.isdir(os.path.join(cb.ROOT, p))
    assert SPEC["command"][1] in [os.path.join(p, "run.py")
                                  for p in SPEC["paths"]]
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_and_units(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
        for key in e.get("reduced", ()):
            assert NAME.match(key)


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_pieces_found_by_name(cell):
    w = harness.workload_entry(SPEC, cell)
    assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4)
    cfg = harness.load_config(w["config"])
    mix = harness.load_traffic(w["traffic"])
    kind = harness.load_module("answers", mix["answer"])
    limits = harness.load_limits(cell)
    assert all(callable(getattr(kind, f)) for f in
               ("answer", "check", "control"))
    assert cfg["name"] == w["config"]
    assert isinstance(limits, dict)
    e2e = {m["name"] for m in harness.cell_metrics(SPEC, cell,
                                                   "end_to_end")}
    produced = {mix.get("time_metric", kind.TIME_METRIC), "setup_s"}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert e2e <= produced
    per = harness.cell_metrics(SPEC, cell, "per_layer")
    assert per
    for m in per:
        assert m["moves"] in e2e
        assert callable(harness.load_module("metrics", m["name"]).read)


def test_config_files_are_the_configs():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        cfg = harness.load_json(os.path.join(cb.ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert c["name"] in {w["config"] for w in SPEC["workloads"]}


def test_per_layer_layers_are_one_line():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all(1 <= len(x) <= 200 for x in layers)


def test_at_most_half_the_cells_take_four_chips():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)
