"""BENCHMARK.json keeps to the benchmark's contract on names and units,
and every name in it resolves to a file of its own under chipbench/."""

import json
import os
import re

import pytest

from chipbench import harness

BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("name", [x["name"] for x in
                                  BENCH["configs"] + BENCH["workloads"]
                                  + METRICS]
                         + [w[k] for w in BENCH["workloads"]
                            for k in ("config", "traffic")]
                         + [k for c in BENCH["configs"]
                            for k in c["reduced"]])
def test_names(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_units_and_readers(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert callable(harness.load_module("metrics", metric["name"]).read)


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cells_resolve(cell):
    c = harness.load_cell(cell)
    harness.load_module("deployments", c.config["deployment"])
    harness.load_module("traffic", c.mix["generator"])
    assert c.end_to_end and c.per_layer
    assert "setup_s" in {m["name"] for m in c.end_to_end}


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + BENCH["per_layer"], ids=lambda e: e["name"])
def test_text_fields(entry):
    """`why`, `source` and `layer` are one line of 1 to 200 characters;
    a per-layer metric moves an end-to-end metric of the benchmark."""
    for key in ("why", "source", "layer"):
        if key in entry:
            text = entry[key]
            assert 1 <= len(text) <= 200 and "\n" not in text \
                and "\t" not in text, (key, text)
    if "moves" in entry:
        assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(config):
    """Each configuration's file lies under `paths`, is its own, and
    names in `reduced` what `BENCHMARK.json` lists."""
    assert config["file"].startswith(BENCH["paths"][0] + "/")
    assert [c["file"] for c in BENCH["configs"]].count(config["file"]) == 1
    data = json.load(open(os.path.join(harness.ROOT, config["file"])))
    assert data["name"] == config["name"]
    assert sorted(data["reduced"]) == sorted(config["reduced"])
