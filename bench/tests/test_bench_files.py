"""BENCHMARK.json and the files it names: every metric, configuration,
traffic mix and limit file found by name, and the contract's limits on
names, units, bounds and cells."""
import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _stems(sub, ext):
    d = os.path.join(BENCH, sub)
    return {f[: -len(ext)] for f in os.listdir(d)
            if f.endswith(ext) and not f.startswith("_")}


def test_every_metric_has_a_reader_and_every_reader_is_listed(bench):
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert names == _stems("metrics", ".py")


@pytest.mark.parametrize("sub,key", [("configs", "config"),
                                     ("traffic", "traffic"),
                                     ("limits", "name")])
def test_every_cell_finds_its_files_by_name(bench, sub, key):
    assert {w[key] for w in bench["workloads"]} <= _stems(sub, ".json")


def test_names_units_and_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in bench["end_to_end"])


def test_each_cell_reports_setup_another_metric_and_a_layer(bench):
    def applies(m, cell):
        return "workloads" not in m or cell in m["workloads"]

    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        mine = {m["name"] for m in bench["end_to_end"]
                if applies(m, w["name"])}
        assert "setup_s" in mine and len(mine) >= 2
        layers = [m for m in bench["per_layer"] if applies(m, w["name"])]
        assert layers
        for m in layers:
            assert m["moves"] in e2e and m["moves"] in mine, (m, w)


def test_run_seconds_fits_a_full_check_of_24_cells(bench):
    rs = bench["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_config_files_agree_with_the_program_except_reduced(bench):
    from harness import cell
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        cfg = cell.program_config(conf, c["reduced"])
        assert cfg.num_layers == conf["num_hidden_layers"]
