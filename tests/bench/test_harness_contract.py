"""BENCHMARK.json keeps to the benchmark's rules, and every piece it
names has its file."""

from __future__ import annotations

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(REPO, p))
    assert 1 <= len(bench["command"]) <= 32
    assert os.path.isfile(os.path.join(REPO, bench["command"][1]))
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("section,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
])
def test_entries_have_exactly_their_keys(bench, section, keys):
    names = [e["name"] for e in bench[section]]
    assert len(names) == len(set(names))
    for e in bench[section]:
        assert set(e) == keys, e["name"]
        assert NAME.match(e["name"])
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


def test_configs_are_files_under_paths(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg.get("reduced", {})) == sorted(c["reduced"])
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "graphs", cfg["graph"]["generator"] + ".py"))
        assert len(c["reduced"]) <= 16
        assert c["source"].startswith("https://")


def test_cells_name_known_pieces(bench):
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        with open(os.path.join(REPO, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            params = json.load(f)
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "drivers", params["driver"] + ".py"))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = list(e2e) + [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in {"host_clock", "device_trace"}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        for w in m.get("workloads", cells):
            scope = e2e[m["moves"]].get("workloads", cells)
            assert w in scope, (m["name"], w)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.isfile(os.path.join(REPO, "benchmark", "metrics",
                                           m["name"] + ".py"))


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        name = w["name"]
        e2e = [m["name"] for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
        layer = [m["name"] for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
        assert "setup_s" in e2e and len(e2e) >= 2, name
        assert layer, name


def test_peaks_table_names_the_v5e():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        table = json.load(f)
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
