"""BENCHMARK.json: every name leads to a file, and every name, unit and
line is made of what the driver accepts."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["bench"]
    assert manifest["command"][1].startswith("bench/")
    assert 1 <= manifest["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60, 180 s a cell to compile, 1200
    # spare, inside 43200
    assert 338 * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_configs_resolve(manifest):
    files = set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["why"]) and line(c["source"])
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["name"] == c["name"] and config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        for key in ("guarantees", "assumed", "tables", "indexes", "conf"):
            assert key in config
        assert os.path.isfile(os.path.join(BENCH, "datasets",
                                           config["dataset"] + ".py"))
    sources = [c["source"] for c in manifest["configs"]]
    assert len(set(sources)) == len(sources)


def test_cells_resolve(manifest):
    configs = {c["name"] for c in manifest["configs"]}
    seen, used = set(), set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert line(w["why"]) and w["chips"] in (1, 4)
        assert w["config"] in configs
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        used.add(w["config"])
        path = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        with open(path) as f:
            traffic = json.load(f)
        for key in ("what", "source", "driver", "op", "tables", "indexes"):
            assert key in traffic, (w["traffic"], key)
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           traffic["driver"] + ".py"))
        assert os.path.isfile(os.path.join(BENCH, "ops",
                                           traffic["op"] + ".py"))
    assert used == configs  # each configuration is used by some cell
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 2)


def test_metrics_resolve(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and line(m["layer"])
        assert m["source"] in SOURCES
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
    for cell in cells:  # setup_s, another end-to-end metric, a per-layer one
        mine = [m for m in manifest["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_file_names_under_paths_are_made_of_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            assert ok.match(rel), rel


def test_run_py_names_no_cell_config_or_metric(manifest):
    with open(os.path.join(BENCH, "run.py")) as f:
        text = f.read()
    names = [x["name"] for key in ("configs", "workloads", "per_layer")
             for x in manifest[key]]
    names += [w["traffic"] for w in manifest["workloads"]]
    names += [m["name"] for m in manifest["end_to_end"]
              if m["name"] != "setup_s"]
    for name in names:
        assert name not in text, name
