"""BENCHMARK.json keeps to its contract, and every configuration, traffic
mix, driver and metric reader it names loads by that name."""

import json
import re
from pathlib import Path

import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and not re.search(
        r"[\n\r\t]", s)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (spec.REPO / p).is_dir()
    assert len(BENCH["command"]) <= 32
    assert all(one_line(w) for w in BENCH["command"])
    for w in BENCH["command"][1:]:
        assert any(w.startswith(p + "/") for p in BENCH["paths"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # A full check with 24 cells fits the driver's 43,200 s.
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_configs_and_cells():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        body = json.loads((spec.REPO / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    assert len({c["file"] for c in BENCH["configs"]}) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) and 1 <= len(pairs) <= 24
    assert len(set(CELLS)) == len(CELLS)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert one_line(w["why"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)


def test_metrics():
    e2e, layer = BENCH["end_to_end"], BENCH["per_layer"]
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    assert "setup_s" in names
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert (spec.HERE / "metrics" / f"{m['name']}.py").is_file()
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e_names = {m["name"] for m in e2e}
    for m in layer:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert one_line(m["layer"]) and m["moves"] in e2e_names
        moved = next(x for x in e2e if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))
    for cell in CELLS:
        got = {m["name"] for m in spec.cell_metrics(BENCH, cell, False)}
        assert "setup_s" in got and len(got) >= 2
        assert spec.cell_metrics(BENCH, cell, True)


def test_every_file_under_paths_is_named_from_name_characters():
    for p in BENCH["paths"]:
        for f in (spec.REPO / p).rglob("*"):
            if "__pycache__" in f.parts or ".data" in f.parts:
                continue
            rel = f.relative_to(spec.REPO).as_posix()
            assert all(NAME.match(part) for part in rel.split("/")), rel


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = spec.Cell(BENCH, cell)
    drv = spec.driver(c.traffic)
    assert callable(drv.run)
    assert c.traffic["name"] == c.workload["traffic"]
    for m in spec.cell_metrics(BENCH, cell, False) + spec.cell_metrics(
            BENCH, cell, True):
        assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("name", [m["name"] for m in
                                  BENCH["end_to_end"] + BENCH["per_layer"]])
def test_reader_finds_nothing_in_an_empty_record(name):
    assert spec.metric_reader(name)({}) is None


def test_no_file_outside_paths_is_named():
    words = " ".join(BENCH["command"])
    assert "gelly_tpu" not in words and "bench.py" not in words
    assert not Path(BENCH["command"][1]).is_absolute()
