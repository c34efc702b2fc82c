"""Find a cell's configuration, traffic mix, driver and metric readers by
the names ``BENCHMARK.json`` gives them. A later cell, mix or metric is a
new file here and a new entry there; nothing in this file names one."""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


def load_benchmark(path: Path | None = None) -> dict:
    with open(path or REPO / "BENCHMARK.json") as f:
        return json.load(f)


def _named(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def metric_reader(name: str):
    """``read(record) -> float | None`` from ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics._m_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver(traffic: dict):
    """The general driver a traffic file names (``drivers/<driver>.py``)."""
    return importlib.import_module(f"benchmark.drivers.{traffic['driver']}")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics with
    ``trace`` off, its per-layer metrics with it on."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


class Cell:
    """One workload entry with its configuration and traffic resolved."""

    def __init__(self, bench: dict, name: str):
        self.workload = _named(bench["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.workload["chips"])
        entry = _named(bench["configs"], self.workload["config"], "config")
        with open(REPO / entry["file"]) as f:
            self.config = json.load(f)
        self.traffic = load_traffic(self.workload["traffic"])
        self.bench = bench
