"""Cells cut to a size a CPU test run holds: the same drivers, plans and
reference, with a small vertex space, graph and chunk."""

from __future__ import annotations

import copy

from benchmark import spec

N_V = 20011  # with 2^15 edges: the average degree of the cell, 3.2


def tiny_cell(name: str) -> spec.Cell:
    cell = spec.Cell(spec.load_benchmark(), name)
    cfg = cell.config = copy.deepcopy(cell.config)
    cfg["vertices"] = N_V
    cfg["graph_vertices"] = N_V - N_V // 100
    cfg["plan"]["compact_capacity"] = N_V
    cfg["edges"] = (1 << 15) + 77
    cfg["ingest"]["chunk_size"] = 1 << 12
    cell.traffic = dict(cell.traffic, merge_every_chunks=4)
    return cell


def run(name: str, seed: int, seconds: float = 1.0, trace: bool = False):
    """The result line a run of the tiny cell prints (``run.result``)."""
    from benchmark import run as harness
    from benchmark.compile_clock import CompileClock

    cell = tiny_cell(name)
    rec = spec.driver(cell.traffic).run(cell, seed, seconds, trace,
                                        CompileClock())
    rec["setup_s"] = 1.0
    return harness.result(cell, rec, trace)
