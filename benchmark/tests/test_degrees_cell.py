"""The degree cell, cut small here, runs through ``file_passes`` as
the cell does and reads correct; the control (one chunk of the
file left out) reads not correct; the degree readers find nothing on a
bus without the ``deg.*`` counters, as on a program that lacks them.

``tiny.py`` cuts the CC cell (it sets ``compact_capacity``, which
``degree_aggregate`` does not take), so this file makes its own cut:
the same vertex space, graph and chunk sizes."""

import copy

import pytest

from benchmark import spec

from .control import readings
from .tiny import N_V

CELL = "degrees-twitter2010-file"
READERS = ["degree_lane_fill.file", "degree_pairs_per_edge.file"]


def tiny_degrees_cell() -> spec.Cell:
    cell = spec.Cell(spec.load_benchmark(), CELL)
    cfg = cell.config = copy.deepcopy(cell.config)
    cfg["vertices"] = N_V
    cfg["graph_vertices"] = N_V - N_V // 100
    cfg["edges"] = (1 << 15) + 77
    cfg["ingest"]["chunk_size"] = 1 << 12
    cell.traffic = dict(cell.traffic, merge_every_chunks=4)
    return cell


def test_sound_run_is_correct():
    from benchmark import run as harness
    from benchmark.compile_clock import CompileClock
    from gelly_tpu import obs

    cell = tiny_degrees_cell()
    with obs.scope() as bus:
        rec = spec.driver(cell.traffic).run(cell, 2**31 + 111, 1.0, False,
                                            CompileClock())
        rec["setup_s"] = 1.0
        out = harness.result(cell, rec, False)
        layer = harness.result(cell, rec, True)["metrics"]
        c = dict(bus.counters)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["label_mismatches"]["value"] == 0
    assert set(out["metrics"]) == {"edges_per_s", "setup_s"}
    assert layer["degree_lane_fill.file"]["value"] == (
        c["deg.fold_pairs"] / c["deg.fold_lanes"])
    assert layer["degree_pairs_per_edge.file"]["value"] == (
        c["deg.fold_pairs"] / c["deg.codec_edges"])
    # the warm-up pass and every measured pass, each the whole file
    passes = len(rec["passes"]) + 1
    assert c["deg.codec_edges"] == passes * cell.config["edges"]
    assert 0 < layer["degree_lane_fill.file"]["value"] <= 1
    assert 0 < layer["degree_pairs_per_edge.file"]["value"] <= 2


@pytest.mark.parametrize("seed", [4, 2**31 + 9])
def test_one_chunk_left_out_is_not_correct(seed):
    r = readings(tiny_degrees_cell(), seed)
    assert r["dropped_unit"][1] == 1 << 12
    assert r["label_mismatches"] > 0


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_the_counters(name):
    from gelly_tpu import obs

    with obs.scope() as bus:
        bus.inc("cc.fold_members", 3)
        bus.inc("cc.fold_lanes", 4)
        assert spec.metric_reader(name)({"passes": [{}]}) is None
