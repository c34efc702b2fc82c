"""The reader of ``degree_i32_fold_share.file``: on the tiny degree cell
with executor defaults (one chunk a fold, an i32 payload) every pair
goes through the i32 scatter and the share reads 1.0; on a bus without
``deg.fold_i32_pairs``, as on a program that lacks the i32 path, it
reads nothing; a counter at 0 reads 0."""

import pytest

from benchmark import spec

from .test_degrees_cell import tiny_degrees_cell

NAME = "degree_i32_fold_share.file"


def test_tiny_cell_folds_every_pair_in_i32():
    from benchmark import run as harness
    from benchmark.compile_clock import CompileClock
    from gelly_tpu import obs

    cell = tiny_degrees_cell()
    with obs.scope() as bus:
        rec = spec.driver(cell.traffic).run(cell, 2**31 + 113, 1.0, False,
                                            CompileClock())
        rec["setup_s"] = 1.0
        out = harness.result(cell, rec, True)
        c = dict(bus.counters)
    assert out["correct"], out["checks"]
    assert c["deg.fold_pairs"] > 0
    assert c["deg.fold_i32_pairs"] == c["deg.fold_pairs"]
    assert out["metrics"][NAME]["value"] == 1.0


@pytest.mark.parametrize("i32_pairs,share", [(None, None), (0, 0.0), (6, 1.0)])
def test_i32_fold_share_reads_its_counter(i32_pairs, share):
    from gelly_tpu import obs

    with obs.scope() as bus:
        bus.inc("cc.fold_members", 3)
        bus.inc("cc.fold_lanes", 4)
        bus.inc("deg.fold_pairs", 6)
        if i32_pairs is not None:
            bus.inc("deg.fold_i32_pairs", i32_pairs)
        got = spec.metric_reader(NAME)({"passes": [{}]})
    assert got == share


def test_reader_finds_nothing_without_the_counters():
    from gelly_tpu import obs

    with obs.scope() as bus:
        bus.inc("cc.fold_members", 3)
        bus.inc("cc.fold_lanes", 4)
        assert spec.metric_reader(NAME)({"passes": [{}]}) is None
