"""StageTimer ``fold_dispatch``: the consumer's time enqueueing folds
(host time, not device time), per million edges."""

from benchmark.metrics._read import stage_ms_per_medge


def read(rec):
    return stage_ms_per_medge(rec, "fold_dispatch")
