"""StageTimer ``h2d``: the transfer thread's busy time, per million
edges."""

from benchmark.metrics._read import stage_ms_per_medge


def read(rec):
    return stage_ms_per_medge(rec, "h2d")
