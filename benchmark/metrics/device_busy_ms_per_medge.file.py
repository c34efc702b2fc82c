"""Device busy time (union of operation intervals) in the traced pass,
per million edges folded in it, from the profiler trace."""

from benchmark.metrics._read import busy_ms_per_medge


def read(rec):
    return busy_ms_per_medge(rec)
