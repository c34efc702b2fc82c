"""1 - device busy / traced sub-window, from the profiler trace."""

from benchmark.metrics._read import idle_share


def read(rec):
    return idle_share(rec)
