"""StageTimer ``consumer_wait``: the fold dispatcher's wait for a staged,
transferred unit (reader, codec and H2D behind it), per million edges."""

from benchmark.metrics._read import stage_ms_per_medge


def read(rec):
    return stage_ms_per_medge(rec, "consumer_wait")
