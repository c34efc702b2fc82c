"""StageTimer ``ingest_chunks``: the producer's busy time reading
the next chunk from the edge file, per million edges."""

from benchmark.metrics._read import stage_ms_per_medge


def read(rec):
    return stage_ms_per_medge(rec, "ingest_chunks")
