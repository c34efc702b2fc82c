"""StageTimer ``ingest_compress``: host codec busy time summed over the
codec workers, per million edges."""

from benchmark.metrics._read import stage_ms_per_medge


def read(rec):
    return stage_ms_per_medge(rec, "ingest_compress")
