"""What several readers share. A reader returns None when its record
holds nothing to read, and the harness then leaves the metric out."""

from __future__ import annotations


def per_medge(seconds, edges) -> float | None:
    """Milliseconds per million edges."""
    if seconds is None or not edges:
        return None
    return 1e3 * seconds / (edges / 1e6)


def stage_ms_per_medge(rec: dict, stage: str) -> float | None:
    """A StageTimer stage's busy seconds over the window (summed over the
    threads that ran it), per million edges folded in the window."""
    return per_medge(rec.get("timer", {}).get(stage), rec.get("edges"))


def idle_share(rec: dict) -> float | None:
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]


def busy_ms_per_medge(rec: dict) -> float | None:
    tr = rec.get("trace")
    return None if not tr else per_medge(tr["busy_s"], tr.get("edges"))
