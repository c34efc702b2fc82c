"""Ratios of the program's ``obs`` bus counters, read from the process's
bus, which the record does not carry: its counters cover the record's
passes and the warm-up pass before them, each a pass over the same file
on the same plan, so a ratio is the passes'."""

from __future__ import annotations


def counter_ratio(rec: dict, num: str, den: str) -> float | None:
    """``num / den``; None for a record without passes or a program
    without the counters (as one that predates them)."""
    from gelly_tpu.obs.bus import get_bus

    if not rec.get("passes"):
        return None
    counters = get_bus().snapshot()["counters"]
    if not counters.get(den):
        return None
    return counters.get(num, 0.0) / counters[den]
