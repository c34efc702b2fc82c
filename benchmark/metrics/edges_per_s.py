"""Every edge of every pass in the window over the sum of the passes'
times (host clock; a pass ends when its final labels are ready)."""


def read(rec):
    passes = rec.get("passes")
    if not passes:
        return None
    return sum(p["edges"] for p in passes) / sum(p["seconds"] for p in passes)
