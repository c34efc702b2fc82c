"""Process start to the start of the measured window: imports, data,
compilation or cache reads, and the warm-up (host clock)."""


def read(rec):
    return rec.get("setup_s")
