"""The share of shipped fold lanes that carry a member: the program's
``obs`` bus counters ``cc.fold_members`` over ``cc.fold_lanes``, counted
where the codec stacks a payload (the rest is bucket padding the device
fold still runs over).

Read from the process's bus, which the record does not carry: its
counters cover the record's passes and the warm-up pass before them,
every one a pass over the same file on the same plan, so the share is
the passes'. A record without passes has nothing to read."""


def read(rec):
    from gelly_tpu.obs.bus import get_bus

    if not rec.get("passes"):
        return None
    counters = get_bus().snapshot()["counters"]
    lanes = counters.get("cc.fold_lanes")
    if not lanes:
        return None
    return counters.get("cc.fold_members", 0.0) / lanes
