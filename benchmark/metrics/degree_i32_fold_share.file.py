"""The share of the degree fold's (vertex, delta) pairs that went through
its i32 scatter: bus counters ``deg.fold_i32_pairs`` over
``deg.fold_pairs``. 1.0 when every payload keeps its per-chunk i32
deltas, one row a fold; 0 when groups of chunks combine into i64
payloads, which take the emulated int64 scatter-add. None on a program
that counts the pairs but not this path (one that predates it)."""

from benchmark.metrics._bus import counter_ratio


def read(rec):
    from gelly_tpu.obs.bus import get_bus

    if "deg.fold_i32_pairs" not in get_bus().snapshot()["counters"]:
        return None
    return counter_ratio(rec, "deg.fold_i32_pairs", "deg.fold_pairs")
