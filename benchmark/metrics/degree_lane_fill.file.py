"""The share of shipped degree fold lanes that carry a (vertex, delta)
pair: bus counters ``deg.fold_pairs`` over ``deg.fold_lanes``, counted
where the sparse degree codec stacks a payload (the rest is the
power-of-two bucket's padding, sent to the device and run over by the
fold's scatter)."""

from benchmark.metrics._bus import counter_ratio


def read(rec):
    return counter_ratio(rec, "deg.fold_pairs", "deg.fold_lanes")
