"""(vertex, delta) pairs the sparse degree codec ships per edge it
compressed: bus counters ``deg.fold_pairs`` over ``deg.codec_edges``. At
most 2 (two endpoints an edge); it falls as a chunk's endpoints repeat.
With ``degree_lane_fill.file`` it gives the H2D bytes an edge costs."""

from benchmark.metrics._bus import counter_ratio


def read(rec):
    return counter_ratio(rec, "deg.fold_pairs", "deg.codec_edges")
