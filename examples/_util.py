"""Shared CLI plumbing for the example programs.

Mirrors the reference examples' hand-rolled ``parseParameters`` pattern
(positional args; no args = built-in default data, e.g.
``M/example/ConnectedComponentsExample.java:81-118``).
"""

from __future__ import annotations

import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])  # repo root

from gelly_tpu import (  # noqa: E402
    TimeCharacteristic,
    edge_stream_from_edges,
    edge_stream_from_file,
)


def stream_from_args(args, vertex_capacity=1 << 16, chunk_size=4096,
                     num_value_cols=0, default_edges=None, **kw):
    """args[0] = optional edge-list path; otherwise built-in default data."""
    if args:
        return edge_stream_from_file(
            args[0], vertex_capacity=vertex_capacity, chunk_size=chunk_size,
            num_value_cols=num_value_cols, **kw,
        )
    # Built-in default data is tiny; cap the chunk at its length so
    # sequential per-slot folds (e.g. the spanner insert scan) don't pay
    # for padding slots.
    return edge_stream_from_edges(
        default_edges, vertex_capacity=vertex_capacity,
        chunk_size=min(chunk_size, 256, max(1, len(default_edges))), **kw,
    )


def sequence_default_edges():
    """The reference examples' default stream: (k, k+2) for k=1..100 with
    event time k*100 (ConnectedComponentsExample.java:121-134)."""
    return [(k, k + 2, float(k * 100)) for k in range(1, 101)]


def arg(args, i, default, cast=int):
    return cast(args[i]) if len(args) > i else default


def run_cli(main):
    """Command-line entry: persistent compile cache on, then
    ``main(argv)``. Tests call ``main`` directly and stay cache-free."""
    from gelly_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main(sys.argv[1:])
