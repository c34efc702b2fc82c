"""Exact streaming triangle count (ExactTriangleCount.java:41-207).

Usage: python examples/exact_triangle_count.py [<edges path>]
Prints (vertex, count) pairs; key -1 is the global count.
"""

from _util import run_cli, stream_from_args
from window_triangles import DEFAULT


def main(args):
    from gelly_tpu.library.triangles import exact_triangle_count

    # Dense N^2 adjacency state: keep the slot space graph-sized.
    stream = stream_from_args(args, default_edges=[
        (s, d) for s, d, _ in DEFAULT
    ], vertex_capacity=1 << 12)
    for k, v in sorted(exact_triangle_count(stream).final_counts().items()):
        print(f"({k},{v})")


if __name__ == "__main__":
    run_cli(main)
