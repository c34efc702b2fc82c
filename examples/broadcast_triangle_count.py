"""Sampled triangle estimate, broadcast-style (BroadcastTriangleCount.java).

All sample instances advance over every edge (the reference broadcasts the
stream to each subtask's reservoir states; here the instances are one
vectorized axis on one device).

Usage: python examples/broadcast_triangle_count.py [<edges path> <samples> <vertices>]
"""

from _util import arg, run_cli, stream_from_args
from window_triangles import DEFAULT

from gelly_tpu.library.triangles import sampled_triangle_count


def main(args):
    stream = stream_from_args(args, default_edges=[
        (s, d) for s, d, _ in DEFAULT
    ])
    samples = arg(args, 1, 1000)
    vertices = arg(args, 2, 11)
    est = None
    for est in sampled_triangle_count(stream, samples, num_vertices=vertices):
        pass
    print(f"estimate: {est}")


if __name__ == "__main__":
    run_cli(main)
