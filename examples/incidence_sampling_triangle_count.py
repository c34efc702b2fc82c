"""Sampled triangle estimate, incidence-sampling distribution
(IncidenceSamplingTriangleCount.java:23-337).

The reference fans sampled/incident edges out to keyed subtasks; the
TPU-native equivalent shards the instance axis over the mesh so each device
advances its own reservoir states (same estimator, same seeded RNG family).
On a single chip this degenerates to the broadcast variant.

Usage: python examples/incidence_sampling_triangle_count.py [<edges path> <samples> <vertices>]
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
    for est in sampled_triangle_count(
        stream, samples, num_vertices=vertices, seed=0xDEADBEEF
    ):
        pass
    print(f"estimate: {est}")


if __name__ == "__main__":
    run_cli(main)
