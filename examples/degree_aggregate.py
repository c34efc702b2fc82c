"""Continuous degree aggregate (getDegrees, SimpleEdgeStream.java:413-438) —
the BASELINE workload #1 surface.

Usage: python examples/degree_aggregate.py [<edges path> <out|in|both>]
"""

from _util import arg, run_cli, sequence_default_edges, stream_from_args


def main(args):
    stream = stream_from_args(args, default_edges=sequence_default_edges())
    mode = arg(args, 1, "both", str)
    ds = {
        "out": stream.get_out_degrees,
        "in": stream.get_in_degrees,
        "both": stream.get_degrees,
    }[mode]()
    for v, d in sorted(ds.final_degrees().items()):
        print(f"({v},{d})")


if __name__ == "__main__":
    run_cli(main)
