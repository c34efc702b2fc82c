"""Bipartiteness check (BipartitenessCheckExample.java:40-125).

Usage: python examples/bipartiteness_check.py [<edges path> <merge every chunks>]
"""

from _util import arg, run_cli, stream_from_args

from gelly_tpu.library.bipartiteness import bipartiteness_check, to_candidates

# BipartitenessCheckTest bipartite fixture as the built-in default.
DEFAULT = [(1, 2), (1, 3), (1, 4), (4, 5), (4, 7), (4, 9)]


def main(args):
    stream = stream_from_args(args, default_edges=DEFAULT)
    agg = bipartiteness_check(stream.ctx.vertex_capacity)
    res = stream.aggregate(agg, merge_every=arg(args, 1, 4)).result()
    print(to_candidates(res, stream.ctx))


if __name__ == "__main__":
    run_cli(main)
