"""Iterative (label-propagation) CC (IterativeConnectedComponents.java:43-229).

Usage: python examples/iterative_connected_components.py [<edges path>]
"""

import numpy as np
from _util import run_cli, sequence_default_edges, stream_from_args

from gelly_tpu.library.iterative_cc import IterativeCCStream


def main(args):
    stream = stream_from_args(args, default_edges=sequence_default_edges())
    labels = np.asarray(IterativeCCStream(stream).final_labels())
    for slot in np.nonzero(labels >= 0)[0]:
        vertex = int(stream.ctx.decode(np.array([slot]))[0])
        comp = int(stream.ctx.decode(np.array([labels[slot]]))[0])
        print(f"({vertex},{comp})")


if __name__ == "__main__":
    run_cli(main)
