"""Greedy weighted matching (CentralizedWeightedMatching.java:36-113).

Usage: python examples/centralized_weighted_matching.py [<edges path (src dst weight)>]
Prints the final matching and its total weight plus net runtime, mirroring
the reference's getNetRuntime report (:62-64).
"""

import time

from _util import run_cli, stream_from_args

from gelly_tpu.library.matching import weighted_matching

DEFAULT = [
    (1, 2, 10.0), (3, 4, 10.0), (2, 3, 45.0), (5, 6, 3.0), (6, 7, 10.0),
]


def main(args):
    stream = stream_from_args(args, default_edges=DEFAULT, num_value_cols=1)
    t0 = time.perf_counter()
    wm = weighted_matching(stream)
    for ev in wm.events():  # the reference's MatchingEvent print stream
        print(f"{ev.type} ({ev.src},{ev.dst},{ev.weight})")
    print(f"total weight: {wm.total_weight()}")
    print(f"Runtime: {int((time.perf_counter() - t0) * 1000)} ms")


if __name__ == "__main__":
    run_cli(main)
