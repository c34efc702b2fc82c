"""The control: the reference with one guarantee broken, compared as a
run's answer is. It has to read as not correct.

The cells state no precision; the guarantee broken is "every edge is
read exactly once": the control's answer leaves out one chunk of the
file the program reads, the chunk drawn from the seed. Its reading is
the number of vertex slots whose label differs from the reference's, the
number each run compares against its limit 0.

    python3 benchmark/tests/control.py --workload <cell> --seeds 1,2,3

runs it at the cell's own size (on the chip's machine: numpy and scipy only)
and prints one line per seed. ``test_control.py`` runs it at a tiny size.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np


def readings(cell, seed: int) -> dict:
    from benchmark import synth
    from benchmark.drivers import common

    cfg = cell.config
    n_e, unit = cfg["edges"], cfg["ingest"]["chunk_size"]
    src, dst = synth.edges(cfg, seed)
    k = synth.seed_key(seed) % (n_e // unit)
    keep = np.ones(n_e, bool)
    keep[k * unit:(k + 1) * unit] = False
    want = common.expected(cfg, src, dst)
    ctrl = common.expected(cfg, src[keep], dst[keep])
    return {"seed": seed, "edges": n_e, "dropped_unit": [k, unit],
            "label_mismatches": common.mismatches(ctrl, want)}


def main(argv=None) -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    from benchmark import spec

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    cell = spec.Cell(spec.load_benchmark(), args.workload)
    for s in args.seeds.split(","):
        t = time.perf_counter()
        line = readings(cell, int(s))
        line["seconds"] = time.perf_counter() - t
        print(json.dumps({"workload": cell.name, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
