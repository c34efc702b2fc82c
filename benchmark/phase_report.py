"""Trace passes of a file-fed cell and split their device time by phase.

    python3 benchmark/phase_report.py --workload <cell> --seed <n> \
        [--passes <k>] [--out <dir>]

On the chip, with the cell's driver pieces (``drivers/file_passes.py``):
makes the seed's edge file, warms every shape up with one pass, then
traces ``k`` passes (default 1; the benchmark's traced pass is the first
of them), each in a trace of its own, and prints one JSON line with,
for each pass,

- its seconds, StageTimer busy seconds and the change of the program's
  ``obs`` bus counters over it;
- its trace's reductions: ``trace_reduce`` (busy, window, top
  operations, idle gaps) and ``trace_phases`` (device seconds by named
  scope, executions, programs, idle gaps by the dispatching thread's
  stage);
- ``derived``: per 10^6 edges, the fold's and the close's device time and
  the fold's exact fixpoint, exact rounds per fold dispatch, the share of
  shipped fold lanes that carry a member, and the consumer's wait.

With ``--out`` it writes the line to ``<dir>/phase_report.json`` and
pass ``i``'s trace to ``<dir>/phase_trace.<i>.xplane.pb.gz``. Without a
TPU it exits 3.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _counters():
    from gelly_tpu.obs.bus import get_bus

    return dict(get_bus().snapshot()["counters"])


def _per_medge(seconds, edges):
    return None if seconds is None else 1e3 * seconds / (edges / 1e6)


def derived(ps: dict, edges: int, fold_program: str,
            close_program: str) -> dict:
    """The per-layer readings of one pass (None where it holds none)."""
    ph = ps["phases"] or {}
    phases, execs = ph.get("phases", {}), ph.get("phase_execs", {})
    progs = ph.get("programs", {})
    c = ps["counters"]
    fold_execs = progs.get(fold_program, {}).get("execs")
    return {
        "fold_device_ms_per_medge": _per_medge(phases.get("cc.fold"), edges),
        "fold_fixpoint_ms_per_medge": _per_medge(
            phases.get("uf.fixpoint"), edges),
        "fold_fixpoint_rounds": (execs["uf.hook"] / fold_execs
                                 if "uf.hook" in execs and fold_execs
                                 else None),
        "close_device_ms_per_medge": _per_medge(
            phases.get("cc.close"), edges),
        "fold_lane_fill": (c["cc.fold_members"] / c["cc.fold_lanes"]
                           if c.get("cc.fold_lanes") else None),
        "consumer_wait_ms_per_medge": _per_medge(
            ps["timer"].get("consumer_wait"), edges),
        "device_busy_ms_per_medge": _per_medge(
            (ps["trace"] or {}).get("busy_s"), edges),
        "fold_program_device_ms_per_medge": _per_medge(
            progs.get(fold_program, {}).get("device_s"), edges),
        "close_program_device_ms_per_medge": _per_medge(
            progs.get(close_program, {}).get("device_s"), edges),
    }


def report(cell, seed: int, passes: int = 1,
           out_dir: str | None = None) -> dict:
    from gelly_tpu.ingest import edge_stream_from_sharded_file
    from gelly_tpu.utils.metrics import StageTimer

    from benchmark import trace_phases, trace_reduce
    from benchmark.drivers import common, file_passes

    cfg, merge_every = cell.config, cell.traffic["merge_every_chunks"]
    ing = cfg["ingest"]
    path = file_passes.edge_file(cell.workload["config"], cfg, seed)
    rep = {"cell": cell.name, "seed": seed, "edges": cfg["edges"],
           "passes": []}
    try:
        agg = common.build_plan(cfg)
        mesh = common.one_chip_mesh()
        stream = edge_stream_from_sharded_file(
            path, cfg["vertices"], shards=ing["shards"],
            chunk_size=ing["chunk_size"])
        t = time.perf_counter()
        file_passes.one_pass(stream, agg, mesh, merge_every, StageTimer())
        rep["warmup_s"] = time.perf_counter() - t
        prof = common.Profiler(cell.name + ".phases")
        fold = "jit_" + agg.fold_compressed.__name__
        close = "jit_" + agg.transform.__name__
        for i in range(passes):
            timer = StageTimer()
            c0 = _counters()
            prof.start()
            _, windows, chunks, dt = file_passes.one_pass(
                stream, agg, mesh, merge_every, timer)
            prof.stop()
            c1 = _counters()
            ps = {"seconds": dt, "windows": windows, "chunks": chunks,
                  "timer": timer.busy(),
                  "counters": {k: v - c0.get(k, 0.0) for k, v in c1.items()
                               if v != c0.get(k, 0.0)}}
            try:
                xp = trace_reduce.find_xplane(prof.dir)
                ps["trace"] = trace_reduce.reduce_dir(prof.dir)
                ps["phases"] = trace_phases.reduce_file(xp)
                if out_dir:
                    with open(xp, "rb") as src, gzip.open(os.path.join(
                            out_dir, f"phase_trace.{i}.xplane.pb.gz"),
                            "wb") as dst:
                        shutil.copyfileobj(src, dst)
            finally:
                shutil.rmtree(prof.dir, ignore_errors=True)
            ps["derived"] = derived(ps, cfg["edges"], fold, close)
            rep["passes"].append(ps)
        rep["device"] = common.device_facts(cell.chips)
    finally:
        os.unlink(path)
    return rep


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--passes", type=int, default=1)
    p.add_argument("--out")
    args = p.parse_args(argv)
    sys.path[:] = [str(REPO)] + [
        q for q in sys.path if Path(q or ".").resolve() != REPO / "benchmark"]
    from benchmark import run, spec

    run.pin_environment()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no result: no TPU", file=sys.stderr)
        return 3
    cell = spec.Cell(spec.load_benchmark(), args.workload)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    rep = report(cell, args.seed, args.passes, args.out)
    line = json.dumps(rep, default=float)
    if args.out:
        with open(os.path.join(args.out, "phase_report.json"), "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
