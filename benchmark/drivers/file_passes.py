"""Closed loop over a binary edge file: back-to-back passes of the seed's
stream through ``stream.aggregate``, each from a fresh summary and on the
plan compiled once.

Traffic parameters (``traffic/<name>.json``): ``merge_every_chunks``.
Configuration (``configs/<name>.json``): ``vertices``, ``edges`` (per
pass), ``degree_exponent``, ``plan``, ``reference`` and ``ingest``
(``shards``, ``chunk_size``).

A pass ends when its final window's labels are ready on the device.
``edges_per_s`` is every edge of every pass over the sum of the passes'
times. Each pass's final labels are compared with the reference once the
window has closed.
"""

from __future__ import annotations

import os
import time

import numpy as np

from .. import synth
from . import common

BLOCK = 1 << 22  # edges written at a time


def edge_file(name: str, config: dict, seed: int) -> str:
    """Run ``seed``'s graph as a binary edge file (little-endian int64
    (src, dst) records), made anew in every run so that every run's
    set-up does the same work; the run deletes it when it ends."""
    d = common.DATA / name
    d.mkdir(parents=True, exist_ok=True)
    path = d / "edges.bin"
    src, dst = synth.edges(config, seed)
    with open(path, "wb") as f:
        for lo in range(0, src.shape[0], BLOCK):
            rec = np.empty((min(BLOCK, src.shape[0] - lo), 2), "<i8")
            rec[:, 0] = src[lo:lo + BLOCK]
            rec[:, 1] = dst[lo:lo + BLOCK]
            f.write(rec.tobytes())
    return str(path)


def one_pass(stream, agg, mesh, merge_every: int, timer):
    """(final labels, windows, chunks, seconds) of one pass."""
    t0 = time.perf_counter()
    labels, windows = None, 0
    out = stream.aggregate(agg, mesh=mesh, merge_every=merge_every,
                           timer=timer)
    with common.annotate("pass"):
        it = iter(out)
        while True:
            with common.annotate("source_next"):
                nxt = next(it, None)
            if nxt is None:
                break
            with common.annotate("emit"):
                labels = nxt.block_until_ready()
                windows += 1
    return labels, windows, out.stats["chunks"], time.perf_counter() - t0


def run(cell, seed: int, seconds: float, trace: bool, clock) -> dict:
    from gelly_tpu.ingest import edge_stream_from_sharded_file
    from gelly_tpu.utils.metrics import StageTimer

    cfg, merge_every = cell.config, cell.traffic["merge_every_chunks"]
    ing = cfg["ingest"]
    n_chunks = -(-cfg["edges"] // ing["chunk_size"])
    setup = {}
    t = time.perf_counter()
    path = edge_file(cell.workload["config"], cfg, seed)
    try:
        setup["data_s"] = time.perf_counter() - t
        t = time.perf_counter()
        c0 = clock.mark()
        agg = common.build_plan(cfg)
        mesh = common.one_chip_mesh()
        stream = edge_stream_from_sharded_file(
            path, cfg["vertices"], shards=ing["shards"],
            chunk_size=ing["chunk_size"])
        one_pass(stream, agg, mesh, merge_every, StageTimer())  # every shape
        c1 = clock.mark()
        setup["warmup_s"] = time.perf_counter() - t
        setup["compile_s"], setup["programs"] = c1[0] - c0[0], c1[1] - c0[1]
        setup["cache_hits"] = c1[2] - c0[2]

        rec = {"setup": setup, "passes": [], "timer": {}, "edges": 0}
        finals = []
        prof = common.Profiler(cell.name) if trace else None
        w0 = rec["t_window_start"] = time.perf_counter()
        while time.perf_counter() - w0 < seconds:
            timer = StageTimer()
            traced = prof is not None and not rec["passes"]
            if traced:
                prof.start()
            labels, windows, chunks, dt = one_pass(
                stream, agg, mesh, merge_every, timer)
            if traced:
                prof.stop()
            rec["passes"].append({"edges": cfg["edges"], "seconds": dt,
                                  "windows": windows, "chunks": chunks})
            for k, v in timer.busy().items():
                rec["timer"][k] = rec["timer"].get(k, 0.0) + v
            finals.append(np.asarray(labels))
            del labels
        rec["window_s"] = time.perf_counter() - w0
        c2 = clock.mark()
        rec["programs_in_window"] = clock.names[c1[1]:c2[1]]
        rec["edges"] = sum(p["edges"] for p in rec["passes"])
        rec["device"] = common.device_facts(cell.chips)
        if prof is not None:
            rec["trace"] = prof.reduce()
            if rec["trace"] is not None:
                rec["trace"]["edges"] = cfg["edges"]
        del agg, stream, mesh

        # The reference, once the window has closed and the peak is read.
        t = time.perf_counter()
        want = common.expected_from_file(cfg, path)
    finally:
        os.unlink(path)
    bad = [common.mismatches(f, want) for f in finals]
    rec["reference_s"] = time.perf_counter() - t
    rec["attempted"] = len(finals)
    rec["failed"] = sum(1 for b in bad if b)
    rec["checks"] = {
        "label_mismatches": (sum(bad), 0),
        "chunks_missing": (sum(n_chunks - p["chunks"]
                               for p in rec["passes"]), 0),
        "windows_missing": (sum(-(-n_chunks // merge_every) - p["windows"]
                                for p in rec["passes"]), 0),
    }
    return rec
