"""Benchmark harness for the BASELINE.json workloads.

Default (no args): the north-star config — streaming Connected Components
over a synthetic power-law edge stream — printing ONE JSON line
{"metric", "value", "unit", "vs_baseline"}.

``--workload {cc,degrees,triangles,bipartiteness,matching}`` selects any of
the five BASELINE configs; each measures its own reference-semantics Python
baseline in-process (the reference publishes no numbers, BASELINE.md: the
baseline must be measured, not quoted). The CC baseline reproduces
``DisjointSet.union`` with path compression per edge
(``/root/reference/src/main/java/org/apache/flink/graph/streaming/summaries/DisjointSet.java:66-118``)
folded edge-by-edge as ``UpdateCC`` does
(``.../library/ConnectedComponents.java:82-87``); the others mirror the
corresponding per-edge/per-window hash-map pipelines (citations at each
baseline function).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

# Every stdout JSON line is collected here and written to bench_out.json
# at process exit (see write_bench_artifact): the committed artifact then
# carries the FULL line set of a run, so README figures can cite a file
# in the repo instead of a quote — the headline line still prints LAST on
# stdout for the driver's last-line parser.
_BENCH_LINES: list = []


def emit(obj: dict) -> dict:
    """Print a workload line to stdout AND record it for bench_out.json."""
    _BENCH_LINES.append(obj)
    print(json.dumps(obj))
    return obj


def trace_out_path(stem: str) -> str:
    """Path for a workload's Chrome-trace capture next to bench.py.

    Default runs write ``<stem>.scratch.json`` (gitignored) so
    ``--workload`` invocations never dirty the tree; a RECORDED round
    (``GELLY_BENCH_RECORD=1``) writes the canonical committed name
    ``<stem>.json`` the artifacts/README cite.
    """
    import os

    name = (f"{stem}.json"
            if os.environ.get("GELLY_BENCH_RECORD") == "1"
            else f"{stem}.scratch.json")
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), name)


def write_bench_artifact(workload: str, path: str | None = None) -> None:
    """Write the run's collected line set next to bench.py.

    Only a full run (``--workload all``) writes the canonical
    ``bench_out.json`` — a single-workload invocation must not clobber
    the committed full line set, so it lands in ``bench_out.partial.json``
    instead. ``captured.chip`` records what actually ran: figures
    captured on ``cpu`` (reduced sizes, interpret-mode kernels) are
    structural stand-ins; the perf claims cite v5e captures
    (BENCH_r0*.json or a TPU-host bench_out.json).
    """
    import os

    if path is None:
        path = "bench_out.json" if workload == "all" else (
            "bench_out.partial.json")

    peaks = chip_peaks()
    out = {
        "schema": 1,
        "captured": {
            "workload": workload,
            "argv": sys.argv[1:],
            "chip": peaks.get("chip"),
            "unix_time": int(time.time()),
        },
        "lines": _BENCH_LINES,
    }
    target = os.path.join(os.path.dirname(os.path.abspath(__file__)), path)
    tmp = target + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    os.replace(tmp, target)


# Roofline denominators, keyed by ``device_kind`` as JAX reports it.
# Sources: Google Cloud TPU documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s per chip) and "TPU v4" (275 TFLOP/s bf16, 32 GB
# HBM at 1,228 GB/s per chip).
CHIP_PEAKS = {
    "TPU v5 lite": {"chip": "v5e", "peak_bf16_tflops": 197.0,
                    "peak_hbm_gbps": 819.0},
    "TPU v4": {"chip": "v4", "peak_bf16_tflops": 275.0,
               "peak_hbm_gbps": 1228.0},
}


def chip_peaks() -> dict:
    """Peak numbers for the attached device (roofline denominators).

    A TPU whose ``device_kind`` is not in :data:`CHIP_PEAKS` is an error,
    not a default. The CPU backend has no peaks: its lines are
    structural stand-ins (``chip: "cpu"``, utilization null), never a
    device measurement.
    """
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return {"chip": "cpu", "peak_bf16_tflops": None,
                "peak_hbm_gbps": None}
    try:
        return dict(CHIP_PEAKS[dev.device_kind])
    except KeyError:
        raise RuntimeError(
            f"no peak numbers for device_kind {dev.device_kind!r}; add it "
            "to bench.CHIP_PEAKS with its published source"
        ) from None


# Logical-byte model of the compact-plan star fold, per payload pair per
# dispatch (documented for the hbm_util fields): 2 unrolled rounds + check
# = 8 pair-sized i32 gathers (value read + index read each) + 2 scatter-min
# rounds (index read + value read + write) -> ~22 i32 accesses ~ 88 bytes.
# Random element-granule gathers cannot reach DRAM burst efficiency, so
# the derived utilization is a LOGICAL-bytes figure (a lower bound on the
# traffic the access pattern implies), not a DMA counter.
STAR_FOLD_BYTES_PER_PAIR = 88
# Degree fold: per edge, two i64 scatter-adds (idx read 4 + read 8 +
# write 8 each) = 40 logical bytes.
DEGREE_FOLD_BYTES_PER_EDGE = 40


def synth_edges(num_edges: int, num_vertices: int, seed: int = 7):
    """Power-law-ish edge stream (Zipf endpoints, the skew CC cares about).

    Emits i32 ids: they are dense in [0, num_vertices), so the identity
    vertex table passes them through zero-copy (the i64 ingest path is
    exercised by the dataset-backed workloads and the test suite)."""
    rng = np.random.default_rng(seed)
    # Zipf over a permuted id space so hot vertices are spread across slots.
    a = 1.3
    src = rng.zipf(a, size=num_edges) % num_vertices
    dst = rng.zipf(a, size=num_edges) % num_vertices
    perm = rng.permutation(num_vertices)
    return perm[src].astype(np.int32), perm[dst].astype(np.int32)


def baseline_cc(src: np.ndarray, dst: np.ndarray,
                cap_edges: int = 4_000_000) -> tuple[float, int]:
    """Reference-semantics per-edge union-find fold on host CPU.

    Folds every edge through ``DisjointSet.union`` semantics one at a time
    (the reference's actual execution shape). Timed on a prefix of up to
    ``cap_edges`` (per-edge cost is flat, so the rate extrapolates); the
    full-stream parity oracle lives in :func:`baseline_cc_numpy` (same
    components, ~6x faster to compute).
    """
    parent: dict[int, int] = {}

    def find(x: int) -> int:
        root = x
        while parent[root] != root:
            root = parent[root]
        while parent[x] != root:  # path compression
            parent[x], x = root, parent[x]
        return root

    def fold(s, d):
        for u, v in zip(s.tolist(), d.tolist()):
            if u not in parent:
                parent[u] = u
            if v not in parent:
                parent[v] = v
            ru, rv = find(u), find(v)
            if ru != rv:
                if ru < rv:
                    parent[rv] = ru
                else:
                    parent[ru] = rv

    n_timed = min(cap_edges, src.shape[0])
    # Best of 2, symmetric with the accelerator side's repeat policy.
    # Timing only — the full-stream parity oracle comes from the (much
    # faster) vectorized numpy baseline.
    dt = float("inf")
    for _ in range(2):
        parent.clear()
        t0 = time.perf_counter()
        fold(src[:n_timed], dst[:n_timed])
        dt = min(dt, time.perf_counter() - t0)
    return dt, n_timed


def baseline_cc_numpy(src: np.ndarray, dst: np.ndarray, num_vertices: int,
                      chunk_size: int, cap_edges: int = 8_000_000):
    """Vectorized host baseline with the same streaming semantics.

    The strongest honest CPU comparison: per-chunk spanning-forest reduction
    (vectorized numpy min-label propagation) folded into a global forest —
    i.e. the same chunked pipeline as the TPU path, minus the device.
    Returns ``(edges/sec timed on a prefix of cap_edges, full-stream global
    labels)`` — the labels double as the parity oracle (identical
    components to the per-edge fold; union is order-free).
    """
    from gelly_tpu.library.connected_components import (
        cc_labels_numpy,
        merge_chunk_forest,
    )

    s32 = src.astype(np.int32)
    d32 = dst.astype(np.int32)
    n = min(cap_edges, src.shape[0])

    def run(n_run):
        glob = np.arange(num_vertices, dtype=np.int32)
        seen = np.zeros(num_vertices, bool)
        for lo in range(0, n_run, chunk_size):
            lab = cc_labels_numpy(
                s32[lo:lo + chunk_size], d32[lo:lo + chunk_size],
                None, num_vertices,
            )
            seen |= lab >= 0
            glob = merge_chunk_forest(glob, lab)
        return glob, seen

    dt = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        run(n)
        dt = min(dt, time.perf_counter() - t0)
    glob, seen = run(src.shape[0])  # untimed full stream: the oracle
    return n / dt, np.where(seen, glob, -1)


# --------------------------------------------------------------------- #
# multicore CPU baseline (VERDICT r2 item 1)
#
# The reference's actual physical plan (SummaryBulkAggregation.java:68-90)
# on a modern CPU: partition the stream, fold each partition through an
# optimized union-find, merge the partial forests. Implemented with the
# native C++ sparse combiner — a *stronger* per-core baseline than the
# reference's per-edge HashMap DisjointSet in Java (dense arrays, no JVM
# or serialization overhead), so ratios against it are conservative.

class _EdgeFiles:
    """src/dst as ``.npy`` files under the checkout's ``.scratch/``,
    memory-mapped read-only by host-only worker processes. The workers
    are ``spawn`` children: a fresh interpreter that never initialises a
    JAX backend, so it neither inherits nor contends for this process's
    hold on the chip."""

    def __init__(self, src, dst):
        import os
        import tempfile

        root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            ".scratch")
        os.makedirs(root, exist_ok=True)
        self.dir = tempfile.mkdtemp(prefix="bench_edges_", dir=root)
        self.paths = []
        for name, a in (("src", src), ("dst", dst)):
            path = os.path.join(self.dir, f"{name}.npy")
            np.save(path, np.ascontiguousarray(a, np.int32))
            self.paths.append(path)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        import shutil

        shutil.rmtree(self.dir, ignore_errors=True)


def _load_edges(paths):
    return tuple(np.load(p, mmap_mode="r") for p in paths)


_MC: dict = {}


def _mc_init(paths, n_v: int) -> None:
    from gelly_tpu.utils import native as nat

    nat.sparse_codecs_available()  # build/dlopen outside the clock
    src, dst = _load_edges(paths)
    _MC.update(src=src, dst=dst, n_v=n_v)


def _mc_worker(rng_):
    lo, hi = rng_
    from gelly_tpu.utils import native as nat

    return nat.cc_chunk_combine_sparse(
        np.ascontiguousarray(_MC["src"][lo:hi]),
        np.ascontiguousarray(_MC["dst"][lo:hi]), None, _MC["n_v"]
    )


def baseline_cc_multicore(src: np.ndarray, dst: np.ndarray, n_v: int,
                          procs: int):
    """Wall-clock edges/sec of the P-process partitioned fold + forest
    merge (the reference's plan: per-partition partial fold, then the
    combine fan-in). On a host with fewer physical cores than ``procs``
    the processes timeshare — the measured rate then approximates the
    sequential rate, and the linear-scaling model (see
    ``vs_baseline_model32``) is the honest stand-in for real multicore.
    Worker start-up (spawn + imports) is outside the timed region; a
    failed or wedged pool raises — it never falls back to a sequential
    fold.
    """
    from gelly_tpu.utils import native as nat

    n = src.shape[0]
    step = -(-n // procs)
    ranges = [(lo, min(lo + step, n)) for lo in range(0, n, step)]
    with _EdgeFiles(src, dst) as files:
        if procs == 1:
            _mc_init(files.paths, n_v)
            t0 = time.perf_counter()
            parts = [_mc_worker(r) for r in ranges]
        else:
            import multiprocessing as mp

            with mp.get_context("spawn").Pool(
                procs, initializer=_mc_init, initargs=(files.paths, n_v)
            ) as pool:
                # Warm every worker (interpreter + imports) untimed.
                pool.map(abs, range(procs), chunksize=1)
                t0 = time.perf_counter()
                parts = pool.map_async(_mc_worker, ranges).get(timeout=600)
        # Forest merge: the partial forests' (vertex, root) pairs are
        # union edges; one more pass merges them (CombineCC's fan-in).
        if len(parts) > 1:
            av = np.concatenate([p[0] for p in parts])
            ar = np.concatenate([p[1] for p in parts])
            nat.cc_chunk_combine_sparse(av, ar, None, n_v)
        dt = time.perf_counter() - t0
    _MC.clear()
    return n / dt


# Child script of the isolated 1-core baseline (VERDICT r4 item 2: the
# in-process measurement swung 9x round-over-round — it timeshared the
# single core with the parent's JAX runtime/ingest threads). The child is
# a fresh interpreter with NOTHING else running: it regenerates the input
# (outside the timed region), folds it through the same native C++
# union-find N times, and reports every repeat so the parent can take
# median + spread.
_BASELINE_CHILD = r"""
import json, sys, time
import numpy as np
sys.path.insert(0, sys.argv[1])
import bench
from gelly_tpu.utils import native as nat
spec = json.loads(sys.argv[2])
src, dst = bench.synth_edges(spec["edges_total"], spec["vertices"],
                             seed=spec["seed"])
src = src[: spec["prefix"]]
dst = dst[: spec["prefix"]]
# One untimed warmup: the first fold after input generation pays page
# faults on the GB-scale table allocations (observed as a lone ~2.5x-low
# first repeat); the steady-state rate is the baseline being modeled.
nat.cc_chunk_combine_sparse(src, dst, None, spec["vertices"])
rates = []
for _ in range(spec["repeats"]):
    t0 = time.perf_counter()
    nat.cc_chunk_combine_sparse(src, dst, None, spec["vertices"])
    rates.append(src.shape[0] / (time.perf_counter() - t0))
print(json.dumps(rates))
"""


def isolated_1core_baseline(spec: dict, repeats: int = 5) -> dict:
    """Median-of-N single-core C++ baseline in an ISOLATED subprocess.

    ``spec`` = {edges_total, vertices, seed, prefix} — the synthetic
    stream is regenerated inside the child (pinned OUTSIDE the timed
    region), so no multi-GB arrays cross the process boundary and the
    measurement shares the core with nothing. Returns
    {median, min, max, repeats}; falls back to the in-process fold if the
    subprocess cannot run (the spread fields then record one sample).
    """
    import os
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    try:
        out = subprocess.run(
            [sys.executable, "-c", _BASELINE_CHILD, repo,
             json.dumps({**spec, "repeats": repeats})],
            capture_output=True, text=True, timeout=1200, check=True,
        )
        rates = sorted(json.loads(out.stdout.strip().splitlines()[-1]))
    except (subprocess.SubprocessError, ValueError, IndexError):
        src, dst = synth_edges(
            spec["edges_total"], spec["vertices"], seed=spec["seed"]
        )
        rates = [baseline_cc_multicore(
            src[: spec["prefix"]], dst[: spec["prefix"]],
            spec["vertices"], 1,
        )]
    return {
        "median": rates[len(rates) // 2],
        "min": rates[0],
        "max": rates[-1],
        "repeats": len(rates),
    }


def multicore_baseline_block(src, dst, n_v: int,
                             spec: dict | None = None) -> dict:
    """The multicore-baseline JSON fields shared by the CC benches.

    ``spec`` (edges_total/vertices/seed/prefix) routes the single-core
    measurement through :func:`isolated_1core_baseline` — median of N>=5
    repeats in a fresh subprocess, with min/max spread recorded (VERDICT
    r4 item 2). Without a spec (non-regenerable input), the in-process
    single-sample fold is used and the spread fields record one sample.
    """
    import os

    host_cores = os.cpu_count() or 1
    procs = max(host_cores, 1)
    if spec is not None:
        iso = isolated_1core_baseline(spec)
    else:
        one = baseline_cc_multicore(src, dst, n_v, 1)
        iso = {"median": one, "min": one, "max": one, "repeats": 1}
    eps_1 = iso["median"]
    eps_p = (
        baseline_cc_multicore(src, dst, n_v, procs)
        if procs > 1 else eps_1
    )
    return {
        # Optimized C++ union-find, one core, full reference plan —
        # median of the isolated repeats; README ratios quote this.
        "baseline_cpp_1core_eps": round(eps_1, 1),
        "baseline_cpp_1core_eps_median": round(iso["median"], 1),
        "baseline_cpp_1core_eps_min": round(iso["min"], 1),
        "baseline_cpp_1core_eps_max": round(iso["max"], 1),
        "baseline_repeats": iso["repeats"],
        # P = nproc worker processes + forest merge, wall-clock.
        "baseline_multicore_eps": round(eps_p, 1),
        "multicore_procs": procs,
        "host_cores": host_cores,
        # Linear-scaling model of the north-star's 32-core CPU bar:
        # 32 x the measured single-core C++ rate — an UPPER bound on any
        # real 32-core Flink deployment (assumes perfect scaling, zero
        # shuffle/serialization cost, and a faster-than-JVM per-core fold).
        "baseline_model32_eps": round(32 * eps_1, 1),
    }


# --------------------------------------------------------------------- #
# device-bound rates (VERDICT r2 item 4)
#
# Chunks pre-staged in HBM, codec off, fold+merge only: the device's own
# throughput, separated from host ingest and host->device transfer.


def _stage_raw_chunks(src, dst, chunk_size: int, max_edges: int):
    """Stack the stream into [K, C] i32 device arrays (+ total edges)."""
    import jax

    n_use = min(src.shape[0], max_edges)
    # A stream shorter than one chunk (reduced-size captures) stages as
    # a single whole-stream chunk instead of zero chunks; an EMPTY
    # stream must not zero the divisor.
    chunk_size = min(chunk_size, max(n_use, 1))
    n_use -= n_use % chunk_size  # whole chunks only: static shapes
    k = n_use // chunk_size
    s = jax.device_put(
        np.ascontiguousarray(src[:n_use], np.int32).reshape(k, chunk_size)
    )
    d = jax.device_put(
        np.ascontiguousarray(dst[:n_use], np.int32).reshape(k, chunk_size)
    )
    jax.block_until_ready((s, d))
    return s, d, n_use


def _device_bound_eps(fold_chunk, transform, init_state, staged,
                      chunk_size: int, repeats: int = 3) -> float:
    """Time scan(fold) over pre-staged [K, C] chunks + final transform.

    The timed region ends in a SCALAR D2H pull: a real completion
    barrier whose own transfer stays off the measured bytes.
    """
    import jax
    import jax.numpy as jnp

    s, d, n_use = staged

    @jax.jit
    def run(state, s, d):
        def step(acc, ck):
            return fold_chunk(acc, ck[0], ck[1]), None

        state, _ = jax.lax.scan(step, state, (s, d))
        out = transform(state)
        return jax.tree.reduce(
            lambda a, b: a + b,
            jax.tree.map(lambda l: jnp.sum(l.astype(jnp.int64)), out),
        )

    float(run(init_state, s, d))  # compile + drain the queue
    dt = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(run(init_state, s, d))
        dt = min(dt, time.perf_counter() - t0)
    return n_use / dt


def gather_study_block(n_v: int = 1 << 24, lanes: int = 1 << 22) -> dict:
    """The random-touch roofline study (the device fold's honest wall).

    Measures, on the attached device, the primitives the union-find fold
    is built from — so the recorded artifact can say WHERE the wall is
    rather than quote one end-to-end number:

    - ``xla_random_gather_mps`` — ``table[idx]``, uniform random idx: the
      ~140M touches/s element-granule HBM wall every chase/hook pays.
    - ``xla_sorted_gather_mps`` — same gather, pre-sorted idx: does XLA
      exploit locality on its own? (It lowers the same gather either
      way; this line proves it.)
    - ``pallas_sorted_gather_mps`` — the VMEM-blocked one-hot-MXU kernel
      (:func:`gelly_tpu.ops.pallas_kernels.sorted_window_gather`) on the
      same sorted idx: the achievable blocked random-touch rate.
    - ``pallas_blocked_roundtrip_mps`` — sort + kernel + unsort
      (:func:`~gelly_tpu.ops.pallas_kernels.blocked_gather`): what an
      UNSORTED gather costs when routed through the kernel — profitable
      only when two sorts undercut the random touches they replace.
    - ``sort_pairs_mlanes_ps`` — the 2-operand ``lax.sort`` rate: the
      regular-op currency the sort-dedup design spends.
    - ``xla_scatter_min_mps`` — the masked scatter-min hook rate.

    Off-TPU the kernels run interpreted (grid steps execute serially in
    Python), so shapes shrink and ``platform`` records that the numbers
    are structural only.
    """
    import jax
    import jax.numpy as jnp

    from gelly_tpu.ops import pallas_kernels as pk
    from gelly_tpu.ops.segments import masked_scatter_min

    tpu = pk.on_tpu()
    if not tpu:
        n_v = min(n_v, 1 << 18)
        lanes = min(lanes, 1 << 13)
    rng = np.random.default_rng(23)
    table = jax.device_put(rng.integers(0, n_v, n_v).astype(np.int32))
    ridx = jax.device_put(rng.integers(0, n_v, lanes).astype(np.int32))
    sidx = jax.device_put(np.sort(np.asarray(ridx)).astype(np.int32))
    jax.block_until_ready((table, ridx, sidx))

    def rate(fn, *args, repeats: int = 3) -> float:
        f = jax.jit(fn)
        float(f(*args))  # compile + drain (scalar D2H barrier)
        dt = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(f(*args))
            dt = min(dt, time.perf_counter() - t0)
        return lanes / dt / 1e6

    out = {
        "gather_table_slots": n_v,
        "gather_lanes": lanes,
        "gather_platform": "tpu" if tpu else "cpu-interpret",
        "xla_random_gather_mps": round(
            rate(lambda t, i: jnp.max(t[i]), table, ridx), 1),
        "xla_sorted_gather_mps": round(
            rate(lambda t, i: jnp.max(t[i]), table, sidx), 1),
        "sort_pairs_mlanes_ps": round(
            rate(lambda a, b: jnp.max(
                jax.lax.sort((a, b), num_keys=1)[0]), ridx, ridx), 1),
        "xla_scatter_min_mps": round(
            rate(lambda t, i: jnp.max(masked_scatter_min(
                t, i, jnp.zeros_like(i), jnp.ones(i.shape, bool))),
                table, ridx), 1),
    }
    try:
        out["pallas_sorted_gather_mps"] = round(
            rate(lambda t, i: jnp.max(pk.sorted_window_gather(t, i)),
                 table, sidx), 1)
        out["pallas_blocked_roundtrip_mps"] = round(
            rate(lambda t, i: jnp.max(pk.blocked_gather(t, i)),
                 table, ridx), 1)
    except Exception as e:  # noqa: BLE001 — study must land regardless
        out["pallas_gather_error"] = f"{type(e).__name__}: {e}"[:300]
    return out


def device_bound_cc_eps(src, dst, n_v: int, chunk_size: int,
                        max_edges: int = 1 << 25,
                        parity_out: dict | None = None,
                        fold_backend: str = "xla",
                        oracle: np.ndarray | None = None) -> float:
    """Device-resident CC rate: per-chunk raw union-find fold + label
    merge, HBM-staged input (the codec exists only because of the ingest
    link). Large chunks use the sort-dedup kernel
    (:func:`gelly_tpu.ops.unionfind.union_edges_dedup`, VERDICT r4
    item 4); ``parity_out`` receives an exact final-label check against
    the chunked numpy oracle on the same staged prefix (``oracle`` skips
    recomputing it when the caller already has the full-prefix labels).
    ``fold_backend`` selects the dedup fold's chase kernel (the
    ``fold_backend=`` plan knob): ``"pallas"`` = the VMEM-blocked sorted
    gather for the lo-endpoint chases."""
    import jax.numpy as jnp

    from gelly_tpu.library.connected_components import RAW_DEDUP_MIN_CHUNK
    from gelly_tpu.ops import segments, unionfind

    chunk_size = min(chunk_size, max(src.shape[0], 1), max(max_edges, 1))
    # Whether the timed fold actually runs the sort-dedup kernel (and so
    # whether a fold_backend= sweep leg exercised its backend at all):
    # reduced captures can clamp the chunk below the dedup threshold,
    # and a parity 'pass' from the generic path must not read as kernel
    # coverage.
    dedup_engaged = chunk_size >= RAW_DEDUP_MIN_CHUNK
    if parity_out is not None:
        parity_out["device_fold_dedup_engaged"] = dedup_engaged

    def fold_chunk(state, cs, cd):
        parent, seen = state
        ok = jnp.ones(cs.shape, bool)
        if dedup_engaged:
            parent = unionfind.union_edges_dedup(
                parent, cs, cd, ok,
                unique_cap=max(1 << 20, 3 * (chunk_size >> 4)),
                backend=fold_backend,
            )
        else:
            parent = unionfind.union_edges(parent, cs, cd, ok)
        seen = segments.mark_seen(seen, cs, ok)
        seen = segments.mark_seen(seen, cd, ok)
        return parent, seen

    def transform(state):
        return unionfind.component_labels(*state)

    init = (unionfind.fresh_forest(n_v), jnp.zeros((n_v,), bool))
    staged = _stage_raw_chunks(src, dst, chunk_size, max_edges)
    eps = _device_bound_eps(fold_chunk, transform, init, staged, chunk_size)
    if parity_out is not None:
        # Decomposition (same method as the MFU split): the timed program
        # includes the per-window full-capacity label transform; timing
        # the folds alone separates the kernel's rate from the
        # once-per-window transform share.
        eps_folds = _device_bound_eps(
            fold_chunk, lambda st: (st[0][:8], st[1][:8]),
            init, staged, chunk_size,
        )
        parity_out["device_fold_no_transform_eps"] = round(eps_folds, 1)
        import jax

        from gelly_tpu.library.connected_components import (
            cc_labels_numpy,
            cc_pairs_numpy,
        )

        s, d, n_use = staged

        @jax.jit
        def run_labels(state, s, d):
            def step(acc, ck):
                return fold_chunk(acc, ck[0], ck[1]), None

            state, _ = jax.lax.scan(step, state, (s, d))
            return transform(state)

        ours = np.asarray(run_labels(init, s, d))
        if oracle is None:
            pv, pr = [], []
            step = 1 << 22
            for lo in range(0, n_use, step):
                a, b = cc_pairs_numpy(src[lo:lo + step], dst[lo:lo + step],
                                      None, n_v)
                pv.append(a)
                pr.append(b)
            oracle = cc_labels_numpy(
                np.concatenate(pv).astype(np.int32),
                np.concatenate(pr).astype(np.int32), None, n_v,
            )
        parity_out["device_fold_parity"] = (
            "pass" if np.array_equal(ours, oracle) else "FAIL"
        )
        parity_out["device_fold_oracle"] = oracle
    return eps


def device_bound_cc_payload_eps(src, dst, n_v: int, chunk_size: int,
                                batch: int = 8,
                                max_edges: int = 1 << 26,
                                codec: str = "sparse",
                                compact_capacity: int | None = None,
                                info_out: dict | None = None) -> float:
    """Device side of the codec plan: fold_compressed over HBM-staged
    sparse payloads (+ the final label transform) — the fold the pipeline
    actually dispatches on device (the union-find partial fold runs in the
    host codec by design; raw-edge device folds are the codec-off figure).
    """
    import jax
    import jax.numpy as jnp

    from gelly_tpu.core.chunk import make_chunk
    from gelly_tpu.library.connected_components import connected_components

    agg = connected_components(n_v, merge="gather", codec=codec,
                               compact_capacity=compact_capacity)
    if agg.on_run_start is not None:
        agg.on_run_start()
    info = {} if info_out is None else info_out
    n_use = min(src.shape[0], max_edges)
    chunk_size = min(chunk_size, n_use)
    batch = max(1, min(batch, n_use // chunk_size))
    n_use -= n_use % (chunk_size * batch)
    payloads = [
        agg.host_compress(make_chunk(
            src[lo:lo + chunk_size], dst[lo:lo + chunk_size], device=False
        ))
        for lo in range(0, n_use, chunk_size)
    ]
    # One stacked row per fold_batch-sized group (the combining stacker
    # pre-merges each group's chunk forests on the host, mirroring the
    # pipeline's per-dispatch payload); the scan folds one row per step.
    n_batches = max(1, len(payloads) // batch)
    stacked = agg.stack_payloads(payloads, n_batches)
    stacked = {key: jax.device_put(a) for key, a in stacked.items()}

    @jax.jit
    def run(state, pl):
        def step(acc, p):
            return agg.fold_compressed(acc, p), None

        state, _ = jax.lax.scan(step, state, pl)
        return jnp.sum(agg.transform(state).astype(jnp.int64))

    float(run(agg.init(), stacked))  # compile + drain (incl. staging H2D)
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(run(agg.init(), stacked))
        dt = min(dt, time.perf_counter() - t0)
    # Padded pair lanes actually processed per timed run (the hbm_util
    # denominators; see STAR_FOLD_BYTES_PER_PAIR). "v" = pairs wire,
    # "m" = the round-5 segment wire.
    lanes_key = "m" if "m" in stacked else "v"
    if lanes_key in stacked:
        info["pair_lanes"] = int(np.prod(stacked[lanes_key].shape))
    info["wall_s"] = dt
    return n_use / dt


def device_bound_degrees_eps(src, dst, n_v: int, chunk_size: int,
                             max_edges: int = 1 << 25) -> float:
    """Device-resident degree-aggregate rate (±1 endpoint scatters)."""
    import jax.numpy as jnp

    from gelly_tpu.ops import segments

    def fold_chunk(deg, cs, cd):
        ok = jnp.ones(cs.shape, bool)
        one = jnp.ones(cs.shape, jnp.int64)
        deg = segments.masked_scatter_add(deg, cs, one, ok)
        deg = segments.masked_scatter_add(deg, cd, one, ok)
        return deg

    init = jnp.zeros((n_v,), jnp.int64)
    staged = _stage_raw_chunks(src, dst, chunk_size, max_edges)
    return _device_bound_eps(fold_chunk, lambda s: s, init, staged,
                             chunk_size)


def _overlap_block(stages: dict) -> dict:
    """Overlap-aware stage accounting for the pipelined executor.

    ``stages`` are thread-summed per-stage BUSY seconds plus
    ``total_wall``. ``overlap_efficiency`` = wall / max(busy): 1.0 means
    the wall collapsed onto the slowest stage (perfect overlap).
    ``pipeline_serial_sum_s`` is the serial cost of the fold path's three
    stages (compress + H2D + fold) — a healthy pipelined run lands
    ``total_wall`` below it (``wall_lt_pipeline_serial_sum``), which is
    exactly the win the executor exists for: on the r05 TPU capture those
    three ran back-to-back for 71% of an 11.0s wall.

    ``codec_wait`` (ordered-turn lock-wait the engine reclassified out of
    ``ingest_compress``) is excluded from the busy/efficiency math: it is
    serialization, not work — a genuinely serial run never waits there,
    so counting it would overstate the serial side of the comparison.
    It stays visible in the line's ``stages`` field.
    """
    from gelly_tpu.utils.metrics import overlap_stats

    tw = stages.get("total_wall")
    if not tw:
        return {}
    o = overlap_stats(stages, tw, exclude=("total_wall", "codec_wait"))
    pipeline_sum = sum(
        stages.get(k, 0.0)
        for k in ("ingest_compress", "h2d", "fold_dispatch")
    )
    return {
        "overlap_efficiency": o["overlap_efficiency"],
        "stage_busy_max_s": o["stage_busy_max_s"],
        "serial_stage_sum_s": o["serial_stage_sum_s"],
        "pipeline_serial_sum_s": round(pipeline_sum, 4),
        "wall_lt_pipeline_serial_sum": bool(tw < pipeline_sum),
    }


def codec_scaling_block(src, dst, n_v: int, chunk: int,
                        cap_edges: int = 1 << 24) -> dict:
    """Host-codec scaling row (VERDICT r3 item 3): edges/s of the
    per-chunk sparse combine with 1..W worker threads (the native
    combiner releases the GIL; each worker owns whole chunks, so combiner
    hash tables stay private). W = available cores — on this image's
    single-core host the row degenerates gracefully to one entry, and the
    linear story is measured rather than assumed wherever cores exist."""
    from concurrent.futures import ThreadPoolExecutor

    from gelly_tpu.core.chunk import make_chunk
    from gelly_tpu.engine.aggregation import available_cores
    from gelly_tpu.library.connected_components import connected_components

    agg = connected_components(n_v, codec="sparse")
    n = min(cap_edges, src.shape[0])
    n -= n % chunk
    chunks = [
        make_chunk(src[lo:lo + chunk], dst[lo:lo + chunk], device=False)
        for lo in range(0, n, chunk)
    ]
    avail = available_cores()
    rates = {}
    for w in range(1, avail + 1):
        dt = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            if w == 1:
                for c in chunks:
                    agg.host_compress(c)
            else:
                with ThreadPoolExecutor(w) as ex:
                    list(ex.map(agg.host_compress, chunks))
            dt = min(dt, time.perf_counter() - t0)
        rates[str(w)] = round(n / dt, 1)
    # In-process THREAD row (one point per available core); the
    # subprocess K-sweep with fixed K ∈ {1,2,4} is codec_workers_eps
    # (codec_workers_block).
    return {"ingest_workers": avail, "codec_threads_eps": rates}


def _codec_worker_main(paths, n_v: int, worker_id: int, workers: int,
                       n_chunks: int, chunk: int, ready, go, q) -> None:
    from gelly_tpu.utils import native as nat

    nat.sparse_codecs_available()  # build/dlopen outside the clock
    src, dst = _load_edges(paths)
    ready.put(worker_id)
    go.wait()
    for ci in range(worker_id, n_chunks, workers):
        lo = ci * chunk
        v, r = nat.cc_chunk_combine_sparse(
            np.ascontiguousarray(src[lo:lo + chunk]),
            np.ascontiguousarray(dst[lo:lo + chunk]), None, n_v
        )
        q.put((v, r))
    q.put(None)


def codec_workers_block(src, dst, n_v: int, chunk: int,
                        ks=(1, 2, 4), cap_edges: int = 1 << 24) -> dict:
    """Multi-worker codec scaling points (the deployment equation's
    measured side): K compressor SUBPROCESSES — spawned, own interpreter,
    own combiner hash tables, no JAX backend — each compressing every
    K-th chunk and feeding the (vertex, root) pair payloads through a
    queue to ONE consumer (this process), exactly the pipeline's shape.
    The clock starts once every worker has loaded its input. On a host
    with fewer cores than K the workers timeshare (oversubscribed is
    fine): the points then bound, rather than exhibit, linear scaling —
    ``host_cores`` rides along so readers can tell which regime a
    capture is in. A worker that fails or wedges raises.
    """
    import multiprocessing as mp
    import os

    from gelly_tpu.utils import native as nat

    n = min(cap_edges, src.shape[0])
    n -= n % chunk
    n_chunks = n // chunk
    if n_chunks == 0 or not nat.sparse_codecs_available():
        # Self-describing skip (the r05 capture recorded only {"1": ...}
        # with no explanation): an empty sweep must say WHY.
        return {
            "codec_workers_eps": {},
            "codec_workers_requested": list(ks),
            "codec_workers_skipped_reason": (
                "stream shorter than one chunk" if n_chunks == 0
                else "native sparse codec unavailable"
            ),
            "host_cores": os.cpu_count() or 1,
        }
    rates: dict = {}
    detail: dict = {}
    host_cores = os.cpu_count() or 1
    ctx = mp.get_context("spawn")
    with _EdgeFiles(src[:n], dst[:n]) as files:
        for k in ks:
            k_eff = min(k, n_chunks)
            q = ctx.Queue(maxsize=2 * k_eff)
            ready = ctx.Queue()
            go = ctx.Event()
            procs = [
                ctx.Process(
                    target=_codec_worker_main,
                    args=(files.paths, n_v, w, k_eff, n_chunks, chunk,
                          ready, go, q),
                    daemon=True,
                )
                for w in range(k_eff)
            ]
            try:
                for p in procs:
                    p.start()
                for _ in range(k_eff):
                    ready.get(timeout=600)
                t0 = time.perf_counter()
                go.set()
                done = 0
                while done < k_eff:
                    if q.get(timeout=600) is None:
                        done += 1
                dt = time.perf_counter() - t0
                for p in procs:
                    p.join(timeout=60)
            finally:
                for p in procs:
                    if p.is_alive():
                        p.terminate()
            rates[str(k)] = round(n / dt, 1)
            # Requested-vs-effective per K: a reduced capture (few
            # chunks, few-core host) reshapes the sweep — record the
            # clamp and the timesharing regime so the artifact explains
            # itself instead of looking like a truncated sweep.
            notes = []
            if k_eff < k:
                notes.append(
                    f"clamped to {k_eff}: stream has only {n_chunks} "
                    "chunks"
                )
            if k > host_cores:
                notes.append(
                    f"oversubscribed: {k} workers timeshare {host_cores} "
                    "core(s) — the point bounds, not exhibits, scaling"
                )
            detail[str(k)] = {
                "requested": k,
                "effective": k_eff,
                "mode": "subprocess",
                "note": "; ".join(notes) or None,
                "skipped_reason": None,
            }
    return {
        "codec_workers_eps": rates,
        "codec_workers_requested": list(ks),
        "codec_workers_detail": detail,
        "codec_workers_mode": "subprocess",
        "codec_workers_chunk": chunk,
        "codec_workers_edges": n,
        "host_cores": host_cores,
    }


def segment_compress_block(src, dst, n_v: int, chunk: int, batch: int,
                           compact_m: int) -> dict:
    """Compact-plan ingest artifacts (VERDICT r4 items 1+7), measured on
    the SAME input the headline runs (r4's scaling row timed the sparse
    codec while the headline ran compact — fixed by measuring the actual
    plan):

    - ``bare_combiner_eps`` — the fused native unit combine alone
      (cc_unit_begin/add/finish);
    - ``ingest_compress_eps`` — the full host compress: unit combine +
      ordered cid assignment + bucket stacking (what the pipeline's
      ``ingest_compress`` stage runs);
    - ``compress_vs_bare`` — their ratio (item 1's done bar: ~<=1.5x);
    - ``wire_mb`` / ``wire_bytes_per_edge`` — exact padded payload bytes
      shipped H2D for the whole stream (item 7's segment wire).
    """
    from gelly_tpu.core.chunk import make_chunk
    from gelly_tpu.library.connected_components import connected_components
    from gelly_tpu.utils import native

    if not native.unit_segments_available():
        return {}
    n = src.shape[0]
    unit = chunk * batch
    if n < unit:
        # Reduced-size captures: shrink the unit to the stream rather
        # than measuring zero edges (and dividing by them).
        unit = max(chunk, n - n % chunk)
    n -= n % unit
    if n == 0:
        return {}
    # Bare combine: the native two-level forest alone.
    t0 = time.perf_counter()
    for lo in range(0, n, unit):
        b = native.UnitForestBuilder(n_v)
        for clo in range(lo, lo + unit, chunk):
            b.add(src[clo:clo + chunk], dst[clo:clo + chunk], None)
        b.finish()
    bare_dt = time.perf_counter() - t0
    # Full host compress (combine + assign + stack), exact wire bytes.
    agg = connected_components(n_v, merge="gather", codec="compact",
                               compact_capacity=compact_m)
    agg.on_run_start()
    wire = 0
    t0 = time.perf_counter()
    for seq, lo in enumerate(range(0, n, unit)):
        payloads = [
            agg.host_compress(make_chunk(
                src[clo:clo + chunk], dst[clo:clo + chunk], device=False
            ))
            for clo in range(lo, lo + unit, chunk)
        ]
        stacked = agg.stack_payloads(payloads, 1, seq=seq)
        wire += sum(a.nbytes for a in stacked.values())
    full_dt = time.perf_counter() - t0
    return {
        "bare_combiner_eps": round(n / bare_dt, 1),
        "ingest_compress_eps": round(n / full_dt, 1),
        "compress_vs_bare": round(full_dt / bare_dt, 2),
        "wire_mb": round(wire / 1e6, 1),
        "wire_bytes_per_edge": round(wire / n, 3),
    }


def tpu_cc(src, dst, num_vertices: int, chunk_size: int, merge_every: int,
           fold_batch: int, codec: str = "auto",
           compact_capacity: int | None = None):
    import jax

    from gelly_tpu import edge_stream_from_edges  # noqa: F401  (registers x64)
    from gelly_tpu.core.io import EdgeChunkSource
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable
    from gelly_tpu.library.connected_components import connected_components

    def make_stream():
        # Ids are already dense in [0, num_vertices): the identity table is
        # the documented fast path, keeping hash densification out of the
        # measured region.
        srcq = EdgeChunkSource(src, dst, chunk_size=chunk_size,
                               table=IdentityVertexTable(num_vertices))
        return edge_stream_from_source(srcq, num_vertices)

    # The ingest codec (native C++ chunk combiner -> compressed forest
    # payloads -> batched device union) is the default CC plan; see
    # gelly_tpu/library/connected_components.py.
    agg = connected_components(num_vertices, merge="gather", codec=codec,
                               compact_capacity=compact_capacity)

    # Warmup: compile fold/merge on a tiny prefix (same static shapes).
    warm_n = min(src.shape[0], chunk_size * fold_batch)
    warm = EdgeChunkSource(src[:warm_n], dst[:warm_n], chunk_size=chunk_size,
                           table=IdentityVertexTable(num_vertices))
    warm_stream = edge_stream_from_source(warm, num_vertices)
    warm_stream.aggregate(agg, merge_every=merge_every,
                          fold_batch=fold_batch).result()

    # Best of 3 timed passes: the timed region ends in a real D2H pull
    # (completion barrier), and the repeats damp transient load on the
    # shared device link (run-to-run swings of 2x are routine there).
    dt = float("inf")
    timer = None
    for _ in range(3):
        stream = make_stream()
        t0 = time.perf_counter()
        res = stream.aggregate(agg, merge_every=merge_every,
                               fold_batch=fold_batch)
        labels = np.asarray(res.result())  # real completion barrier (D2H)
        t = time.perf_counter() - t0
        if t < dt:
            dt, timer = t, res.timer
    return labels, stream.ctx, dt, timer


def obs_trace_block(src, dst, n_v: int, chunk: int, merge_every: int,
                    fold_batch: int, codec: str, compact_capacity,
                    off_eps: float, workload: str) -> dict:
    """Tracer overhead + trace artifact (ISSUE 5 acceptance): re-run the
    pipeline with an installed ``obs.SpanTracer`` — same knobs and
    best-of-3 policy as the tracer-off headline — record tracer-on eps
    against it, and write the best pass's validated Chrome-trace JSON
    (Perfetto-loadable, one track per stage/worker, bus counters in
    ``otherData``) next to bench.py as ``trace_<workload>.json``.

    The overhead contract is <2% on the TPU capture; the committed CPU
    artifact documents the schema at reduced size (CPU walls swing more
    than 2% run to run, so ``overhead_lt_2pct`` is a v5e claim).

    ISSUE 14: a THIRD interleaved pass measures histogram/watermark
    recording alone (``obs.record_metrics()``, no tracer) on the same
    shared compiled plan — ``hist_overhead_frac`` rides next to
    ``tracer_overhead_frac`` under the same <2% contract, and the
    recorded fold-dispatch quantiles land in the block so the capture
    documents the histogram schema too.
    """
    import os

    from gelly_tpu import obs
    from gelly_tpu.core.io import EdgeChunkSource
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable
    from gelly_tpu.library.connected_components import connected_components

    agg = connected_components(n_v, merge="gather", codec=codec,
                               compact_capacity=compact_capacity)
    n_e = src.shape[0]

    def one_pass(tracer, record=False):
        # Identical pass every way — same compiled plan (cached on the
        # agg instance), same D2H completion barrier; only the installed
        # tracer / recording flag differs, so the comparison isolates
        # observability cost from compile/warmup variance. Each pass
        # gets its OWN bus scope, so the snapshot exported with the
        # trace describes exactly the traced run — never a multi-pass
        # sum.
        import contextlib

        srcq = EdgeChunkSource(src, dst, chunk_size=chunk,
                               table=IdentityVertexTable(n_v))
        stream = edge_stream_from_source(srcq, n_v)
        with obs.scope() as bus:
            rec_ctx = (obs.record_metrics() if record
                       else contextlib.nullcontext())
            ctx = (obs.install(tracer) if tracer is not None
                   else contextlib.nullcontext())
            t0 = time.perf_counter()
            with rec_ctx, ctx:
                res = stream.aggregate(agg, merge_every=merge_every,
                                       fold_batch=fold_batch)
                np.asarray(res.result())
            dt = time.perf_counter() - t0
            return dt, bus.snapshot()

    one_pass(None)  # compile warmup outside all measurements
    dt_off = dt_on = dt_hist = float("inf")
    best = None
    bus_snap: dict = {}
    hist_snap: dict = {}
    # Interleaved best-of-3 triples: shared-link load swings hit every
    # side alike instead of biasing one.
    for _ in range(3):
        dt_off = min(dt_off, one_pass(None)[0])
        tr = obs.SpanTracer(capacity=1 << 16, heartbeat_every_s=30.0)
        t, snap = one_pass(tr)
        if t < dt_on:
            dt_on, best, bus_snap = t, tr, snap
        t, snap = one_pass(None, record=True)
        if t < dt_hist:
            dt_hist, hist_snap = t, snap
    on_eps = n_e / dt_on
    path = trace_out_path(f"trace_{workload}")
    trace = obs.write_chrome_trace(  # validates the schema before writing
        path, best, extra={"workload": workload, **bus_snap},
    )
    overhead = dt_on / dt_off - 1.0
    hist_overhead = dt_hist / dt_off - 1.0
    return {"obs": {
        "headline_eps": round(off_eps, 1),
        "tracer_off_eps": round(n_e / dt_off, 1),
        "tracer_on_eps": round(on_eps, 1),
        "tracer_overhead_frac": round(max(0.0, overhead), 4),
        "overhead_lt_2pct": bool(overhead < 0.02),
        "hist_on_eps": round(n_e / dt_hist, 1),
        "hist_overhead_frac": round(max(0.0, hist_overhead), 4),
        "hist_overhead_lt_2pct": bool(hist_overhead < 0.02),
        "fold_dispatch_ms": hist_snap.get("histograms", {}).get(
            "engine.fold_dispatch_ms", {}),
        "backlog_final": hist_snap.get("watermarks", {}).get(
            "stream", {}),
        "trace_file": os.path.basename(path),
        "trace_events": len(trace["traceEvents"]),
        "trace_id": best.trace_id,
        "spans_dropped": best.dropped,
        "heartbeats": len(best.instants("heartbeat")),
    }}


def components_of(labels_by_id: dict) -> set[frozenset]:
    comps: dict[int, set] = {}
    for v, lbl in labels_by_id.items():
        comps.setdefault(lbl, set()).add(v)
    return {frozenset(c) for c in comps.values()}


# --------------------------------------------------------------------- #
# additional BASELINE workloads


def _dataset(name: str):
    """Checked-in dataset fixture path, or None (bench falls back to the
    synthetic stream). See data/: generated samples shaped like the
    BASELINE workloads' named datasets (ego-Facebook / movielens-10k)."""
    import os

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", name)
    return p if os.path.exists(p) else None


def bench_degrees(args):
    """Workload #1: continuous degree aggregate (getDegrees,
    SimpleEdgeStream.java:413-478) over the ego-Facebook-shaped fixture
    (BASELINE config #1) through the native parser; synthetic fallback.
    Baseline: per-edge HashMap updates."""
    import jax

    from gelly_tpu.core.io import EdgeChunkSource, read_edge_list
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable

    ds = _dataset("facebook_like.txt")
    if ds is not None:
        fsrc, fdst, _ = read_edge_list(ds)  # native C++ parser path
        reps = max(1, args.edges // fsrc.shape[0])
        # Densify to i32 once at stream prep (ids fit the fixture's 4096-
        # slot space): the identity table then slices chunks zero-copy.
        src = np.concatenate([fsrc.astype(np.int32)] * reps)
        dst = np.concatenate([fdst.astype(np.int32)] * reps)
        args = argparse.Namespace(**vars(args))
        args.vertices = 4096  # fixture id space, power-of-two capacity
        args.edges = src.shape[0]
        args.chunk_size = 1 << 21  # tiny deltas per chunk: favor big chunks
    else:
        src, dst = synth_edges(args.edges, args.vertices)

    # The TPU path runs at full stream scale (fixed dispatch costs amortize
    # over the stream, as in deployment); the interpreted per-edge baseline
    # loop is rate-stable, so its edges/sec is measured on a bounded prefix
    # and compared rate-to-rate.
    n_base = min(args.edges, 2_000_000)

    from gelly_tpu.library.degrees import degree_aggregate

    agg = degree_aggregate(args.vertices)
    # Degree payloads are tiny dense vectors (N*4 bytes regardless of chunk
    # size), while each H2D dispatch carries a fixed cost — so batch
    # aggressively: fewer, bigger uploads.
    merge_every = max(args.merge_every, 16)
    fold_batch = max(args.fold_batch, 16)

    def stream():
        return edge_stream_from_source(
            EdgeChunkSource(src, dst, chunk_size=args.chunk_size,
                            table=IdentityVertexTable(args.vertices)),
            args.vertices,
        )

    np.asarray(stream().aggregate(
        agg, merge_every=merge_every, fold_batch=fold_batch
    ).result())  # warmup/compile
    dt, stages = float("inf"), {}
    for _ in range(2):
        t0 = time.perf_counter()
        res = stream().aggregate(
            agg, merge_every=merge_every, fold_batch=fold_batch
        )
        final = np.asarray(res.result())  # real D2H pull (completion barrier)
        wall = time.perf_counter() - t0
        if wall < dt:
            dt = wall
            stages = {k: round(v, 4) for k, v in res.timer.totals.items()}
    print(json.dumps({"stage_breakdown": "degree_aggregate",
                      "total_wall": round(dt, 4),
                      "merge_every": merge_every, "fold_batch": fold_batch,
                      **stages}),
          file=sys.stderr)

    deg: dict[int, int] = {}
    t0 = time.perf_counter()
    for u, v in zip(src[:n_base].tolist(), dst[:n_base].tolist()):
        deg[u] = deg.get(u, 0) + 1
        deg[v] = deg.get(v, 0) + 1
    dt_base = time.perf_counter() - t0
    if not args.skip_parity:
        if n_base < args.edges:  # finish the oracle with vectorized counts
            deg_v = (
                np.bincount(src[n_base:], minlength=args.vertices)
                + np.bincount(dst[n_base:], minlength=args.vertices)
            )
            for i in np.nonzero(deg_v)[0].tolist():
                deg[i] = deg.get(i, 0) + int(deg_v[i])
        nz = np.nonzero(final)[0]
        ours = {int(i): int(final[i]) for i in nz}
        if ours != deg:
            raise SystemExit("degree parity FAILED")
    dev_eps = device_bound_degrees_eps(
        src, dst, args.vertices, min(args.chunk_size, 1 << 21)
    )
    peaks = chip_peaks()
    hbm_gbps = dev_eps * DEGREE_FOLD_BYTES_PER_EDGE / 1e9
    return ("degree_aggregate_throughput", args.edges / dt, n_base / dt_base,
            {"device_fold_eps": round(dev_eps, 1),
             # Logical-bytes roofline of the scatter-add fold (see
             # DEGREE_FOLD_BYTES_PER_EDGE).
             "fold_hbm_gbps": round(hbm_gbps, 1),
             "fold_hbm_util": (
                 round(hbm_gbps / peaks["peak_hbm_gbps"], 4)
                 if peaks["peak_hbm_gbps"] else None)})


def bench_triangles(args):
    """Workload #3: window triangle count (WindowTriangles.java). Baseline:
    per-window python adjacency + per-edge common-neighbor counting."""
    import jax  # noqa: F401

    from gelly_tpu.core.io import EdgeChunkSource, TimeCharacteristic
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable

    from gelly_tpu.ops.pallas_kernels import on_tpu as _tri_on_tpu

    # 2M edges / 10 windows: large enough that fixed per-run dispatch and
    # pull costs stop dominating the measured rate, small enough for the
    # per-window python oracle.
    n_e = min(args.edges, 2_000_000)
    n_v = min(args.vertices, 1 << 12)
    if not _tri_on_tpu():
        # Off-TPU every MXU tier runs through the Pallas interpreter
        # (serial Python grid steps): shrink to structural sizes so the
        # CPU artifact still carries the full line (figures marked by
        # the capture's chip field, never quoted as perf).
        n_e = min(n_e, 200_000)
        n_v = min(n_v, 1 << 9)
    src, dst = synth_edges(n_e, n_v)
    ts = np.arange(n_e, dtype=np.int64)  # 10 windows
    window_ms = n_e // 10
    # Window buffers are wire-padded to capacity; size them to the real
    # window content (window_ms edges, doubled for the ALL-direction
    # calibration the API expects) instead of chunk-size heuristics.
    window_capacity = 1 << (2 * window_ms - 1).bit_length()

    def stream():
        return edge_stream_from_source(
            EdgeChunkSource(src, dst, timestamps=ts,
                            chunk_size=args.chunk_size,
                            table=IdentityVertexTable(n_v),
                            time=TimeCharacteristic.EVENT),
            n_v,
        )

    from gelly_tpu.library.triangles import window_triangle_counts_batched

    list(window_triangle_counts_batched(
        stream(), window_ms, window_capacity=window_capacity,
        batch=10))  # warmup
    import jax.numpy as jnp

    dt = float("inf")
    for _ in range(3):  # best-of-3: damp shared-device variance
        t0 = time.perf_counter()
        # Keep per-window counts on device; one batched pull at the end
        # (each host sync stalls until the device drains).
        wins, counts = zip(*window_triangle_counts_batched(
            stream(), window_ms, window_capacity=window_capacity,
        batch=10))
        counts = np.asarray(jnp.stack(counts))
        dt = min(dt, time.perf_counter() - t0)
    ours = dict(zip(wins, counts.tolist()))

    # Device-bound kernel rate: all 10 canonical-dedup window columns
    # pre-staged in HBM, one grouped dispatch, scalar-sized pull — what
    # the count kernel sustains without the per-run transfer and host
    # costs the pipeline figure above carries.
    from gelly_tpu.library.triangles import (
        _packed_out_windows,
        _window_triangle_count_packed_group,
    )
    from gelly_tpu.ops import segments as _segments

    cols = [c for _, c in _packed_out_windows(
        stream(), window_ms, window_capacity, n_v
    )]
    bucket = max(1024, 1 << max(
        0, max(c.shape[0] for c in cols) - 1
    ).bit_length())
    staged = np.full((len(cols), bucket), _segments.INT_MAX, np.int32)
    for i, c in enumerate(cols):
        staged[i, : c.shape[0]] = c
    staged = jax.device_put(staged)
    np.asarray(_window_triangle_count_packed_group(staged, n_v, n_v, "mxu"))
    dt_kernel = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(_window_triangle_count_packed_group(
            staged, n_v, n_v, "mxu"
        ))
        dt_kernel = min(dt_kernel, time.perf_counter() - t0)

    # MFU decomposition (VERDICT r4 item 8): the whole-dispatch mfu
    # divides the group's FLOPs by a wall that includes the fixed
    # dispatch latency (the experiment below measures it). Re-running
    # the same program over a 4x-replicated window group isolates the
    # MARGINAL kernel rate: (extra FLOPs) / (extra wall). On the chip
    # tool's v5e this is not measured yet.
    staged4 = jnp.tile(staged, (4, 1))
    np.asarray(_window_triangle_count_packed_group(staged4, n_v, n_v, "mxu"))
    dt_kernel4 = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(_window_triangle_count_packed_group(
            staged4, n_v, n_v, "mxu"
        ))
        dt_kernel4 = min(dt_kernel4, time.perf_counter() - t0)

    # Third tier: the Pallas wedge MATMUL alone (same marginal method, on
    # the first real window's mask) — separates the MXU kernel's own
    # efficiency from the program's adjacency-build scatters, which hit
    # the same ~140M random-accesses/s wall as every scatter on this chip.
    from gelly_tpu.ops.pallas_kernels import wedge_count_matrix

    valid0 = staged[0] != (np.iinfo(np.int32).max)
    safe0 = jnp.where(valid0, staged[0], 0)
    a0 = (safe0 // n_v).astype(jnp.int32)
    b0 = (safe0 % n_v).astype(jnp.int32)
    mask0 = jnp.zeros((n_v, n_v), bool).at[a0, b0].max(valid0, mode="drop")
    mask0 = mask0 | mask0.T

    @jax.jit
    def wedge_k(ms):
        return jax.lax.map(lambda x: wedge_count_matrix(x)[0, 0], ms)

    def time_wedge(k):
        ms = jnp.broadcast_to(mask0[None], (k,) + mask0.shape)
        np.asarray(wedge_k(ms))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(wedge_k(ms))
            best = min(best, time.perf_counter() - t0)
        return best

    w_lo, w_hi = time_wedge(4), time_wedge(16)

    # Secondary figure: the degree-bucketed sparse windowed path — the
    # large-n_v workhorse (VERDICT r3 item 4). Zipf endpoints (a=1.6):
    # realistic skew, no toy degree cap — the bucketed path adapts its
    # table depth to each window's true max degree and splits the D x D
    # intersections by actual row fill.
    from gelly_tpu.library.triangles import (
        _bucketize_window,
        _stack_bucketed,
        _window_triangle_count_bucketed_group,
        window_triangles_bucketed,
    )

    rng = np.random.default_rng(31)
    n_v_sp = 1 << 20
    # Fixed scale, decoupled from the dense workload's clamped edge count:
    # ~10M edges amortize the per-dispatch fixed cost, and the python
    # oracle's one timed pass stays ~10s.
    n_sp = 10_000_000 if _tri_on_tpu() else 500_000
    src_sp = (rng.zipf(1.6, n_sp) % n_v_sp).astype(np.int64)
    dst_sp = (rng.zipf(1.6, n_sp) % n_v_sp).astype(np.int64)
    ts_sp = np.arange(n_sp, dtype=np.int64)
    wsz = n_sp // 10

    def stream_sp():
        return edge_stream_from_source(
            EdgeChunkSource(src_sp, dst_sp, timestamps=ts_sp,
                            chunk_size=args.chunk_size,
                            table=IdentityVertexTable(n_v_sp),
                            time=TimeCharacteristic.EVENT),
            n_v_sp,
        )

    sp_kw = dict(window_capacity=4 * wsz, batch=10)
    list(window_triangles_bucketed(stream_sp(), wsz, **sp_kw))
    dt_sp = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        ws_sp, cs = zip(*window_triangles_bucketed(
            stream_sp(), wsz, **sp_kw
        ))
        cs = np.asarray(jnp.stack(cs))
        dt_sp = min(dt_sp, time.perf_counter() - t0)

    # Device-bound kernel rate: host prep + payload staging untimed, one
    # grouped dispatch timed (the pipeline figure above carries the host
    # prep and the transfer).
    payloads_sp = [
        _bucketize_window(
            src_sp[w0:w0 + wsz], dst_sp[w0:w0 + wsz],
            np.ones(wsz, bool), n_v_sp, None,
        )
        for w0 in range(0, n_sp, wsz)
    ]
    payload_sp, t_cap, d_sp, h_cap, ladder_sp = _stack_bucketed(payloads_sp)
    dev_sp = jax.tree.map(jax.device_put, payload_sp)
    jax.tree.map(np.asarray, dev_sp)
    np.asarray(_window_triangle_count_bucketed_group(
        dev_sp, t_cap, d_sp, h_cap, ladder_sp
    ))
    dt_spk = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out_sp = _window_triangle_count_bucketed_group(
            dev_sp, t_cap, d_sp, h_cap, ladder_sp
        )
        float(jnp.sum(out_sp))
        dt_spk = min(dt_spk, time.perf_counter() - t0)

    # Sparse-path python baseline: same per-window set-intersection oracle
    # as the dense workload — also the parity oracle for the sparse
    # counts. One full timed pass (rate is flat; it doubles as the oracle).
    t0 = time.perf_counter()
    sp_base: dict[int, int] = {}
    for w0 in range(0, n_sp, wsz):
        adj_sp: dict[int, set] = {}
        seen_sp = set()
        for i in range(w0, min(w0 + wsz, n_sp)):
            a, b = int(src_sp[i]), int(dst_sp[i])
            if a == b or (a, b) in seen_sp or (b, a) in seen_sp:
                continue
            seen_sp.add((a, b))
            adj_sp.setdefault(a, set()).add(b)
            adj_sp.setdefault(b, set()).add(a)
        sp_base[w0 // wsz] = sum(
            1 for a, b in seen_sp
            for u in adj_sp[a] & adj_sp[b] if u < min(a, b)
        )
    dt_sp_base = time.perf_counter() - t0
    if not args.skip_parity:
        if dict(zip(ws_sp, cs.tolist())) != sp_base:
            raise SystemExit("sparse window-triangle parity FAILED")

    # Best-of-2 like the accelerator side: the interpreted loop shares the
    # single CPU core with background load, and a one-shot timing has
    # swung the reported ratio by ~2x run to run.
    dt_base = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        base: dict[int, int] = {}
        for w in range(0, n_e, window_ms):
            adj: dict[int, set] = {}
            cnt = 0
            seen = set()
            for i in range(w, min(w + window_ms, n_e)):
                a, b = int(src[i]), int(dst[i])
                if a == b or (a, b) in seen or (b, a) in seen:
                    continue
                seen.add((a, b))
                adj.setdefault(a, set()).add(b)
                adj.setdefault(b, set()).add(a)
            for a, b in seen:
                lo = min(a, b)
                cnt += sum(1 for u in adj[a] & adj[b] if u < lo)
            base[w // window_ms] = cnt
        dt_base = min(dt_base, time.perf_counter() - t0)
    if ours != base:
        raise SystemExit(f"triangle parity FAILED: {ours} vs {base}")
    # MXU roofline: the wedge kernel computes W = M^T M per window —
    # 2 * n_v^3 FLOPs each (f32 accumulation on the MXU), len(cols)
    # windows per timed dispatch group.
    peaks = chip_peaks()
    mxu_tflops = len(cols) * 2 * (n_v ** 3) / dt_kernel / 1e12
    # Marginal rate over the 3 extra window-group replicas: the fixed
    # dispatch cost cancels, leaving the kernel's own sustained rate.
    marg_dt = max(dt_kernel4 - dt_kernel, 1e-9)
    marg_tflops = 3 * len(cols) * 2 * (n_v ** 3) / marg_dt / 1e12
    return ("window_triangles_throughput", n_e / dt, n_e / dt_base,
            {"device_kernel_eps": round(n_e / dt_kernel, 1),
             "mxu_tflops": round(mxu_tflops, 2),
             "mfu": (round(mxu_tflops / peaks["peak_bf16_tflops"], 4)
                     if peaks["peak_bf16_tflops"] else None),
             # Fixed-dispatch-free kernel rate (see decomposition above):
             # the figure comparable to an MXU roofline.
             "mfu_marginal": (
                 round(marg_tflops / peaks["peak_bf16_tflops"], 4)
                 if peaks["peak_bf16_tflops"] else None),
             # The Pallas W = MᵀM matmul alone, marginal over 12 extra
             # windows: the MXU kernel's own sustained fraction of peak.
             "mfu_wedge_kernel": (
                 round(
                     12 * 2 * (n_v ** 3) / max(w_hi - w_lo, 1e-9) / 1e12
                     / peaks["peak_bf16_tflops"], 4,
                 )
                 if peaks["peak_bf16_tflops"] else None),
             "dispatch_fixed_ms": round(
                 max(0.0, (4 * dt_kernel - dt_kernel4) / 3) * 1000, 1),
             "sparse_pipeline_eps": round(n_sp / dt_sp, 1),
             "sparse_pipeline_vs_baseline": round(dt_sp_base / dt_sp, 2),
             "sparse_kernel_eps": round(n_sp / dt_spk, 1),
             "sparse_vs_baseline": round(
                 (n_sp / dt_spk) / (n_sp / dt_sp_base), 2),
             "sparse_kernel_vertices": n_v_sp,
             "sparse_edges": n_sp})


def bench_spanner(args) -> dict:
    """Device-rate k-spanner (VERDICT r4 item 9): the batched closed-form
    distance-2 gate (library/spanner.py:_sparse_fold_chunk_k2) folding a
    Zipf stream at n_v = 2^20 on device — vs the ~5k edges/s per-edge BFS
    scan it replaces. A sampled host BFS oracle asserts the stretch bound
    on the accepted spanner for a random subset of input edges."""
    import jax
    import jax.numpy as jnp

    from gelly_tpu.library.spanner import (
        SparseSpannerSummary,
        _sparse_fold_chunk_k2,
    )

    n_v, D, sub = 1 << 20, 16, 1 << 14
    n_e = 1 << 21
    rng = np.random.default_rng(31)
    src = (rng.zipf(1.6, n_e) % n_v).astype(np.int32)
    dst = (rng.zipf(1.6, n_e) % n_v).astype(np.int32)
    sd = jax.device_put(jnp.asarray(src))
    dd = jax.device_put(jnp.asarray(dst))
    ok = jnp.ones(n_e, bool)

    def init():
        return SparseSpannerSummary(
            nbr=jnp.full((n_v, D), -1, jnp.int32),
            deg=jnp.zeros((n_v,), jnp.int32),
            esrc=jnp.zeros((n_e,), jnp.int32),
            edst=jnp.zeros((n_e,), jnp.int32),
            n=jnp.zeros((), jnp.int32),
            overflow=jnp.zeros((), bool),
            deg_overflow=jnp.zeros((), jnp.int32),
        )

    fold = jax.jit(
        lambda s, a, b, o: _sparse_fold_chunk_k2(s, a, b, o, D, sub)
    )
    out = fold(init(), sd, dd, ok)
    int(out.n)  # compile + drain
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = fold(init(), sd, dd, ok)
        accepted = int(out.n)  # scalar D2H completion barrier
        dt = min(dt, time.perf_counter() - t0)
    # Sampled stretch oracle: every sampled INPUT edge's endpoints must be
    # within k=2 hops in the accepted spanner (or be an accepted edge).
    es = np.asarray(out.esrc)[:accepted]
    ed = np.asarray(out.edst)[:accepted]
    adj: dict[int, set] = {}
    for a, b in zip(es.tolist(), ed.tolist()):
        adj.setdefault(a, set()).add(b)
        adj.setdefault(b, set()).add(a)
    idx = rng.choice(n_e, 500, replace=False)
    bad = 0
    for i in idx.tolist():
        a, b = int(src[i]), int(dst[i])
        if a == b or b in adj.get(a, ()):  # direct
            continue
        if adj.get(a, set()) & adj.get(b, set()):  # within 2
            continue
        bad += 1
    return {
        "metric": "spanner_device",
        "value": round(n_e / dt, 1),
        "unit": "edges/sec",
        "vertices": n_v,
        "k": 2,
        "max_degree": D,
        "gate_batch": sub,
        "accepted_edges": accepted,
        "deg_overflow": int(out.deg_overflow),
        "stretch_sample": "pass" if bad == 0 else f"FAIL ({bad}/500)",
    }


def bench_bipartiteness(args):
    """Workload #4: bipartiteness check (BipartitenessCheck.java). Runs the
    ingest-codec plan (native parity combiner) at CC-like scale. Baseline:
    per-edge parity DSU in python (Candidates-equivalent), timed on a
    prefix."""
    import jax

    from gelly_tpu.core.io import EdgeChunkSource
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable
    from gelly_tpu.library.bipartiteness import bipartiteness_check

    n_e = min(args.edges, 16_000_000)
    chunk = min(max(args.chunk_size, 1 << 18), 1 << 23)
    merge_every, fold_batch = 4, 4
    src, dst = synth_edges(n_e, args.vertices)
    agg = bipartiteness_check(args.vertices)

    def stream():
        return edge_stream_from_source(
            EdgeChunkSource(src, dst, chunk_size=chunk,
                            table=IdentityVertexTable(args.vertices)),
            args.vertices,
        )

    warm = stream().aggregate(agg, merge_every=merge_every,
                              fold_batch=fold_batch).result()
    np.asarray(warm.labels)
    dt, stages = float("inf"), {}
    for _ in range(2):
        s = stream()
        t0 = time.perf_counter()
        out = s.aggregate(agg, merge_every=merge_every,
                          fold_batch=fold_batch)
        res = out.result()
        np.asarray(res.labels)  # real completion barrier (D2H pull)
        wall = time.perf_counter() - t0
        if wall < dt:
            dt = wall
            stages = {k: round(v, 4) for k, v in out.timer.totals.items()}
    print(json.dumps({"stage_breakdown": "bipartiteness",
                      "total_wall": round(dt, 4), **stages}),
          file=sys.stderr)

    parent: dict = {}
    rel: dict = {}

    def find(x):
        path = []
        while parent[x] != x:
            path.append(x)
            x = parent[x]
        r = 0
        for p in reversed(path):
            r ^= rel[p]
            parent[p], rel[p] = x, r
        return x

    state = {"ok": True}

    def fold(s, d):
        for u, v in zip(s.tolist(), d.tolist()):
            for x in (u, v):
                if x not in parent:
                    parent[x], rel[x] = x, 0
            ru, rv = find(u), find(v)
            pu, pv = rel[u], rel[v]
            if ru == rv:
                if pu == pv:
                    state["ok"] = False
            else:
                parent[ru] = rv
                rel[ru] = pu ^ pv ^ 1

    n_base = min(n_e, 4_000_000)  # per-edge python: timed prefix, rate is flat
    t0 = time.perf_counter()
    fold(src[:n_base], dst[:n_base])
    dt_base = time.perf_counter() - t0
    if not args.skip_parity:
        fold(src[n_base:], dst[n_base:])  # untimed remainder for the oracle
        if bool(res.ok) != state["ok"]:
            raise SystemExit(
                f"bipartiteness parity FAILED: {bool(res.ok)} vs {state['ok']}"
            )
    return "bipartiteness_throughput", n_e / dt, n_base / dt_base


def bench_matching(args):
    """Workload #5: greedy weighted matching
    (CentralizedWeightedMatching.java:76-107) over the movielens-shaped
    weighted stream fixture (BASELINE config #5) through the native
    parser; synthetic fallback. Both sides are sequential host loops by
    design (the stage is centralized in the reference too); ours adds the
    chunked-stream plumbing around the same algorithm."""
    from gelly_tpu.core.io import EdgeChunkSource, read_edge_list
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable
    from gelly_tpu.library.matching import weighted_matching

    ds = _dataset("ratings_like.txt")
    # The native fold runs ~20M edges/s, so a big enough stream is needed
    # for a stable timed region; the python baseline loop doubles as the
    # full-stream parity oracle, which bounds the practical size.
    if ds is not None:
        fsrc, fdst, fval = read_edge_list(ds, num_value_cols=1)
        reps = max(1, min(args.edges, 4_000_000) // fsrc.shape[0])
        # Each repetition permutes the id space (a fresh isomorphic
        # instance): verbatim repeats would mostly no-op through the
        # matcher and flatter the measured rate.
        rng = np.random.default_rng(11)
        perms = [rng.permutation(4096).astype(np.int32)
                 for _ in range(reps)]
        src = np.concatenate([p[fsrc] for p in perms])
        dst = np.concatenate([p[fdst] for p in perms])
        w = np.concatenate([fval] * reps)
        args = argparse.Namespace(**vars(args))
        args.vertices = 4096
        n_e = src.shape[0]
    else:
        n_e = min(args.edges, 2_000_000)  # sequential workload: bounded
        src, dst = synth_edges(n_e, args.vertices)
        rng = np.random.default_rng(3)
        w = rng.integers(1, 1000, n_e).astype(np.float64)

    def stream():
        return edge_stream_from_source(
            EdgeChunkSource(src, dst, val=w, chunk_size=args.chunk_size,
                            table=IdentityVertexTable(args.vertices)),
            args.vertices,
        )

    weighted_matching(stream()).final()  # warmup
    dt = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        ours = {(a, b): wt for a, b, wt in
                weighted_matching(stream()).final_matching()}
        dt = min(dt, time.perf_counter() - t0)

    t0 = time.perf_counter()
    matching: dict[int, tuple] = {}  # endpoint -> (a, b, w)
    for u, v, wt in zip(src.tolist(), dst.tolist(), w.tolist()):
        if u == v:
            continue
        coll = {id(e): e for x in (u, v) if x in matching
                for e in [matching[x]]}
        if wt > 2 * sum(e[2] for e in coll.values()):
            for e in coll.values():
                matching.pop(e[0], None)
                matching.pop(e[1], None)
            matching[u] = matching[v] = (u, v, wt)
        del coll
    base = {(min(a, b), max(a, b)): wt
            for a, b, wt in set(matching.values())}
    dt_base = time.perf_counter() - t0
    if ours != base:
        raise SystemExit(
            f"matching parity FAILED ({len(ours)} vs {len(base)} edges)"
        )
    return "weighted_matching_throughput", n_e / dt, n_e / dt_base


def bench_cc(args) -> dict:
    """North-star workload #2: streaming Connected Components."""
    src, dst = synth_edges(args.edges, args.vertices)

    labels, ctx, dt_tpu, timer = tpu_cc(
        src, dst, args.vertices, args.chunk_size, args.merge_every,
        args.fold_batch,
    )
    eps = args.edges / dt_tpu

    dt_base, n_base = baseline_cc(src, dst)
    base_eps = n_base / dt_base
    numpy_eps, oracle_labels = baseline_cc_numpy(
        src, dst, args.vertices, args.chunk_size,
        # Keep the timed prefix >= 2 chunks so the numpy side still
        # exercises the chunked fold+merge pipeline it claims to measure.
        cap_edges=max(8_000_000, 2 * args.chunk_size),
    )

    if not args.skip_parity:
        lab = np.asarray(labels)
        slots = np.nonzero(lab >= 0)[0]
        raw = ctx.decode(slots)
        ours = components_of(
            {int(r): int(lab[s]) for s, r in zip(slots, raw)}
        )
        o_slots = np.nonzero(oracle_labels >= 0)[0]
        theirs = components_of(
            {int(s): int(oracle_labels[s]) for s in o_slots}
        )
        if ours != theirs:
            raise SystemExit(json.dumps({
                "error": "label parity FAILED",
                "ours": len(ours), "theirs": len(theirs),
            }))

    stages = {
        k: round(v, 4)
        for k, v in (timer.busy() if timer else {}).items()
    }
    stages["total_wall"] = round(dt_tpu, 4)
    mc = multicore_baseline_block(src, dst, args.vertices, spec={
        "edges_total": args.edges, "vertices": args.vertices,
        "seed": 7, "prefix": args.edges,
    })
    dev_eps = device_bound_cc_eps(src, dst, args.vertices, args.chunk_size)
    dev_payload_eps = device_bound_cc_payload_eps(
        src, dst, args.vertices, min(args.chunk_size, 1 << 21)
    )

    # Windowed-codec delta (VERDICT r3 item 8): event-time tumbling CC
    # with the ingest codec engaged vs the raw windowed fold — payloads
    # are window-scoped (chunks mask to one window before compression).
    from gelly_tpu.core.io import EdgeChunkSource, TimeCharacteristic
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable
    from gelly_tpu.library.connected_components import connected_components

    n_w = min(args.edges, 8_000_000)
    ts_w = np.arange(n_w, dtype=np.int64)

    def stream_w():
        return edge_stream_from_source(
            EdgeChunkSource(src[:n_w], dst[:n_w], timestamps=ts_w,
                            chunk_size=min(args.chunk_size, 1 << 20),
                            table=IdentityVertexTable(args.vertices),
                            time=TimeCharacteristic.EVENT),
            args.vertices,
        )

    win_rates = {}
    win_labels = {}
    for name, agg_kw in (("codec", {}), ("raw", {"ingest_combine": False})):
        agg_w = connected_components(args.vertices, **agg_kw)
        stream_w().aggregate(agg_w, window_ms=n_w // 4).result()  # warm
        dt_w = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            out = stream_w().aggregate(agg_w, window_ms=n_w // 4).result()
            win_labels[name] = np.asarray(out)
            dt_w = min(dt_w, time.perf_counter() - t0)
        win_rates[name] = n_w / dt_w
    if not np.array_equal(win_labels["codec"], win_labels["raw"]):
        raise SystemExit("windowed codec/raw label parity FAILED")
    return {
        "metric": "streaming_cc_throughput",
        "value": round(eps, 1),
        "unit": "edges/sec",
        "vs_baseline": round(eps / base_eps, 2),
        # Hardened comparison: vectorized numpy host pipeline with the same
        # chunked streaming semantics (VERDICT r1 item 5). vs_baseline keeps
        # the reference-semantics per-edge fold as its denominator for
        # round-over-round comparability.
        "vs_numpy_stream": round(eps / numpy_eps, 2),
        # Pipeline vs device-bound split (VERDICT r2 items 1/4): the
        # pipeline figure includes host ingest and transfer; the
        # device_fold_eps row is the HBM-staged fold+merge rate.
        **mc,
        "vs_baseline_multicore": round(eps / mc["baseline_multicore_eps"], 2),
        "vs_baseline_model32": round(eps / mc["baseline_model32_eps"], 3),
        "device_fold_eps": round(dev_eps, 1),
        "device_fold_payload_eps": round(dev_payload_eps, 1),
        "device_vs_model32": round(dev_eps / mc["baseline_model32_eps"], 2),
        # Event-time tumbling CC, codec on vs off (parity-checked): the
        # windowed wire rides the codec too (VERDICT r3 item 8).
        "windowed_codec_eps": round(win_rates["codec"], 1),
        "windowed_raw_eps": round(win_rates["raw"], 1),
        "windowed_codec_speedup": round(
            win_rates["codec"] / win_rates["raw"], 2),
        # Stage seconds are thread-summed BUSY time (ingest stages may
        # run on multiple workers), so they can exceed total_wall; the
        # overlap block relates them to the wall clock.
        "stages": stages,
        **_overlap_block(stages),
    }


def bench_cc_large(args) -> dict:
    """North-star workload #2 at north-star scale (VERDICT r2 item 3):
    streaming CC over a Twitter-2010-class synthetic stream — n_v >= 2^24
    slots, >= 2^28 Zipf edges with a hot vertex of degree >= 10^6 —
    through the sparse touched-slot codec, with full final-label parity
    against a pure-numpy chunked oracle and memory headroom reported."""
    import resource

    n_v = args.large_vertices
    n_e = args.large_edges
    chunk = args.large_chunk_size
    # Big merge windows: fewer full-capacity transforms, and the host
    # group pre-combine dedups more pairs per payload (touched vertices
    # grow sublinearly in window edges on skewed streams). 64
    # chunks/window = 4 emissions over the 2^28 stream. The STAGED unit
    # is deliberately smaller than the window (fold_batch=16 → 4 units
    # per window): a window-sized mega-unit serializes the whole window's
    # compress behind ONE pool worker and leaves the pipelined executor
    # nothing to overlap — unit granularity is what feeds it.
    merge_every = 64
    fold_batch = 16
    # Compact root space (codec="compact"): M bounds distinct touched
    # vertices per run (~5.5M for the north-star stream), NOT capacity or
    # edges — and never needs to exceed the vertex space, so a reduced
    # capture's M tracks its reduced capacity (an oversized M only
    # inflates the once-per-window transform, which at CPU-capture sizes
    # buried the pipeline stages under merge_emit).
    compact_m = min(1 << 23, n_v)
    src, dst = synth_edges(n_e, n_v, seed=17)
    hot_degree = int(
        (np.bincount(src, minlength=n_v) + np.bincount(dst, minlength=n_v))
        .max()
    )

    labels, ctx, dt_tpu, timer = tpu_cc(
        src, dst, n_v, chunk, merge_every, fold_batch,
        codec="compact", compact_capacity=compact_m,
    )
    eps = n_e / dt_tpu

    parity = "skipped"
    if not args.skip_parity:
        # Pure-numpy oracle, chunked to keep unique() tractable: per-chunk
        # spanning-forest pairs (cc_pairs_numpy), then one global min-label
        # fixpoint over all pairs — independent of the native C++ and
        # device paths. Asserts exact final-label equality (both sides use
        # the canonical min-slot root), the reference's parity oracle
        # semantics (T/example/test/ConnectedComponentsTest.java:40-47).
        from gelly_tpu.library.connected_components import cc_pairs_numpy

        pv, pr = [], []
        for lo in range(0, n_e, chunk):
            v, r = cc_pairs_numpy(
                src[lo:lo + chunk], dst[lo:lo + chunk], None, n_v
            )
            pv.append(v)
            pr.append(r)
        from gelly_tpu.library.connected_components import cc_labels_numpy

        av = np.concatenate(pv).astype(np.int32)
        ar = np.concatenate(pr).astype(np.int32)
        # The collected pairs are union edges: one fixpoint over them gives
        # the full-stream labels (-1 for untouched slots), same min-slot
        # canonical convention as the pipeline's transform.
        oracle = cc_labels_numpy(av, ar, None, n_v)
        ours = np.asarray(labels)
        if not np.array_equal(ours, oracle):
            raise SystemExit(json.dumps({
                "metric": "streaming_cc_large",
                "error": "label parity FAILED",
                "mismatches": int((ours != oracle).sum()),
            }))
        parity = "pass"

    # Multicore baseline: rate-flat, measured on a 2^26-edge prefix (the
    # device baselines below pick their own bounded prefixes).
    n_base = min(n_e, 1 << 26)
    mc = multicore_baseline_block(src[:n_base], dst[:n_base], n_v, spec={
        "edges_total": n_e, "vertices": n_v, "seed": 17, "prefix": n_base,
    })
    # Raw device fold (sort-dedup kernel, VERDICT r4 item 4) on a
    # 2^26-edge prefix at 2^25-edge chunks: dedup amortizes with chunk
    # size (distinct pairs grow sublinearly), so the mega-chunk shape is
    # the kernel's own operating point, not a bench trick. Exact label
    # parity against the chunked numpy oracle rides along — and the fold
    # runs as a BACKEND SWEEP (the fold_backend= plan knob): XLA random
    # gathers vs the Pallas VMEM-blocked chase kernel, each parity-
    # checked, with the winner recorded as device_fold_eps. The
    # gather_study block alongside decomposes the wall primitive by
    # primitive (random vs sorted vs blocked-kernel touch rates, sort
    # and scatter-min currency), so whichever way the sweep lands the
    # artifact says WHY.
    from gelly_tpu.ops.pallas_kernels import on_tpu as _bench_on_tpu

    dev_chunk = min(1 << 25, n_e)
    dev_max = min(1 << 26, n_e)
    fold_parity: dict = {}
    dev_eps = device_bound_cc_eps(src, dst, n_v, dev_chunk,
                                  max_edges=dev_max,
                                  parity_out=fold_parity)
    fold_oracle = fold_parity.pop("device_fold_oracle", None)
    sweep: dict = {
        "device_fold_eps_xla": round(dev_eps, 1),
        "device_fold_parity_xla": fold_parity.get("device_fold_parity"),
    }
    # Off-TPU the kernel interprets (serial Python grid): measure a
    # reduced shape so the CPU artifact still exercises the path, but
    # never let a reduced run win the headline comparison.
    pal_chunk = dev_chunk if _bench_on_tpu() else min(dev_chunk, 1 << 22)
    pal_max = dev_max if _bench_on_tpu() else pal_chunk
    same_shape = (pal_chunk, pal_max) == (dev_chunk, dev_max)
    dev_eps_pallas = None
    pal_parity: dict = {}
    try:
        dev_eps_pallas = device_bound_cc_eps(
            src, dst, n_v, pal_chunk, max_edges=pal_max,
            parity_out=pal_parity, fold_backend="pallas",
            oracle=fold_oracle if same_shape else None,
        )
        pal_parity.pop("device_fold_oracle", None)
        sweep["device_fold_eps_pallas"] = round(dev_eps_pallas, 1)
        sweep["device_fold_parity_pallas"] = pal_parity.get(
            "device_fold_parity")
        sweep["device_fold_no_transform_eps_pallas"] = pal_parity.get(
            "device_fold_no_transform_eps")
        notes = []
        if not same_shape:
            notes.append(f"cpu-interpret, reduced to chunk={pal_chunk}")
        if not pal_parity.get("device_fold_dedup_engaged"):
            notes.append(
                "chunk below dedup threshold: the pallas kernel never "
                "ran in this leg (parity is of the generic fold)"
            )
        if notes:
            sweep["device_fold_pallas_note"] = "; ".join(notes)
    except Exception as e:  # noqa: BLE001 — sweep must never kill the line
        sweep["device_fold_pallas_error"] = f"{type(e).__name__}: {e}"[:300]
    if (dev_eps_pallas is not None and same_shape
            and dev_eps_pallas > dev_eps
            and pal_parity.get("device_fold_dedup_engaged")
            and pal_parity.get("device_fold_parity") == "pass"):
        dev_eps = dev_eps_pallas
        sweep["device_fold_backend"] = "pallas"
        fold_parity["device_fold_parity"] = pal_parity["device_fold_parity"]
        fold_parity["device_fold_no_transform_eps"] = pal_parity.get(
            "device_fold_no_transform_eps",
            fold_parity.get("device_fold_no_transform_eps"))
    else:
        sweep["device_fold_backend"] = "xla"
    sweep["gather_study"] = gather_study_block()
    # batch matches the pipeline's fold_batch so the stacked rows mirror
    # its per-dispatch combined payloads; the full stream is staged so the
    # once-per-window transform amortizes exactly as in the pipeline.
    fold_info: dict = {}
    dev_payload_eps = device_bound_cc_payload_eps(
        src, dst, n_v, chunk, batch=fold_batch, max_edges=n_e,
        codec="compact", compact_capacity=compact_m, info_out=fold_info,
    )
    peaks = chip_peaks()
    fold_hbm_gbps = (
        fold_info.get("pair_lanes", 0) * STAR_FOLD_BYTES_PER_PAIR
        / max(fold_info.get("wall_s", 1), 1e-9) / 1e9
    )
    fold_hbm_util = (
        round(fold_hbm_gbps / peaks["peak_hbm_gbps"], 4)
        if peaks["peak_hbm_gbps"] else None
    )

    # Tracer-on re-capture + Perfetto trace artifact (never kills the
    # line: the obs block is observability OF the bench, not the bench).
    try:
        obs_block = obs_trace_block(
            src, dst, n_v, chunk, merge_every, fold_batch,
            "compact", compact_m, eps, "streaming_cc_large",
        )
    except Exception as e:  # noqa: BLE001
        obs_block = {"obs": {"error": f"{type(e).__name__}: {e}"[:300]}}

    stages = {
        k: round(v, 4)
        for k, v in (timer.busy() if timer else {}).items()
    }
    stages["total_wall"] = round(dt_tpu, 4)
    overlap = _overlap_block(stages)
    rss_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    avail_gb = 0.0
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable"):
                avail_gb = int(line.split()[1]) / 1e6
                break
    return {
        "metric": "streaming_cc_large",
        "value": round(eps, 1),
        "unit": "edges/sec",
        "edges": n_e,
        "vertices": n_v,
        "hot_vertex_degree": hot_degree,
        "parity": parity,
        "merge_window_chunks": merge_every,
        "compact_capacity": compact_m,
        **segment_compress_block(src, dst, n_v, chunk, fold_batch,
                                 compact_m),
        **codec_scaling_block(src, dst, n_v, chunk),
        **codec_workers_block(
            src, dst, n_v, chunk, cap_edges=min(1 << 24, n_e),
            ks=tuple(int(k) for k in getattr(
                args, "codec_workers", "1,2,4").split(",")),
        ),
        **mc,
        "vs_baseline_multicore": round(eps / mc["baseline_multicore_eps"], 2),
        "vs_baseline_model32": round(eps / mc["baseline_model32_eps"], 3),
        "device_fold_eps": round(dev_eps, 1),
        **fold_parity,
        **sweep,
        "device_fold_payload_eps": round(dev_payload_eps, 1),
        "device_vs_model32": round(dev_eps / mc["baseline_model32_eps"], 2),
        # Roofline view of the star fold (logical-bytes model, see
        # STAR_FOLD_BYTES_PER_PAIR): random element-granule gathers — the
        # utilization is the traffic the access pattern implies vs HBM
        # peak, not a DMA counter.
        "chip": peaks["chip"],
        "fold_hbm_gbps": round(fold_hbm_gbps, 1),
        "fold_hbm_util": fold_hbm_util,
        "peak_rss_gb": round(rss_gb, 2),
        "mem_available_gb": round(avail_gb, 2),
        "stages": stages,
        **overlap,
        **obs_block,
    }


_SHARDED_STATE_CHILD = r"""
import json, time
from functools import partial
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from gelly_tpu.parallel import collectives, mesh as mesh_lib
from gelly_tpu.parallel.mesh import SHARD_AXIS
from gelly_tpu.parallel.sharded_cc import ShardedCC
from gelly_tpu.ops.unionfind import (
    fresh_forest, merge_forest_stack, union_edges, union_pairs_rooted,
)

S = 8
m = mesh_lib.make_mesh(S)
sharded = NamedSharding(m, P(SHARD_AXIS))
rng = np.random.default_rng(11)
n_pairs = 1 << 16
# Per-shard touched slots are bounded by 2 * (n_pairs / S): the delta
# gather bucket that covers the worst case (the engine sizes it from the
# measured count; here the bound is static).
DELTA_BUCKET = 2 * (n_pairs // S)
out = {}
for n_v in (1 << 20, 1 << 23, 1 << 24):
    a = (rng.zipf(1.4, n_pairs) % n_v).astype(np.int32)
    b = (rng.zipf(1.4, n_pairs) % n_v).astype(np.int32)
    # Slot-sharded plan: state maintenance = the pair fold itself (there
    # is no separate per-window cross-shard merge — folds keep the global
    # forest consistent through the keyed exchange).
    cc = ShardedCC(n_v, mesh=m)
    cc.fold(a, b)  # compile the fold path
    # Warm the dirty-delta emission path too: the first labels() call
    # pays one-time costs (sharded device_put transfer programs, D2H
    # plumbing) that are not the stage's steady-state — round 5 recorded
    # that cold call as the emission figure.
    cc.labels()
    dt_s = float("inf")
    emits = []
    for _ in range(3):
        cc2 = ShardedCC(n_v, mesh=m)
        t0 = time.perf_counter()
        cc2.fold(a, b)
        dt_s = min(dt_s, time.perf_counter() - t0)
        # Incremental emission (VERDICT r4 item 3): resolves only the
        # fold's dirty parent entries against the host root cache + ONE
        # capacity gather (the output array itself). Median-of-3, same
        # repeat protocol as the CPU baseline; each repeat folds into a
        # fresh instance so the dirty delta is identical every time.
        t0 = time.perf_counter()
        cc2.labels()
        emits.append(time.perf_counter() - t0)
    emits.sort()
    dt_emit = emits[len(emits) // 2]
    # Replicated plan's per-window merge: stacked S x n_v forest union
    # (cost inherently prop. to full capacity, pairs or not).
    stack = jnp.broadcast_to(jnp.arange(n_v, dtype=jnp.int32)[None], (S, n_v))
    merged = merge_forest_stack(stack); np.asarray(merged)  # compile
    dt_r = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        np.asarray(merge_forest_stack(stack))
        dt_r = min(dt_r, time.perf_counter() - t0)

    # Dirty-delta merge (the engine's merge_mode="delta" window close):
    # S per-shard window forests holding the SAME pairs exchange only
    # their compacted dirty (slot, parent) rows and union them into the
    # replicated base — cost prop. to touched rows, not capacity. Same
    # repeat protocol as the replicated row; the CLAIM is the capacity
    # slope of this row next to the replicated one.
    av = jax.device_put(a.reshape(S, -1).astype(np.int32), sharded)
    bv = jax.device_put(b.reshape(S, -1).astype(np.int32), sharded)

    @partial(jax.jit, out_shardings=(sharded, sharded))
    def build_locals(aa, bb):
        def body(a_, b_):
            ok = jnp.ones(a_.shape[-1], bool)
            p = union_edges(fresh_forest(n_v), a_[0], b_[0], ok)
            seen = jnp.zeros((n_v,), bool).at[a_[0]].set(True)
            seen = seen.at[b_[0]].set(True)
            return p[None], seen[None]
        return mesh_lib.shard_map_fn(
            m, body, in_specs=(P(SHARD_AXIS),) * 2,
            out_specs=(P(SHARD_AXIS),) * 2,
        )(aa, bb)

    @jax.jit
    def delta_merge(lp, ls, base):
        def body(p, s, g):
            iota = jnp.arange(n_v, dtype=jnp.int32)
            d = s[0] | (p[0] != iota)
            slots, vals, _ = collectives.compact_delta(d, p[0], DELTA_BUCKET)
            gs, gv = collectives.gather_delta(slots, vals)
            ok = gs >= 0
            # union_pairs_rooted: every round sized to the gathered rows,
            # no full-capacity flatten (the library merge_delta's kernel).
            merged = union_pairs_rooted(
                g, jnp.where(ok, gs, 0), jnp.where(ok, gv, 0), ok
            )
            return merged[None]
        return mesh_lib.shard_map_fn(
            m, body, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
            out_specs=P(SHARD_AXIS),
        )(lp, ls, base)

    lp, ls = build_locals(av, bv)
    base = fresh_forest(n_v)
    jax.block_until_ready(delta_merge(lp, ls, base))  # compile
    dt_d = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready(delta_merge(lp, ls, base))
        dt_d = min(dt_d, time.perf_counter() - t0)

    out[str(n_v)] = {
        "sharded_fold_s": round(dt_s, 3),
        "emission_s": round(dt_emit, 3),
        "emission_s_min": round(emits[0], 3),
        "emission_s_max": round(emits[-1], 3),
        "emission_repeats": len(emits),
        "replicated_merge_s": round(dt_r, 3),
        "delta_merge_s": round(dt_d, 4),
        "delta_bucket": DELTA_BUCKET,
        "per_device_state_bytes": cc.per_device_state_bytes(),
        "replicated_state_bytes": n_v * 5,
    }
print(json.dumps(out))
"""


def bench_sharded_state() -> dict:
    """Slot-sharded CC summaries (VERDICT r3 item 2): the vertex-striped
    plan has NO per-window cross-shard merge — state maintenance is the
    pair fold (∝ pairs), vs the replicated plan's stacked merge (∝ n_v by
    construction); emission (∝ output size, inherent) is reported
    separately. Runs on an 8-virtual-device CPU mesh in a child pinned
    to ``JAX_PLATFORMS=cpu`` (it never touches the chip this process
    holds); absolute CPU times are not comparable to the TPU lines —
    only the capacity SLOPE is the claim. Per-device state is n_v/S (asserted in
    tests/test_sharded_cc.py and the driver dryrun).
    """
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    kept = " ".join(
        t for t in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in t
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"{kept} --xla_force_host_platform_device_count=8".strip(),
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c",
             f"import sys; sys.path.insert(0, {here!r})\n"
             + _SHARDED_STATE_CHILD],
            env=env, cwd=here, capture_output=True, text=True, timeout=900,
        )
        if proc.returncode != 0:
            return {"metric": "sharded_state_cc",
                    "error": proc.stderr[-400:]}
        rows = json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001 — never kill the headline line
        return {"metric": "sharded_state_cc",
                "error": f"{type(e).__name__}: {e}"[:400]}
    lo, hi = rows["1048576"], rows["8388608"]
    star = rows.get("16777216", hi)  # the 2^24 north-star capacity row
    return {
        "metric": "sharded_state_cc",
        # Headline: 8x the capacity costs the sharded fold ~1x (pairs
        # fixed), while the replicated per-window merge pays the full 8x.
        "value": round(
            hi["sharded_fold_s"] / max(lo["sharded_fold_s"], 1e-9), 2
        ),
        "unit": "x fold cost for 8x capacity (8-dev CPU mesh; 1.0 = flat)",
        "capacity_slope_replicated_merge": round(
            hi["replicated_merge_s"] / max(lo["replicated_merge_s"], 1e-9), 2,
        ),
        # The dirty-delta merge (merge_mode="delta") measured on the SAME
        # pair windows: its slope vs capacity must sit strictly below the
        # replicated row's (the r05 replicated slope hit 3.65 at 8x and
        # 32.2s absolute at 2^24; delta cost tracks touched rows).
        "capacity_slope_delta_merge": round(
            hi["delta_merge_s"] / max(lo["delta_merge_s"], 1e-9), 2,
        ),
        "delta_merge_lt_replicated_at_2e24": bool(
            star["delta_merge_s"] < star["replicated_merge_s"]
        ),
        # VERDICT r4 item 3's bar, at the 2^24 north-star capacity:
        # incremental emission at or below the fold cost.
        "emission_le_fold_at_2e24": bool(
            star["emission_s"] <= star["sharded_fold_s"]
        ),
        "detail": rows,
    }


def bench_ingest(args) -> dict:
    """The ``gelly_tpu.ingest`` workload block (ISSUE 9): (a) the
    sharded-reader S-sweep — per-reader-lane parse+compress eps over a
    binary edge file, with the trace-backed serialization check (zero
    ``produce`` spans, one compress track per lane, max-lane busy vs
    wall) — and (b) loopback-socket server/client eps speaking the
    compressed-pair wire format, plus a backpressure pass with a tiny
    high-water mark proving the staged depth stays bounded.

    Schema (committed reduced CPU captures are structural stand-ins;
    eps claims cite TPU-host runs):

    - ``sharded_readers.S<k>``: ``{eps, wall_s, lanes, compress_tracks,
      produce_spans, lane_busy_max_s, lane_busy_sum_s,
      serialized_frac}`` — ``serialized_frac`` = wall / lane-busy-sum;
      a single produce loop pins it near 1.0, independent lanes push it
      toward 1/S.
    - ``sharded_readers.eps_scaling_s4_vs_s1``: headline ratio.
    - ``socket_ingest``: ``{eps, wall_s, chunks, wire_bytes_per_edge,
      backpressure: {engagements, max_staged_depth, high_water,
      bounded}}``.
    - ``stacked`` (ISSUE 18): the coalescing-factor sweep K ∈ {1, 8,
      64} — one header/CRC/syscall/fold-dispatch per K chunks. Per-K
      rows: ``{eps, data_frames, frames_per_edge, wire_bytes_per_edge,
      header_crc_bytes_per_edge, stack_table_bytes_per_edge,
      recv_syscalls_lower_bound, one_fold_dispatch_per_frame}``;
      headline ``header_crc_reduction_k64_vs_k1`` (≥ 8x) and
      ``bit_identical_across_k``. eps rows are structural on a 1-core
      host (``scaling_measurable``/``skipped_reason``) — the
      per-frame overhead amortization is the committed claim.
    """
    import os
    import tempfile
    import threading

    from gelly_tpu import obs
    from gelly_tpu.engine.aggregation import available_cores
    from gelly_tpu.ingest import (
        IngestClient,
        IngestServer,
        ShardedEdgeSource,
        write_binary_edges,
    )
    from gelly_tpu.library.connected_components import connected_components
    from gelly_tpu.obs import bus as obs_bus

    n_e = min(args.edges, 1 << 21)
    n_v = min(args.vertices, 1 << 17)
    chunk = min(args.chunk_size, 1 << 14)
    src, dst = synth_edges(n_e, n_v)
    agg = connected_components(n_v, codec="sparse")

    out: dict = {"metric": "ingest", "edges": n_e, "vertices": n_v,
                 "chunk_size": chunk, "unit": "edges/sec"}
    tmp = tempfile.mkdtemp(prefix="gelly-ingest-bench-")
    path = os.path.join(tmp, "edges.bin")
    write_binary_edges(path, src, dst)

    # ---------------------------------------------------- reader sweep
    sweep: dict = {}
    best_trace = None
    eps_by_s: dict = {}
    for S in (1, 2, 4):
        source = ShardedEdgeSource(path, shards=S, chunk_size=chunk,
                                   vertex_capacity=n_v)
        tracer = obs.SpanTracer(capacity=1 << 16, heartbeat_every_s=None)

        def stage(unit, _tr=tracer):
            seq, group = unit
            t0 = _tr.now()
            payload = agg.host_compress(group[0])
            _tr.span("compress",
                     f"compress/{threading.current_thread().name}",
                     t0, unit=seq)
            return payload

        with obs.scope(), obs.install(tracer):
            t0 = time.perf_counter()
            n_units = sum(1 for _ in source.stage_units(
                stage, batch=1, depth=2 * S))
            wall = time.perf_counter() - t0
        spans = tracer.spans("compress")
        busy: dict = {}
        for s in spans:
            busy[s["track"]] = busy.get(s["track"], 0.0) + s["dur"]
        busy_sum = sum(busy.values())
        eps_by_s[S] = n_e / wall
        sweep[f"S{S}"] = {
            "eps": round(n_e / wall, 1),
            "wall_s": round(wall, 4),
            "units": n_units,
            "lanes": S,
            "compress_tracks": len(busy),
            "produce_spans": len(tracer.spans("produce")),
            "lane_busy_max_s": round(max(busy.values(), default=0.0), 4),
            "lane_busy_sum_s": round(busy_sum, 4),
            # 1.0 = fully serialized (one lane's busy IS the wall);
            # 1/S = perfect lane independence.
            "serialized_frac": round(wall / max(busy_sum, 1e-9), 4),
        }
        if S == 4:
            best_trace = tracer
    sweep["eps_scaling_s4_vs_s1"] = round(eps_by_s[4] / eps_by_s[1], 2)
    sweep["per_lane_tracks_ok"] = bool(
        sweep["S4"]["compress_tracks"] == 4
        and sweep["S4"]["produce_spans"] == 0
    )
    # Self-describing scaling context (codec_workers_block precedent):
    # on a 1-core host the lanes physically serialize — the structural
    # claims (per-lane tracks, no produce span, bounded backpressure)
    # still hold and are asserted; the eps-scales-with-S claim is a
    # multi-core/TPU-host capture.
    cores = available_cores()
    sweep["available_cores"] = cores
    sweep["scaling_measurable"] = bool(cores >= 2)
    if cores < 2:
        sweep["skipped_reason"] = (
            "single-core host: S reader lanes time-slice one core, so "
            "eps cannot scale here; per-lane independence is proven "
            "structurally (compress_tracks == S, produce_spans == 0)"
        )
    out["sharded_readers"] = sweep
    if best_trace is not None:
        tpath = trace_out_path("trace_ingest_sharded")
        trace = obs.write_chrome_trace(
            tpath, best_trace, extra={"workload": "ingest_sharded_s4"},
        )
        out["trace_file"] = os.path.basename(tpath)
        out["trace_events"] = len(trace["traceEvents"])

    # ------------------------------------------------- loopback socket
    sock_chunk = 4096
    payloads = [
        agg.host_compress(c)
        for c in ShardedEdgeSource(path, shards=1, chunk_size=sock_chunk,
                                   vertex_capacity=n_v)
    ]
    wire_edges = n_e

    def run_socket(high_water, low_water, consumer_sleep):
        with obs_bus.scope() as bus:
            kw = {"queue_depth": 64}
            if high_water is not None:
                kw.update(high_water=high_water, low_water=low_water,
                          pause_poll_s=0.002)
            max_depth = 0
            done = threading.Event()

            def consume(srv):
                nonlocal max_depth
                for _seq, _p in srv.payloads():
                    d = bus.gauges.get("ingest.staged_depth", 0)
                    if d > max_depth:
                        max_depth = d
                    if consumer_sleep:
                        time.sleep(consumer_sleep)
                done.set()

            with IngestServer(**kw) as srv:
                t = threading.Thread(target=consume, args=(srv,),
                                     daemon=True)
                t.start()
                cli = IngestClient("127.0.0.1", srv.port,
                                   send_pause_timeout=120)
                cli.connect()
                t0 = time.perf_counter()
                for p in payloads:
                    cli.send(p)
                cli.flush(timeout=300)
                wall = time.perf_counter() - t0
                cli.close()
            done.wait(timeout=30)
            snap = bus.snapshot()["counters"]
            return wall, max_depth, snap

    wall, _depth, snap = run_socket(None, None, 0.0)
    out["socket_ingest"] = {
        "eps": round(wire_edges / wall, 1),
        "wall_s": round(wall, 4),
        "chunks": len(payloads),
        "wire_bytes_per_edge": round(
            snap.get("ingest.bytes_received", 0) / wire_edges, 4
        ),
        "frames_rejected": int(snap.get("ingest.frames_rejected", 0)),
    }
    hw = 2
    _wall, max_depth, snap = run_socket(hw, 1, 0.0005)
    out["socket_ingest"]["backpressure"] = {
        "high_water": hw,
        "engagements": int(snap.get("ingest.backpressure_engaged", 0)),
        "pauses_received": int(snap.get("ingest.pauses_received", 0)),
        "max_staged_depth": int(max_depth),
        "bounded": bool(max_depth <= hw),
    }

    # ----------------------- pre-compressed wire (DATA_COMPRESSED)
    # The shared compression plane's wire leg: the CLIENT compresses
    # each chunk to its sparse CC pairs and ships DATA_COMPRESSED
    # frames; the server admits them straight into staging and the
    # engine folds the payloads with precompressed=True — a traced run
    # shows ZERO server-side compress spans. Shape pinned to the
    # codec's wire-win regime (edges >> touched vertices per chunk:
    # 2^17-edge chunks over 2^12 slots => <= 4096 pairs * 8 B =
    # ~0.25 B/edge), vs the 16 B/edge raw-edge DATA twin. eps rows are
    # structural on a 1-core host like everything else here.
    from gelly_tpu.core.chunk import make_chunk
    from gelly_tpu.engine.aggregation import run_aggregation
    from gelly_tpu.ingest.client import edge_payload
    from gelly_tpu.parallel import mesh as mesh_lib

    m1 = mesh_lib.make_mesh(1)
    cw_nv = 1 << 12
    cw_chunk = 1 << 17
    cw_n = 8
    cw_edges = cw_chunk * cw_n
    rng = np.random.default_rng(17)
    cagg = connected_components(cw_nv, codec="sparse")
    cchunks = []
    for _ in range(cw_n):
        s = rng.integers(0, cw_nv, cw_chunk).astype(np.int64)
        d = rng.integers(0, cw_nv, cw_chunk).astype(np.int64)
        cchunks.append(make_chunk(
            s.astype(np.int32), d.astype(np.int32),
            raw_src=s, raw_dst=d, capacity=cw_chunk, device=False,
        ))
    t0 = time.perf_counter()
    cpayloads = [cagg.host_compress(c) for c in cchunks]  # client leg
    client_compress_s = time.perf_counter() - t0

    def wire_pass(items, compressed):
        with obs_bus.scope() as bus:
            done = threading.Event()
            with IngestServer(queue_depth=64) as srv:
                def consume():
                    for _ in srv.frames():
                        pass
                    done.set()

                th = threading.Thread(target=consume, daemon=True)
                th.start()
                cli = IngestClient("127.0.0.1", srv.port,
                                   send_pause_timeout=120)
                cli.connect()
                t0 = time.perf_counter()
                for p in items:
                    cli.send(p, compressed=compressed)
                cli.flush(timeout=300)
                wall = time.perf_counter() - t0
                cli.close()
            done.wait(timeout=30)
            return wall, bus.snapshot()["counters"]

    raw_wall, raw_snap = wire_pass(
        [edge_payload(np.asarray(c.raw_src), np.asarray(c.raw_dst))
         for c in cchunks], False,
    )
    comp_wall, comp_snap = wire_pass(cpayloads, True)

    # Engine fold of the compressed stream (zero compress spans) +
    # bit-identity vs the file-ingest codec path over the SAME chunks.
    agg_wire = connected_components(cw_nv, codec="sparse")
    tracer = obs.SpanTracer(capacity=1 << 16, heartbeat_every_s=None)
    with obs.scope() as tb, obs.install(tracer):
        with IngestServer(queue_depth=64, stop_on_bye=True) as srv:
            def feed():
                cli = IngestClient("127.0.0.1", srv.port,
                                   send_pause_timeout=120)
                cli.connect()
                for p in cpayloads:
                    cli.send_compressed(p)
                cli.flush(timeout=300)
                cli.close()

            ft = threading.Thread(target=feed, daemon=True)
            ft.start()
            t0 = time.perf_counter()
            wire_final = np.asarray(run_aggregation(
                agg_wire, srv.compressed_payloads(), merge_every=cw_n,
                mesh=m1, precompressed=True, ingest_workers=0,
                prefetch_depth=0, h2d_depth=0,
            ).result())
            fold_wall = time.perf_counter() - t0
            ft.join(timeout=60)
        tsnap = tb.snapshot()
    tpath = trace_out_path("trace_ingest_compressed")
    trace = obs.write_chrome_trace(
        tpath, tracer, extra={"workload": "ingest_compressed", **tsnap},
    )
    golden = np.asarray(run_aggregation(
        cagg, cchunks, merge_every=cw_n, mesh=m1, ingest_workers=0,
        prefetch_depth=0, h2d_depth=0,
    ).result())
    comp_bpe = comp_snap.get("ingest.bytes_received", 0) / cw_edges
    raw_bpe = raw_snap.get("ingest.bytes_received", 0) / cw_edges
    n_compress = len(tracer.spans("compress"))
    out["compressed_wire"] = {
        "vertices": cw_nv,
        "chunk_size": cw_chunk,
        "edges": cw_edges,
        "client_compress_s": round(client_compress_s, 4),
        "wire_bytes_per_edge": round(comp_bpe, 4),
        "raw_wire_bytes_per_edge": round(raw_bpe, 4),
        "wire_compression_x": round(raw_bpe / max(comp_bpe, 1e-9), 1),
        "eps_wire_compressed": round(cw_edges / max(comp_wall, 1e-9), 1),
        "eps_wire_raw": round(cw_edges / max(raw_wall, 1e-9), 1),
        "eps_fold": round(cw_edges / max(fold_wall, 1e-9), 1),
        "data_frames_compressed": int(
            comp_snap.get("ingest.data_frames_compressed", 0)
        ),
        "server_compress_spans": n_compress,
        "server_stack_spans": len(tracer.spans("stack")),
        "zero_server_compress": bool(n_compress == 0),
        "parity_vs_file_ingest": bool(
            wire_final.tobytes() == golden.tobytes()
        ),
        "wire_bytes_per_edge_le_0p35": bool(comp_bpe <= 0.35),
        "trace_file": os.path.basename(tpath),
        "trace_events": len(trace["traceEvents"]),
    }

    # ------------------------------- stacked wire frames (ISSUE 18)
    # K payloads behind ONE header/CRC/recv/fold-dispatch. Small
    # chunks (64 edges) make per-frame overhead visible; the stream is
    # client-compressed sparse CC pairs so the SAME pass proves the
    # engine-side contract: each STACKED frame stages as one unit and
    # rides fold_codec's stacked dispatch whole — one fold span per
    # wire frame. Bit-identity across K closes the loop.
    from gelly_tpu.ingest import wire as wire_mod

    st_nv = 1 << 10
    st_chunk = 64
    st_n = 512  # divisible by every K: all stacks flush full
    st_edges = st_chunk * st_n
    rng = np.random.default_rng(23)
    st_chunks = []
    for _ in range(st_n):
        s = rng.integers(0, st_nv, st_chunk).astype(np.int64)
        d = rng.integers(0, st_nv, st_chunk).astype(np.int64)
        st_chunks.append(make_chunk(
            s.astype(np.int32), d.astype(np.int32),
            raw_src=s, raw_dst=d, capacity=st_chunk, device=False,
        ))
    st_payloads = [
        connected_components(st_nv, codec="sparse").host_compress(c)
        for c in st_chunks
    ]
    stacked: dict = {
        "chunk_size": st_chunk, "chunks": st_n, "edges": st_edges,
        "header_bytes": wire_mod.HEADER_BYTES,
    }
    hdr_bpe: dict = {}
    labels_by_k: dict = {}
    st_trace = None
    for K in (1, 8, 64):
        st_agg = connected_components(st_nv, codec="sparse")
        tracer = obs.SpanTracer(capacity=1 << 16, heartbeat_every_s=None)
        with obs_bus.scope() as bus, obs.install(tracer):
            with IngestServer(queue_depth=64, stop_on_bye=True) as srv:
                def feed(_srv=srv, _k=K):
                    kw = {"stack": _k} if _k > 1 else {}
                    cli = IngestClient("127.0.0.1", _srv.port,
                                       send_pause_timeout=120, **kw)
                    cli.connect()
                    for p in st_payloads:
                        cli.send_compressed(p)
                    cli.flush(timeout=300)
                    cli.close()

                ft = threading.Thread(target=feed, daemon=True)
                ft.start()
                t0 = time.perf_counter()
                final = np.asarray(run_aggregation(
                    st_agg, srv.compressed_payload_units(),
                    merge_every=st_n, fold_batch=max(K, 1), mesh=m1,
                    precompressed=True, ingest_workers=0,
                    prefetch_depth=0, h2d_depth=0,
                ).result())
                wall = time.perf_counter() - t0
                ft.join(timeout=60)
            snap = bus.snapshot()["counters"]
        labels_by_k[K] = final
        data_frames = int(snap.get("ingest.frames_stacked", 0)
                          + snap.get("ingest.data_frames_compressed", 0))
        frames_recv = int(snap.get("ingest.frames_received", 0))
        units = int(snap.get("engine.units_folded", 0))
        hdr = wire_mod.HEADER_BYTES * data_frames / st_edges
        hdr_bpe[K] = hdr
        # Stack body table: u16 count + (u8 kind, u32 len) per payload
        # — the bytes that REPLACE the per-chunk headers/CRCs.
        table = (0 if K == 1
                 else (st_n // K) * (2 + 5 * K))
        stacked[f"K{K}"] = {
            "eps": round(st_edges / max(wall, 1e-9), 1),
            "wall_s": round(wall, 4),
            "data_frames": data_frames,
            "frames_per_edge": round(data_frames / st_edges, 6),
            "wire_bytes_per_edge": round(
                snap.get("ingest.bytes_received", 0) / st_edges, 4),
            "header_crc_bytes_per_edge": round(hdr, 4),
            "stack_table_bytes_per_edge": round(table / st_edges, 4),
            # read_frame = one recv for the header + one for the body,
            # so 2 syscalls per frame is the floor the server pays.
            "recv_syscalls_lower_bound": 2 * frames_recv,
            "units_folded": units,
            "fold_spans": len(tracer.spans("fold")),
            "one_fold_dispatch_per_frame": bool(units == data_frames),
            "server_compress_spans": len(tracer.spans("compress")),
        }
        if K == 64:
            st_trace = tracer
    stacked["header_crc_reduction_k64_vs_k1"] = round(
        hdr_bpe[1] / max(hdr_bpe[64], 1e-12), 1)
    stacked["header_crc_reduced_8x"] = bool(
        hdr_bpe[1] / max(hdr_bpe[64], 1e-12) >= 8.0)
    stacked["bit_identical_across_k"] = bool(
        labels_by_k[8].tobytes() == labels_by_k[1].tobytes()
        and labels_by_k[64].tobytes() == labels_by_k[1].tobytes())
    stacked["available_cores"] = cores
    stacked["scaling_measurable"] = bool(cores >= 2)
    if cores < 2:
        stacked["skipped_reason"] = (
            "single-core host: sender and folder time-slice one core, "
            "so eps cannot show the syscall/dispatch amortization "
            "here; the committed claims are structural (frames, "
            "header+CRC bytes/edge, one fold dispatch per frame)"
        )
    if st_trace is not None:
        tpath = trace_out_path("trace_ingest_stacked")
        trace = obs.write_chrome_trace(
            tpath, st_trace, extra={"workload": "ingest_stacked_k64"},
        )
        stacked["trace_file"] = os.path.basename(tpath)
        stacked["trace_events"] = len(trace["traceEvents"])
    out["stacked"] = stacked

    out["value"] = out["socket_ingest"]["eps"]
    return out


def _tenant_chunks(seed: int, n_edges: int, n_v: int, chunk: int) -> list:
    """Identity-slot host chunks for one tenant stream (numpy fast path —
    the python tuple ingest would dominate a 256-tenant build)."""
    from gelly_tpu.core.chunk import make_chunk

    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_v, n_edges).astype(np.int64)
    dst = rng.integers(0, n_v, n_edges).astype(np.int64)
    return [
        make_chunk(src[i:i + chunk].astype(np.int32),
                   dst[i:i + chunk].astype(np.int32),
                   raw_src=src[i:i + chunk], raw_dst=dst[i:i + chunk],
                   capacity=chunk, device=False)
        for i in range(0, n_edges, chunk)
    ]


def bench_tenants(args) -> dict:
    """The multi-tenant batched fold engine (ISSUE 10): aggregate
    edges/sec for N ∈ {1, 8, 64, 256} tenants, batched (ONE vmapped
    dispatch advances every tenant per scheduling round) vs the
    sequential-loop baseline (each tenant its own single-stream
    ``run_aggregation`` pass over the same plan).

    The structural claim holds on any host and is recorded per point:
    ``fold_dispatches_batched`` stays at chunks-per-tenant regardless
    of N, while the sequential loop pays N × that. The SPEEDUP claim
    (aggregate eps ≥ 3x at N=64) is an accelerator-host capture: a
    1-core CPU stand-in executes the vmapped lanes serially, so the
    dispatch amortization it proves structurally cannot show up as
    eps (codec_workers_block precedent — self-describing
    ``scaling_measurable``/``skipped_reason``).
    """
    import os

    from gelly_tpu.engine.aggregation import (
        available_cores,
        run_aggregation,
    )
    from gelly_tpu.engine.tenants import MultiTenantEngine
    from gelly_tpu.library.connected_components import cc_tenant_tier

    n_v = 1 << 12
    chunk = 1 << 10
    edges_per_tenant = 1 << 13  # 8 chunks/tenant
    merge_every = 2
    agg, cap = cc_tenant_tier(n_v, chunk_capacity=chunk)
    chunks_per_tenant = edges_per_tenant // chunk

    from gelly_tpu import obs

    rows = {}
    trace_info = {}
    for n_tenants in (1, 8, 64, 256):
        streams = {
            t: _tenant_chunks(1000 + t, edges_per_tenant, n_v, chunk)
            for t in range(n_tenants)
        }
        # Batched: one engine, one tier, N lanes. The N=64 acceptance
        # point runs under a tracer: the exported timeline IS the proof
        # that one fold span per scheduling round advances all N lanes.
        eng = MultiTenantEngine(merge_every=merge_every)
        eng.add_tier("bench", agg, cap)
        for t in range(n_tenants):
            eng.admit(t, "bench", chunks=streams[t])
        tracer = (obs.SpanTracer(heartbeat_every_s=None)
                  if n_tenants == 64 else None)
        t0 = time.perf_counter()
        if tracer is not None:
            with obs.install(tracer):
                out = eng.drain()
        else:
            out = eng.drain()
        batched_s = time.perf_counter() - t0
        if tracer is not None:
            folds = tracer.spans("fold")
            tpath = trace_out_path("trace_tenants_n64")
            obs.write_chrome_trace(
                tpath, tracer, extra={"workload": "tenants_n64"},
            )
            trace_info = {
                "trace_file": os.path.basename(tpath),
                "trace_fold_spans": len(folds),
                "trace_lanes_per_fold": sorted(
                    {s["args"]["lanes"] for s in folds}
                ),
                "trace_one_dispatch_per_window": bool(
                    len(folds) == chunks_per_tenant
                ),
            }
        total_edges = n_tenants * edges_per_tenant

        # Sequential-loop baseline on the SAME plan: one
        # run_aggregation pass per tenant (inline ingest — thread-pool
        # setup per tiny stream would swamp the 1-core baseline).
        t0 = time.perf_counter()
        seq_last = None
        for t in range(n_tenants):
            seq_last = np.asarray(
                run_aggregation(
                    agg, streams[t], merge_every=merge_every,
                    ingest_workers=0, prefetch_depth=0, h2d_depth=0,
                ).result()
            )
        seq_s = time.perf_counter() - t0
        # Parity spot check: the batched engine's last tenant vs its
        # single-stream run (bit-identical labels — the tests assert
        # the full matrix; the bench keeps the capture honest).
        parity = bool(
            seq_last.tobytes()
            == np.asarray(out[n_tenants - 1]).tobytes()
        )
        rows[str(n_tenants)] = {
            "tenants": n_tenants,
            "eps_batched": round(total_edges / max(batched_s, 1e-9), 1),
            "eps_sequential": round(total_edges / max(seq_s, 1e-9), 1),
            "speedup": round(seq_s / max(batched_s, 1e-9), 2),
            "fold_dispatches_batched": eng.stats["dispatches"],
            "fold_dispatches_sequential": n_tenants * chunks_per_tenant,
            "one_dispatch_per_round": bool(
                eng.stats["dispatches"] == chunks_per_tenant
            ),
            "parity": parity,
        }

    # QoS policy plane (ISSUE 17). Two captures, neither a scaling
    # claim: (a) weighted fair share at the DRR grant level — the
    # deterministic ⌊R·wᵢ/w_max⌋−1 fairness bound, measured over R
    # rounds of an always-backlogged 1:2:4 mix; (b) the degradation
    # ladder end-to-end through the engine (limit → park → un-park →
    # re-park → shed) with the backlog-age watermark driven directly —
    # the bench has no wire, so the signal input is the same seam the
    # QoS suite uses — recording the transition counts and the
    # bounded-backlog bit (the shed queue really dropped and the
    # surviving tenant completed).
    from gelly_tpu.engine.qos import QosController, QosPolicy
    from gelly_tpu.obs import bus as obs_bus

    weights = {"bronze": 1.0, "silver": 2.0, "gold": 4.0}
    qc = QosController(per_tenant={
        t: QosPolicy(weight=w) for t, w in weights.items()
    })
    R = 400
    grants = {t: 0 for t in weights}
    clk = 0.0
    t0 = time.perf_counter()
    for _ in range(R):
        clk += 0.01
        for t in qc.plan_round(list(weights), now=clk):
            grants[t] += 1
    plan_s = time.perf_counter() - t0
    w_max = max(weights.values())
    fairness = {
        t: {
            "weight": w,
            "grants": grants[t],
            "chunks_per_round": round(grants[t] / R, 4),
            "expected_share": round(w / w_max, 4),
            "within_bound": bool(
                grants[t] >= int(R * w / w_max) - 1
            ),
        }
        for t, w in weights.items()
    }

    ladder_pol = QosPolicy(backlog_budget_s=0.5, limit_after=1,
                           park_after=1, unpark_below_s=0.25,
                           unpark_grace_s=0.0, shed_queue_depth=3)
    qos_ctrl = QosController(default=QosPolicy(), eval_every_s=0.01,
                             per_tenant={"victim": ladder_pol})
    cc_small, cap_small = cc_tenant_tier(1 << 7, chunk_capacity=32)

    def _bench_wait(pred, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return True
            time.sleep(0.01)
        return bool(pred())

    def _small_chunks(seed):
        from gelly_tpu import edge_stream_from_edges

        e = np.random.default_rng(seed).integers(0, 1 << 7, (256, 2))
        return list(edge_stream_from_edges(
            [(int(a), int(b)) for a, b in e],
            vertex_capacity=1 << 7, chunk_size=32,
        ))

    backlog_bounded = False
    survivor_done = False
    with obs_bus.scope() as bus:
        ages = {}
        bus.watermarks.backlog_age = lambda tid: ages.get(tid, 0.0)
        eng = MultiTenantEngine(merge_every=1, qos=qos_ctrl,
                                poll_s=0.01)
        eng.add_tier("cc", cc_small, cap_small)
        eng.admit("victim", "cc")
        eng.admit("other", "cc")
        vic = _small_chunks(1)
        oth = _small_chunks(2)
        eng.start()
        try:
            for ch in vic[:2]:
                eng.submit("victim", ch)
            for ch in oth[:2]:
                eng.submit("other", ch)
            _bench_wait(lambda: eng.position("victim") == 2
                        and eng.position("other") == 2)
            # Sustained over-budget backlog: limit, then park.
            ages["victim"] = 10.0
            ages["other"] = 10.0
            _bench_wait(lambda: eng.qos_state("victim") == "parked")
            # Pressure drains: auto un-park.
            ages["victim"] = 0.0
            ages["other"] = 0.0
            _bench_wait(lambda: eng.qos_state("victim") in ("ok", "limited"))
            # Overload again and bury the parked queue: shed.
            ages["victim"] = 10.0
            ages["other"] = 10.0
            _bench_wait(lambda: eng.qos_state("victim") == "parked")
            for ch in vic[2:8]:
                eng.submit("victim", ch)
            _bench_wait(lambda: eng.qos_state("victim") == "shed")
            backlog_bounded = eng.queue_depth("victim") == 0
            ages["other"] = 0.0
            for ch in oth[2:]:
                eng.submit("other", ch)
            eng.finish("other")
            survivor_done = _bench_wait(
                lambda: eng.telemetry()["other"]["done"])
        finally:
            eng.stop()
        qsnap = bus.snapshot()["counters"]
    qos_block = {
        "fairness": fairness,
        "fairness_rounds": R,
        "plan_round_us": round(plan_s / R * 1e6, 2),
        "fairness_bound_ok": all(
            f["within_bound"] for f in fairness.values()
        ),
        "rate_limited": int(qsnap.get("qos.rate_limited", 0)),
        "parked": int(qsnap.get("qos.parked", 0)),
        "unparked": int(qsnap.get("qos.unparked", 0)),
        "shed": int(qsnap.get("qos.shed", 0)),
        "chunks_dropped": int(qsnap.get("qos.chunks_dropped", 0)),
        "backlog_bounded": bool(backlog_bounded),
        "survivor_completed": bool(survivor_done),
        # Policy decisions are host-independent control flow — there
        # is no accelerator scaling claim to defer here.
        "scaling_measurable": False,
    }

    cores = available_cores()
    speedup64 = rows["64"]["speedup"]
    out = {
        "metric": "tenants_batched_fold",
        "value": speedup64,
        "unit": "x aggregate eps vs sequential loop at N=64",
        "vertex_capacity": n_v,
        "chunk": chunk,
        "edges_per_tenant": edges_per_tenant,
        "merge_every": merge_every,
        "sweep": rows,
        "dispatch_amortization_ok": all(
            r["one_dispatch_per_round"] for r in rows.values()
        ),
        **trace_info,
        "parity_ok": all(r["parity"] for r in rows.values()),
        "qos": qos_block,
        "available_cores": cores,
        # The 3x-at-N=64 acceptance bar needs lanes that actually run
        # in parallel (vector units across tenants on an accelerator);
        # a 1-core CPU serializes them, so the eps claim is deferred to
        # a TPU capture while the dispatch-count proof stands here.
        "scaling_measurable": bool(cores >= 2 and speedup64 >= 1.0),
    }
    if not out["scaling_measurable"]:
        out["skipped_reason"] = (
            f"{cores}-core CPU stand-in: vmapped tenant lanes execute "
            "serially, so aggregate eps cannot beat the sequential loop "
            "here; the amortization is proven structurally instead — "
            "fold_dispatches_batched == chunks_per_tenant "
            f"({chunks_per_tenant}) at every N while the sequential "
            "loop pays N x that (fold_dispatches_sequential)"
        )
    return out


def bench_multiquery(args) -> dict:
    """Fused multi-query execution (ISSUE 12): Q ∈ {1, 2, 4}
    heterogeneous questions answered from ONE shared ingest pipeline
    (``run_aggregation(queries=[...])`` / ``engine.multiquery.fuse``)
    on the streaming-CC workload shape, against the sequential
    baseline (one full single-query pass per question over the same
    stream).

    The structural claim holds on any host and is recorded per point:
    produce/compress/H2D stage span counts at Q=4 EQUAL the Q=1 run
    (the shared legs run once per chunk, not once per query) and fold
    dispatches per chunk stay 1 regardless of Q. The WALL claim
    (``marginal_query_cost_frac`` <= 0.10 — query Q+1 costs under 10%
    of the single-query wall) is an accelerator-host capture: on a
    CPU stand-in the fused program's Q folds execute serially on the
    same cores that run ingest, so the marginal query pays real wall
    here (self-describing ``scaling_measurable``/``skipped_reason``,
    tenants-bench precedent). Queries: CC + out-degrees +
    bipartiteness + in-degrees (the spanner is parity-covered by the
    test suite instead — its per-edge scan fold would dominate a CPU
    stand-in and measure the fold, not the fusion).
    """
    import os

    import jax

    from gelly_tpu import obs
    from gelly_tpu.core.io import EdgeChunkSource
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable
    from gelly_tpu.engine.aggregation import (
        available_cores,
        run_aggregation,
    )
    from gelly_tpu.engine.multiquery import fuse
    from gelly_tpu.library.bipartiteness import bipartiteness_query
    from gelly_tpu.library.connected_components import cc_query
    from gelly_tpu.library.degrees import degrees_query

    n_v = 1 << 14
    chunk = 1 << 12
    n_edges = 1 << 17
    merge_every = 4
    rng = np.random.default_rng(31)
    src = rng.integers(0, n_v, n_edges).astype(np.int64)
    dst = rng.integers(0, n_v, n_edges).astype(np.int64)
    chunks = -(-n_edges // chunk)

    def stream():
        srcq = EdgeChunkSource(src, dst, chunk_size=chunk,
                               table=IdentityVertexTable(n_v))
        return edge_stream_from_source(srcq, n_v)

    def mk_queries(q):
        specs = [cc_query(n_v), degrees_query(n_v),
                 bipartiteness_query(n_v),
                 degrees_query(n_v, count_out=False, name="in_degrees")]
        return specs[:q]

    rows = {}
    trace_info = {}
    walls = {}
    for qn in (1, 2, 4):
        queries = mk_queries(qn)
        fused = fuse(queries)

        def one_pass():
            return run_aggregation(
                fused, stream(), merge_every=merge_every
            ).result()

        one_pass()  # compile warmup (plans cache on the fused instance)
        wall = float("inf")
        for _ in range(3):  # best-of-3: sub-100ms CPU walls swing
            with obs.scope() as bus:
                t0 = time.perf_counter()
                final = one_pass()
                wall = min(wall, time.perf_counter() - t0)
                counters = bus.snapshot()["counters"]
        # Span-count pass under a tracer (untimed — the timed wall above
        # stays tracer-free on BOTH sides of the comparison).
        tracer = obs.SpanTracer(capacity=1 << 16)
        with obs.scope() as tbus, obs.install(tracer):
            one_pass()
            tsnap = tbus.snapshot()
        stage_counts = {
            s: len(tracer.spans(s))
            for s in ("produce", "compress", "h2d", "fold")
        }
        if qn == 4:
            tpath = trace_out_path("trace_multiquery_q4")
            trace = obs.write_chrome_trace(
                tpath, tracer,
                extra={"workload": "multiquery_q4", **tsnap},
            )
            mq_spans = tracer.spans("multiquery")
            trace_info = {
                "trace_file": os.path.basename(tpath),
                "trace_events": len(trace["traceEvents"]),
                "trace_query_tracks": sorted(
                    {s["args"]["query"] for s in mq_spans}
                ),
                "trace_fold_spans_carry_queries": bool(
                    all("queries" in s["args"]
                        for s in tracer.spans("fold"))
                ),
            }

        # Sequential baseline: one full single-query pass per question
        # over the same stream (each pass pays its own produce/
        # compress/H2D leg — the cost fusion amortizes away).
        seq_wall = 0.0
        parity = {}
        for q in queries:
            run_aggregation(
                q.agg, stream(), merge_every=merge_every
            ).result()  # warm
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                alone = run_aggregation(
                    q.agg, stream(), merge_every=merge_every
                ).result()
                best = min(best, time.perf_counter() - t0)
            seq_wall += best
            parity[q.name] = bool(all(
                np.asarray(w).tobytes() == np.asarray(g).tobytes()
                for w, g in zip(jax.tree.leaves(alone),
                                jax.tree.leaves(final[q.name]))
            ))

        walls[qn] = wall
        rows[str(qn)] = {
            "queries": [q.name for q in queries],
            "wall_s": round(wall, 4),
            "answers_per_sec": round(qn * n_edges / max(wall, 1e-9), 1),
            "sequential_wall_s": round(seq_wall, 4),
            "fold_dispatches_fused": int(
                counters.get("engine.units_folded", 0)
            ),
            "fold_dispatches_sequential": qn * chunks,
            "fold_dispatches_per_chunk": round(
                counters.get("engine.units_folded", 0) / chunks, 4
            ),
            "stage_spans": stage_counts,
            "parity": parity,
        }

    # Fused codec sharing (the shared compression plane): the Q=2 set
    # with every query's codec ON — ONE multi-query compressed payload
    # per chunk, folds through fold_compressed. Structural bits:
    # compress spans == chunks (not chunks x Q), fold dispatches stay
    # 1/chunk, multiquery.compressed_chunks counts each chunk once,
    # and every query's final summary is bit-identical to the raw
    # fused run's.
    cqueries = [cc_query(n_v, compressed=True, codec="sparse"),
                degrees_query(n_v, compressed=True, codec="sparse")]
    fused_c = fuse(cqueries)
    raw_twin = fuse([cc_query(n_v), degrees_query(n_v)])

    def c_pass(plan):
        return run_aggregation(
            plan, stream(), merge_every=merge_every
        ).result()

    c_pass(fused_c)  # compile warmup
    c_pass(raw_twin)
    c_wall = float("inf")
    for _ in range(3):
        with obs.scope() as cb:
            t0 = time.perf_counter()
            c_final = c_pass(fused_c)
            c_wall = min(c_wall, time.perf_counter() - t0)
            c_counters = cb.snapshot()["counters"]
    raw_final = c_pass(raw_twin)
    tracer = obs.SpanTracer(capacity=1 << 16)
    with obs.scope(), obs.install(tracer):
        c_pass(fused_c)
    c_compress = tracer.spans("compress")
    payload_bytes = sum(
        s["args"].get("payload_bytes", 0) for s in c_compress
    )
    parity_c = {
        q.name: bool(all(
            np.asarray(w).tobytes() == np.asarray(g).tobytes()
            for w, g in zip(jax.tree.leaves(c_final[q.name]),
                            jax.tree.leaves(raw_final[q.name]))
        ))
        for q in cqueries
    }
    compressed_row = {
        "queries": [q.name for q in cqueries],
        "wall_s": round(c_wall, 4),
        "raw_fused_wall_s": rows["2"]["wall_s"],
        "compressed_chunks": int(
            c_counters.get("multiquery.compressed_chunks", 0)
        ),
        "one_payload_per_chunk": bool(
            c_counters.get("multiquery.compressed_chunks", 0) == chunks
            and len(c_compress) == chunks
        ),
        "one_fold_dispatch_per_chunk": bool(
            c_counters.get("engine.units_folded", 0) == chunks
        ),
        "compressed_payload_bytes_per_edge": round(
            payload_bytes / n_edges, 4
        ),
        "parity_vs_raw_fused": parity_c,
    }

    marginal = (walls[4] - walls[1]) / (3 * max(walls[1], 1e-9))
    q1s, q4s = rows["1"]["stage_spans"], rows["4"]["stage_spans"]
    shared_legs_equal = all(
        q1s[s] == q4s[s] for s in ("produce", "compress", "h2d")
    )
    cores = available_cores()
    out = {
        "metric": "multiquery_fused",
        "value": round(marginal, 4),
        "unit": "marginal wall frac per added query (vs Q=1 wall)",
        "vertex_capacity": n_v,
        "chunk": chunk,
        "edges": n_edges,
        "merge_every": merge_every,
        "sweep": rows,
        "marginal_query_cost_frac": round(marginal, 4),
        "stage_counts_equal_q1": bool(shared_legs_equal),
        "one_fold_dispatch_per_chunk": bool(all(
            r["fold_dispatches_fused"] == chunks for r in rows.values()
        )),
        "parity_ok": bool(all(
            all(r["parity"].values()) for r in rows.values()
        )),
        "compressed": compressed_row,
        "fused_codec_parity": bool(all(parity_c.values())),
        **trace_info,
        "available_cores": cores,
        "scaling_measurable": bool(cores >= 2 and marginal <= 0.10),
    }
    if not out["scaling_measurable"]:
        out["skipped_reason"] = (
            f"{cores}-core CPU stand-in: the fused program's Q folds "
            "execute serially on the ingest cores, so query Q+1 pays "
            "real wall here; the amortization is proven structurally "
            "instead — produce/compress/H2D span counts at Q=4 equal "
            "the Q=1 run and fold dispatches per chunk stay 1 at every "
            "Q (the <= 0.10 marginal-wall bar is the accelerator-host "
            "capture, where ingest dominates and the marginal fold is "
            "the 0.0009s dispatch of the r05 trace)"
        )
    return out


_DELTA_CROSSOVER_CHILD = r"""
import json, time
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
from gelly_tpu.core.chunk import make_chunk
from gelly_tpu.engine.aggregation import run_aggregation
from gelly_tpu.library.connected_components import connected_components
from gelly_tpu.obs import bus as obs_bus
from gelly_tpu.parallel import mesh as mesh_lib

S = 8
m = mesh_lib.make_mesh(S)
CAP = 1 << 14  # static chunk capacity; valid mask carries the row count
WINDOWS = 4
rng = np.random.default_rng(23)

def stream_for(n_v, rows):
    # Each window touches ~`rows` distinct vertices: a near-path over a
    # rotating contiguous range (dirty rows scale with `rows`, not CAP).
    chunks = []
    for w in range(WINDOWS):
        base = (w * rows * 2) % max(1, n_v - rows - 1)
        a = base + rng.integers(0, rows, CAP).astype(np.int64)
        b = np.minimum(a + 1, n_v - 1)
        valid_n = min(rows, CAP)
        src = np.zeros(CAP, np.int64); dst = np.zeros(CAP, np.int64)
        src[:valid_n] = a[:valid_n]; dst[:valid_n] = b[:valid_n]
        c = make_chunk(src.astype(np.int32), dst.astype(np.int32),
                       raw_src=src, raw_dst=dst, capacity=CAP,
                       device=False)
        mask = np.zeros(CAP, bool); mask[:valid_n] = True
        chunks.append(c._replace(valid=c.valid & mask))
    return chunks

# Two capacity classes: the small one is where the replicated merge is
# cheap enough for the crossover to land INSIDE the densities a chunk
# can generate; the large one documents the delta margin at serving
# capacity (the r05 regime where replicated hit the 32.2s cliff).
out = {}
for n_v in (1 << 15, 1 << 18):
    sweep = {}
    for rows in (256, 1024, 4096, 8192, 16384):
        row = {}
        # ONE stream per (capacity, density) point: both modes fold the
        # IDENTICAL chunks, so delta_s vs replicated_s differ only by
        # the window-close path (the shared rng would otherwise hand
        # each mode different edges — cross-stream noise in the very
        # comparison the calibration derives from).
        chunks = stream_for(n_v, rows)
        for mode in ("delta", "replicated"):
            agg = connected_components(
                n_v, merge="gather", ingest_combine=False,
                merge_mode=mode,
            )
            with obs_bus.scope() as bus:
                res = run_aggregation(
                    agg, chunks, mesh=m, merge_every=1,
                    ingest_workers=0, prefetch_depth=0, h2d_depth=0,
                )
                # Warm compile on a separate pass, then time the drain.
                for _ in res:
                    pass
                res = run_aggregation(
                    agg, chunks, mesh=m, merge_every=1,
                    ingest_workers=0, prefetch_depth=0, h2d_depth=0,
                )
                t0 = time.perf_counter()
                for _ in res:
                    pass
                row[mode + "_s"] = round(time.perf_counter() - t0, 4)
                if mode == "delta":
                    row["measured_dirty_rows"] = int(
                        bus.gauges.get("engine.window_dirty_rows", -1)
                    )
        sweep[str(rows)] = row
    out[str(n_v)] = sweep
print(json.dumps(out))
"""


def merge_delta_crossover_block() -> dict:
    """The ``merge_delta_auto_rows`` crossover sweep (ISSUE 10
    satellite): per-window dirty rows measured off the
    ``engine.window_dirty_rows`` gauge PR 5 wired, against the wall of
    merge_mode="delta" vs "replicated" on identical streams — so
    ``merge_mode="auto"`` gets a MEASURED threshold instead of the
    ``capacity/4`` structural guess (pass it back through
    ``connected_components(delta_auto_rows=)``). Runs on the
    8-virtual-device CPU mesh in a clean child (same harness as
    ``sharded_state_cc``); the recommended value is chip-relative —
    re-record on the serving hardware.
    """
    import os
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    kept = " ".join(
        t for t in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in t
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"{kept} --xla_force_host_platform_device_count=8".strip(),
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c",
             f"import sys; sys.path.insert(0, {here!r})\n"
             + _DELTA_CROSSOVER_CHILD],
            env=env, cwd=here, capture_output=True, text=True,
            timeout=1800,
        )
        if proc.returncode != 0:
            return {"metric": "merge_delta_crossover",
                    "error": proc.stderr[-400:]}
        rows = json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001 — never kill the headline line
        return {"metric": "merge_delta_crossover",
                "error": f"{type(e).__name__}: {e}"[:400]}
    S = 8
    calibration = {}
    headline = None
    for n_v_str, sweep in rows.items():
        n_v = int(n_v_str)
        crossover = None
        for r in sorted(sweep, key=int):
            if sweep[r]["delta_s"] >= sweep[r]["replicated_s"]:
                crossover = int(r)
                break
        if crossover is None:
            # Delta won at EVERY density a chunk can generate at this
            # capacity — including dirty ≈ capacity: the measured
            # threshold sits at or above the densest point, so the
            # cap/4 default is too CONSERVATIVE here (it hands dense
            # windows to the replicated merge delta still beats).
            # Record the densest measured win as a lower bound.
            densest = max(sweep, key=int)
            count = sweep[densest]["measured_dirty_rows"]
            bound = "lower"
        else:
            count = sweep[str(crossover)]["measured_dirty_rows"]
            bound = "measured"
        bucket = max(256, 1 << max(0, count - 1).bit_length())
        # The engine's auto rule compares S * bucket to the plan's
        # merge_delta_auto_rows: the calibrated value is the gathered
        # row count at the crossover density (or at the densest
        # delta-won point when no crossover landed in the sweep).
        recommended = S * bucket
        if headline is None:
            headline = recommended
        calibration[n_v_str] = {
            "crossover_rows": crossover,
            "bound": bound,
            "default_auto_rows": n_v // 4,
            "recommended_delta_auto_rows": recommended,
            "recommended_frac_of_capacity": round(recommended / n_v, 4),
            "sweep": sweep,
        }
    return {
        "metric": "merge_delta_crossover",
        "value": headline,
        "unit": "calibrated merge_delta_auto_rows (gathered rows) at "
                "the smallest measured capacity (8-dev CPU mesh)",
        "shards": S,
        "calibration": calibration,
        "calibration_note": (
            "pass recommended_delta_auto_rows to "
            "connected_components(delta_auto_rows=) on this chip; "
            "bound='lower' means delta won at every measurable "
            "density (crossover above the sweep — the cap/4 default "
            "switches to replicated too early); CPU-mesh capture — "
            "re-record on the serving hardware"
        ),
    }


def bench_windows(args=None) -> dict:
    """Pane-ring sliding windows (ISSUE 19): pane-close cost must scale
    with PANE size, not window length, and TTL decay must bound
    steady-state capacity by the active set.

    Two claims, both structural (ratios of walls captured on the same
    host, and monotone counters), so they hold on the CPU stand-in:

    - **O(pane) closes** — windowed CC at W ∈ {4, 16, 64} panes over the
      same stream: per-close wall stays flat in W (two-stack suffix
      aggregation pays O(1) amortized combines — see the
      ``combines_per_close`` counter ratio), while the full-replay
      oracle (re-fold the window's W·merge_every chunks from scratch at
      each close, the pre-ring cost) grows linearly in W.
    - **Bounded capacity** — compact CC + TTL over a DRIFTING stream
      (the active vertex block slides, so the cumulative id set grows
      without bound): the compact session's assigned-slot trace must
      plateau once the ring fills instead of tracking the cumulative
      set — steady-state memory ∝ active set, not stream length.

    Absolute edges/s here are a 1-core CPU stand-in
    (``scaling_measurable: false``); the committed claims are the
    W-independence, oracle-ratio, and plateau BOOLEANS.
    """
    import os

    from gelly_tpu import obs
    from gelly_tpu.core.io import EdgeChunkSource
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable
    from gelly_tpu.engine.aggregation import run_aggregation
    from gelly_tpu.library.connected_components import (
        connected_components,
    )

    n_v = 1 << 14
    chunk = 1 << 11
    me = 2  # pane = merge_every chunks
    panes_total = 160
    n_chunks = panes_total * me
    n_edges = n_chunks * chunk

    # Drifting stream: chunk i draws from a sliding 1<<10-vertex block,
    # advancing 16 ids per chunk (mod n_v) — cumulative ids far exceed
    # any window's active set, the TTL bench's forcing function.
    rng = np.random.default_rng(19)
    block = 1 << 10
    src = np.empty(n_edges, np.int64)
    dst = np.empty(n_edges, np.int64)
    for i in range(n_chunks):
        lo = (i * 16) % n_v
        s = lo + rng.integers(0, block, chunk)
        d = lo + rng.integers(0, block, chunk)
        src[i * chunk:(i + 1) * chunk] = s % n_v
        dst[i * chunk:(i + 1) * chunk] = d % n_v

    def stream(upto_chunks=n_chunks):
        srcq = EdgeChunkSource(src[:upto_chunks * chunk],
                               dst[:upto_chunks * chunk],
                               chunk_size=chunk,
                               table=IdentityVertexTable(n_v))
        return edge_stream_from_source(srcq, n_v)

    rows = {}
    per_close = {}
    oracle_per_close = {}
    trace_info = {}
    for w in (4, 16, 64):
        agg = connected_components(n_v, merge="gather", codec="dense",
                                   windowed=w)
        list(run_aggregation(agg, stream(), merge_every=me))  # warm
        wall = float("inf")
        for _ in range(3):
            with obs.scope() as bus:
                t0 = time.perf_counter()
                st = run_aggregation(agg, stream(), merge_every=me)
                n_out = sum(1 for _ in st)
                wall = min(wall, time.perf_counter() - t0)
                counters = bus.snapshot()["counters"]
        closes = counters.get("windows.panes_closed", n_out)
        per_close[w] = wall / max(closes, 1)

        # Full-replay oracle: the pre-ring cost of ONE close at this W —
        # re-fold the window's W*me chunks from scratch, one merge +
        # transform at the end (what every close would pay without the
        # ring). Same compiled fold, same chunk shape.
        oagg = connected_components(n_v, merge="gather", codec="dense")
        owin = min(w * me, n_chunks)
        run_aggregation(oagg, stream(owin), merge_every=owin).result()
        obest = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            run_aggregation(oagg, stream(owin), merge_every=owin).result()
            obest = min(obest, time.perf_counter() - t0)
        oracle_per_close[w] = obest

        if w == 64:
            tracer = obs.SpanTracer(capacity=1 << 16)
            with obs.scope() as tbus, obs.install(tracer):
                list(run_aggregation(agg, stream(), merge_every=me))
                tsnap = tbus.snapshot()
            tpath = trace_out_path("trace_windows")
            trace = obs.write_chrome_trace(
                tpath, tracer,
                extra={"workload": "windows_w64", **tsnap})
            closes_traced = tracer.instants("pane_close")
            trace_info = {
                "trace_file": os.path.basename(tpath),
                "trace_events": len(trace["traceEvents"]),
                "trace_pane_close_instants": len(closes_traced),
                "trace_ring_live_max": max(
                    (i["args"]["ring_live"] for i in closes_traced),
                    default=0),
            }

        rows[str(w)] = {
            "window_panes": w,
            "pane_close_wall_ms": round(per_close[w] * 1e3, 4),
            "replay_oracle_close_wall_ms": round(
                oracle_per_close[w] * 1e3, 4),
            "ring_vs_replay_speedup": round(
                oracle_per_close[w] / max(per_close[w], 1e-12), 2),
            "combines_per_close": round(
                counters.get("windows.combine_dispatches", 0)
                / max(closes, 1), 4),
            "panes_closed": int(closes),
            "edges_per_sec": round(n_edges / max(wall, 1e-9), 1),
        }

    # ---- TTL decay: bounded steady-state capacity on the drift ----
    w_ttl, ttl = 8, 8
    cagg = connected_components(n_v, codec="compact",
                                compact_capacity=n_v,
                                windowed=w_ttl, ttl_panes=ttl)
    st = run_aggregation(cagg, stream(), merge_every=me,
                         prefetch_depth=0, h2d_depth=0, ingest_workers=1)
    assigned = []
    for _ in st:
        assigned.append(int(cagg.session.assigned))
    fill = ttl + w_ttl  # TTL cannot evict before this many closes
    plateau = max(assigned[fill:])
    cumulative_ids = int(np.unique(np.concatenate([src, dst])).size)
    capacity_bounded = bool(
        plateau <= max(assigned[:fill])  # stopped growing at the fill
        and plateau * 3 <= cumulative_ids  # and is NOT cumulative
    )

    # ---- the committed structural claims ----
    w64_within_2x_w4 = bool(per_close[64] <= 2.0 * per_close[4])
    ring_8x_cheaper = bool(
        oracle_per_close[64] >= 8.0 * per_close[64])

    return {
        "metric": "windows_pane_ring",
        "value": round(per_close[64] * 1e3, 4),
        "unit": "ms per pane close at W=64 (pane = "
                f"{me} x {chunk}-edge chunks)",
        "per_window": rows,
        "claims": {
            "w64_close_within_2x_of_w4": w64_within_2x_w4,
            "ring_ge_8x_cheaper_than_replay_at_w64": ring_8x_cheaper,
            "ttl_capacity_bounded": capacity_bounded,
        },
        "ttl": {
            "window_panes": w_ttl,
            "ttl_panes": ttl,
            "assigned_trace_head": assigned[:fill],
            "assigned_trace_tail": assigned[-8:],
            "steady_state_slots": plateau,
            "cumulative_stream_ids": cumulative_ids,
        },
        **trace_info,
        "scaling_measurable": False,
        "skipped_reason": (
            "1-core CPU stand-in: absolute walls/edges-per-sec are not "
            "accelerator figures; the committed claims are the "
            "structural booleans (per-close flat in W, >=8x vs the "
            "replay oracle, TTL plateau), which are host-relative"
        ),
    }


# --------------------------------------------------------------------- #
# ISSUE 20: wire trace-context stamping overhead + e2e causal trace


def bench_obs(args):
    """Re-prove the <2% tracer-overhead contract with WIRE trace-context
    stamping enabled (ISSUE 20 satellite), and capture the committed
    end-to-end causal artifact.

    Interleaved best-of-3 loopback passes over the same payload set and
    compiled plan: tracer OFF vs tracer ON. With a tracer installed the
    client stamps every DATA frame's payload with (trace_id, span_id),
    the server links wire_recv/staging spans to it, and the engine
    chains fold → merge_emit → checkpoint through the tracer's context
    registry — so the ON side is the full stamping + linking cost, not
    just span recording. The best ON pass is exported as
    ``trace_e2e_wire.json``: one trace_id spanning client_send →
    wire_recv → staging → fold → checkpoint with parent span ids (the
    committed causal-chain artifact README cites).

    As with the file-ingest obs block, ``overhead_lt_2pct`` is a v5e
    claim; the CPU capture documents the schema and records the
    structural causal-chain booleans, which are host-relative.
    """
    import contextlib
    import os
    import tempfile
    import threading

    from gelly_tpu import obs
    from gelly_tpu.engine.aggregation import run_aggregation
    from gelly_tpu.ingest import IngestClient, IngestServer
    from gelly_tpu.ingest.client import edge_payload
    from gelly_tpu.library.connected_components import connected_components
    from gelly_tpu.parallel import mesh as mesh_lib

    n_v = 1 << 12
    chunk = 1 << 15
    n_chunks = 8
    n_e = chunk * n_chunks
    rng = np.random.default_rng(23)
    payloads = [
        edge_payload(rng.integers(0, n_v, chunk).astype(np.int64),
                     rng.integers(0, n_v, chunk).astype(np.int64))
        for _ in range(n_chunks)
    ]
    m1 = mesh_lib.make_mesh(1)
    agg = connected_components(n_v)  # shared: compiled plan caches on it

    def one_pass(tracer, ckpt_dir):
        ctx = (obs.install(tracer) if tracer is not None
               else contextlib.nullcontext())
        with obs.scope(), ctx:
            with IngestServer(queue_depth=64, stop_on_bye=True) as srv:
                def feed():
                    cli = IngestClient("127.0.0.1", srv.port,
                                       send_pause_timeout=120)
                    cli.connect()
                    for p in payloads:
                        cli.send(p)
                    cli.flush(timeout=300)
                    cli.close()

                th = threading.Thread(target=feed, daemon=True)
                th.start()
                t0 = time.perf_counter()
                # checkpoint_every is a WINDOW cadence: half-stream
                # windows + every-window checkpoints put two durable
                # points (and their linked checkpoint spans) in the
                # capture.
                res = run_aggregation(
                    agg, srv.chunks(chunk, n_v),
                    merge_every=n_chunks // 2, mesh=m1,
                    checkpoint_path=os.path.join(ckpt_dir, "ck.npz"),
                    checkpoint_every=1, ingest_workers=0,
                    prefetch_depth=0, h2d_depth=0,
                )
                np.asarray(res.result())
                wall = time.perf_counter() - t0
                th.join(timeout=60)
        return wall

    with tempfile.TemporaryDirectory() as ckpt_dir:
        one_pass(None, ckpt_dir)  # compile warmup outside measurement
        dt_off = dt_on = float("inf")
        best = None
        for _ in range(3):
            dt_off = min(dt_off, one_pass(None, ckpt_dir))
            tr = obs.SpanTracer(capacity=1 << 16, heartbeat_every_s=None)
            t = one_pass(tr, ckpt_dir)
            if t < dt_on:
                dt_on, best = t, tr

    tpath = trace_out_path("trace_e2e_wire")
    trace = obs.write_chrome_trace(
        tpath, best, extra={"workload": "e2e_wire"},
    )
    # Structural causal-chain claims over the exported ring: every stage
    # present, every span on the ONE trace_id, recv→staging parented to
    # the client's send span ids.
    sends = best.spans("client_send")
    recvs = best.spans("wire_recv")
    stages = best.spans("staging")
    folds = [s for s in best.spans("fold") if "trace" in s["args"]]
    ckpts = [s for s in best.spans("checkpoint") if "trace" in s["args"]]
    tid = best.trace_id
    linked = (
        [s["args"].get("trace") for s in sends + recvs + stages]
        + [s["args"]["trace"] for s in folds + ckpts]
    )
    send_ids = {s["args"]["span"] for s in sends}
    return {
        "metric": "obs_wire",
        "edges": n_e,
        "vertices": n_v,
        "chunk_size": chunk,
        "unit": "edges/sec",
        "wire_off_eps": round(n_e / dt_off, 1),
        "wire_on_eps": round(n_e / dt_on, 1),
        "overhead_frac": round(max(0.0, dt_on / dt_off - 1.0), 4),
        "overhead_lt_2pct": bool(dt_on / dt_off - 1.0 < 0.02),
        "trace_file": os.path.basename(tpath),
        "trace_events": len(trace["traceEvents"]),
        "trace_id": tid,
        "causal_chain": {
            "client_send_spans": len(sends),
            "wire_recv_spans": len(recvs),
            "staging_spans": len(stages),
            "fold_spans_linked": len(folds),
            "checkpoint_spans_linked": len(ckpts),
            "single_trace_id": bool(
                linked and all(t == tid for t in linked)),
            "recv_parented_to_send": bool(
                recvs and all(r["args"].get("parent") in send_ids
                              for r in recvs)),
        },
        "scaling_measurable": False,
        "skipped_reason": (
            "1-core CPU stand-in: overhead_lt_2pct is a v5e claim; the "
            "committed claims here are the causal-chain booleans"
        ),
    }


def _carries_error(obj) -> bool:
    """Does an emitted line hold an error anywhere (``error`` or a
    ``*_error`` key, at any depth)?"""
    if isinstance(obj, dict):
        return any(
            (k == "error" or str(k).endswith("_error")) and v is not None
            or _carries_error(v)
            for k, v in obj.items()
        )
    if isinstance(obj, list):
        return any(_carries_error(v) for v in obj)
    return False


def main() -> int:
    """Run the selected workloads; non-zero when any emitted line
    carries an error (phase failures are recorded, never swallowed)."""
    from gelly_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    rc = _run_workloads()
    if rc == 0 and any(_carries_error(line) for line in _BENCH_LINES):
        rc = 1
    return rc


def _run_workloads() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="all",
                   choices=["all", "cc", "cc_large", "degrees", "triangles",
                            "bipartiteness", "matching", "spanner", "codec",
                            "gather", "ingest", "tenants", "multiquery",
                            "windows", "obs"])
    # K-points for the subprocess codec-scaling sweep (codec_workers_eps):
    # comma list; oversubscribed K on small hosts is fine (the points then
    # bound, rather than exhibit, scaling).
    p.add_argument("--codec-workers", default="1,2,4")
    p.add_argument("--edges", type=int, default=64_000_000)
    p.add_argument("--vertices", type=int, default=1 << 17)
    p.add_argument("--chunk-size", type=int, default=1 << 23)
    p.add_argument("--merge-every", type=int, default=2)
    p.add_argument("--fold-batch", type=int, default=2)
    p.add_argument("--large-edges", type=int, default=1 << 28)
    p.add_argument("--large-vertices", type=int, default=1 << 24)
    # 2^20 measured best end-to-end at 2^28 edges: the sparse combiner's
    # hash table stays near-cache-sized (codec ~45M edges/s single-core
    # vs ~32M at 2^22) while the group pre-combine keeps device
    # dispatches amortized.
    p.add_argument("--large-chunk-size", type=int, default=1 << 20)
    p.add_argument("--skip-parity", action="store_true")
    args = p.parse_args()

    others = {
        "degrees": bench_degrees,
        "triangles": bench_triangles,
        "bipartiteness": bench_bipartiteness,
        "matching": bench_matching,
    }

    # Non-CC workloads keep per-edge python baselines: clamp their sizes so
    # a single-workload run doesn't inherit the CC-scale 64M default.
    small = argparse.Namespace(**vars(args))
    small.edges = min(args.edges, 2_000_000)
    small.chunk_size = min(args.chunk_size, 1 << 18)
    small.merge_every = 8

    if args.workload == "gather":
        emit({"metric": "gather_study", **gather_study_block()})
        write_bench_artifact(args.workload)
        return 0
    if args.workload == "codec":
        src, dst = synth_edges(min(args.edges, 1 << 24), args.vertices)
        emit({
            "metric": "codec_workers",
            **codec_workers_block(
                src, dst, args.vertices, min(args.chunk_size, 1 << 20),
                ks=tuple(int(k) for k in args.codec_workers.split(",")),
            ),
        })
        write_bench_artifact(args.workload)
        return 0
    if args.workload == "ingest":
        emit(bench_ingest(args))
        write_bench_artifact(args.workload)
        return 0
    if args.workload == "tenants":
        emit(bench_tenants(args))
        emit(merge_delta_crossover_block())
        write_bench_artifact(args.workload)
        return 0
    if args.workload == "multiquery":
        emit(bench_multiquery(args))
        write_bench_artifact(args.workload)
        return 0
    if args.workload == "windows":
        emit(bench_windows(args))
        write_bench_artifact(args.workload)
        return 0
    if args.workload == "obs":
        emit(bench_obs(args))
        write_bench_artifact(args.workload)
        return 0
    if args.workload == "spanner":
        emit(bench_spanner(args))
        write_bench_artifact(args.workload)
        return 0
    if args.workload == "cc":
        emit(bench_cc(args))
        write_bench_artifact(args.workload)
        return 0
    if args.workload == "cc_large":
        emit(bench_cc_large(args))
        write_bench_artifact(args.workload)
        return 0
    # bipartiteness and degrees run codec-scale streams and self-clamp
    # their python baselines; the rest keep per-edge python baselines and
    # need the small sizes end to end.
    full_size = ("bipartiteness", "degrees")

    if args.workload != "all":
        out = others[args.workload](
            args if args.workload in full_size else small
        )
        metric, eps, base_eps = out[:3]
        emit({
            "metric": metric,
            "value": round(eps, 1),
            "unit": "edges/sec",
            "vs_baseline": round(eps / base_eps, 2),
            **(out[3] if len(out) > 3 else {}),
        })
        write_bench_artifact(args.workload)
        return 0

    # Default: all five BASELINE workloads plus the Twitter-scale CC
    # config, one JSON line each; the north-star-scale CC line prints
    # LAST so a last-line parser records it. The full line set also
    # lands in bench_out.json (write_bench_artifact). A workload that
    # fails records an error line and the run goes on; main() then
    # exits non-zero.
    rc = 0
    try:
        for name, fn in others.items():
            try:
                out = fn(args if name in full_size else small)
                metric, eps, base_eps = out[:3]
                emit({
                    "metric": metric,
                    "value": round(eps, 1),
                    "unit": "edges/sec",
                    "vs_baseline": round(eps / base_eps, 2),
                    **(out[3] if len(out) > 3 else {}),
                })
            except (SystemExit, Exception) as e:  # noqa: BLE001
                # A parity SystemExit or a workload crash still records a
                # line: the artifact must carry every workload either way.
                emit({"metric": name, "error": f"{type(e).__name__}: {e}"})
        for name, heavy in (
            ("spanner_device", lambda: bench_spanner(args)),
            ("ingest", lambda: bench_ingest(args)),
            ("tenants_batched_fold", lambda: bench_tenants(args)),
            ("windows_pane_ring", lambda: bench_windows(args)),
            ("merge_delta_crossover", merge_delta_crossover_block),
            ("streaming_cc_throughput", lambda: bench_cc(args)),
            ("sharded_state_cc", bench_sharded_state),
            ("streaming_cc_large", lambda: bench_cc_large(args)),
        ):
            try:
                emit(heavy())
            except (SystemExit, Exception) as e:  # noqa: BLE001
                emit({"metric": name, "error": f"{type(e).__name__}: {e}"})
    finally:
        write_bench_artifact(args.workload)
    return rc


if __name__ == "__main__":
    sys.exit(main())
