"""Bring-up smoke of the streaming CC pipeline on a TPU.

Usage: python chip_smoke.py [--chips 4]

One process drives the system's main path through the entry points a
user calls, at the north-star width (``vertex_capacity = 2^24``, the
``bench_cc_large`` configuration), and checks every result against an
independent reference:

  a. device check — a TPU or a non-zero exit, never a CPU run;
  b. file-fed streaming CC (compact codec, pipelined executor at its
     defaults) over 2^26 Zipf edges vs the pure-numpy oracle;
  c. the raw device fold, compiled for ``fold_backend`` xla and pallas
     (the pallas plan must hold a compiled ``tpu_custom_call``);
  d. window triangles where ``method="auto"`` picks the MXU kernel, vs
     ``method="gather"``;
  e. the served path: an ``IngestServer`` fed client-compressed STACKED
     frames, vs the file-fed fold of the same edges.

``--chips 4`` runs only the mesh path (compact-codec CC over a 4-device
mesh plus slot-sharded ``ShardedCC``) and its one-chip comparison.

Each phase prints one JSON line (wall, compile time, parity); the last
line is ``{"ok": true, "device": {...}}`` only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(HERE, ".smoke_data")  # gitignored, removed after

# Phase sizes at the north-star width (bench.py bench_cc_large).
FULL = {
    "n_v": 1 << 24,
    "n_e": 1 << 26,
    "chunk": 1 << 20,
    "compact_m": 1 << 23,
    "merge_every": 16,           # 64 chunks -> 4 windows
    "raw_chunk": 1 << 22,
    "raw_chunks": 4,
    "tri_n": 4096,
    "tri_window_edges": 1 << 14,
    "tri_windows": 4,
    "served_edges": 1 << 22,
    "wire_chunk": 1 << 16,
    "stack": 8,
    "mesh_edges": 1 << 24,
    "mesh_merge_every": 8,
}
SEED = 17


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events (a cache hit is timed as its retrieval)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.compile_s, self.cache_hits


def run_phase(name: str, clock: CompileClock, fn, *args) -> bool:
    """Run one phase and print its line; True when it passed."""
    c0, h0 = clock.mark()
    t0 = time.perf_counter()
    line = {"phase": name}
    try:
        extra = fn(*args)
        line["parity"] = bool(extra.pop("parity"))
        line.update(extra)
    except Exception as e:  # noqa: BLE001 — reported, then exit non-zero
        line["parity"] = False
        line["error"] = f"{type(e).__name__}: {e}"[:800]
    line["wall_s"] = time.perf_counter() - t0
    line["compile_s"] = clock.compile_s - c0
    line["cache_hits"] = clock.cache_hits - h0
    emit(line)
    return line["parity"] and "error" not in line


# ------------------------------------------------------------------ #
# reference data and oracle


def oracle_labels(src, dst, n_v: int, step: int = 1 << 22) -> np.ndarray:
    """Pure-numpy CC labels (canonical min slot, -1 untouched): chunked
    spanning-forest pairs, then one fixpoint over all of them."""
    from gelly_tpu.library.connected_components import (
        cc_labels_numpy,
        cc_pairs_numpy,
    )

    pv, pr = [], []
    for lo in range(0, src.shape[0], step):
        v, r = cc_pairs_numpy(src[lo:lo + step], dst[lo:lo + step], None, n_v)
        pv.append(v)
        pr.append(r)
    return cc_labels_numpy(np.concatenate(pv).astype(np.int32),
                           np.concatenate(pr).astype(np.int32), None, n_v)


def compact_cc(sz: dict):
    from gelly_tpu.library.connected_components import connected_components

    return connected_components(sz["n_v"], merge="gather", codec="compact",
                                compact_capacity=sz["compact_m"])


def file_fed_labels(agg, src, dst, sz: dict, name: str, mesh=None,
                    merge_every=None):
    """Write the edges as a binary edge file and fold it through
    ``stream.aggregate`` (pipelined executor at its defaults); returns
    (final labels, windows closed)."""
    from gelly_tpu.ingest.readers import (
        edge_stream_from_sharded_file,
        write_binary_edges,
    )

    if mesh is None:
        mesh = one_chip_mesh()
    os.makedirs(DATA_DIR, exist_ok=True)
    path = os.path.join(DATA_DIR, f"{name}.bin")
    write_binary_edges(path, src, dst)
    try:
        stream = edge_stream_from_sharded_file(
            path, sz["n_v"], shards=1, chunk_size=sz["chunk"],
        )
        labels, windows = None, 0
        for labels in stream.aggregate(
            agg, mesh=mesh,
            merge_every=merge_every or sz["merge_every"],
        ):
            windows += 1
        return np.asarray(labels), windows
    finally:
        os.remove(path)


def one_chip_mesh():
    import jax

    from gelly_tpu.parallel.mesh import make_mesh

    return make_mesh(1, jax.devices()[:1])


def require_native() -> None:
    from gelly_tpu.utils import native

    if not native.available("chunk_combiner"):
        raise RuntimeError(
            "native chunk_combiner unavailable; the smoke never takes the "
            "numpy fallback"
        )


# ------------------------------------------------------------------ #
# phases


def phase_stream_cc(sz: dict, edges, state: dict) -> dict:
    """(b) file-fed streaming CC at north-star width vs the oracle."""
    require_native()
    src, dst = edges
    agg = compact_cc(sz)
    labels, windows = file_fed_labels(agg, src, dst, sz, "stream")
    oracle = oracle_labels(src, dst, sz["n_v"])
    state["agg"] = agg
    mism = int((labels != oracle).sum())
    return {
        "parity": mism == 0 and windows >= 4,
        "vertex_capacity": sz["n_v"], "edges": int(src.shape[0]),
        "windows": windows, "mismatches": mism,
        "components": int((oracle == np.arange(sz["n_v"])).sum()),
    }


def phase_raw_fold(sz: dict, edges) -> dict:
    """(c) raw device fold, xla vs pallas backend, compiled."""
    import jax

    from gelly_tpu.core.chunk import make_chunk
    from gelly_tpu.library.connected_components import connected_components

    src, dst = edges
    c = sz["raw_chunk"]
    chunks = [
        make_chunk(src[i * c:(i + 1) * c], dst[i * c:(i + 1) * c],
                   capacity=c)
        for i in range(sz["raw_chunks"])
    ]
    on_tpu = jax.devices()[0].platform == "tpu"
    out, line = {}, {}
    for backend in ("xla", "pallas"):
        agg = connected_components(sz["n_v"], fold_backend=backend)
        state = agg.init()
        plan = jax.jit(agg.fold, donate_argnums=0).lower(
            state, chunks[0]).compile()
        kernel = "tpu_custom_call" in plan.as_text()
        if backend == "pallas" and on_tpu and not kernel:
            raise AssertionError("pallas fold plan holds no tpu_custom_call")
        mem = plan.memory_analysis()
        line[f"{backend}_temp_bytes"] = (
            None if mem is None else int(mem.temp_size_in_bytes))
        line[f"{backend}_kernel"] = kernel
        for ch in chunks:
            state = plan(state, ch)
        out[backend] = np.asarray(agg.transform(state))
    n_used = sz["raw_chunk"] * sz["raw_chunks"]
    oracle = oracle_labels(src[:n_used], dst[:n_used], sz["n_v"])
    return {
        "parity": bool(np.array_equal(out["xla"], out["pallas"])
                       and np.array_equal(out["xla"], oracle)),
        "edges": n_used, **line,
    }


def phase_window_triangles(sz: dict) -> dict:
    """(d) window triangles where auto picks the MXU kernel."""
    import jax

    from gelly_tpu.core.io import EdgeChunkSource, TimeCharacteristic
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable
    from gelly_tpu.library import triangles
    from gelly_tpu.ops import pallas_kernels as pk

    n = sz["tri_n"]
    per = sz["tri_window_edges"]
    n_e = per * sz["tri_windows"]
    rng = np.random.default_rng(SEED)
    src = rng.integers(0, n, n_e).astype(np.int64)
    dst = rng.integers(0, n, n_e).astype(np.int64)
    window_ms = 1000
    ts = (np.arange(n_e, dtype=np.int64) // per) * window_ms

    def stream():
        return edge_stream_from_source(
            EdgeChunkSource(src, dst, timestamps=ts, chunk_size=per,
                            table=IdentityVertexTable(n),
                            time=TimeCharacteristic.EVENT), n)

    counts = {}
    for method in ("auto", "gather"):
        counts[method] = dict(triangles.window_triangles(
            stream(), window_ms, capacity=n, window_capacity=2 * per,
            method=method))
    mxu = triangles._pick_method("auto", n)(n) == "mxu"
    kernel = None
    if jax.devices()[0].platform == "tpu":
        if not mxu:
            raise AssertionError(f"auto did not pick mxu at n={n}")
        x = jax.ShapeDtypeStruct((n, n), np.bool_)
        kernel = "tpu_custom_call" in jax.jit(pk.wedge_count_matrix).lower(
            x).compile().as_text()
        if not kernel:
            raise AssertionError("wedge kernel was not compiled")
    return {
        "parity": (counts["auto"] == counts["gather"]
                   and len(counts["auto"]) == sz["tri_windows"]),
        "slots": n, "windows": len(counts["auto"]), "auto_is_mxu": mxu,
        "kernel": kernel, "triangles": int(sum(counts["auto"].values())),
    }


def phase_served(sz: dict, edges, state: dict) -> dict:
    """(e) IngestServer on loopback fed client-compressed STACKED frames
    vs the file-fed fold of the same edges."""
    from gelly_tpu.core.chunk import make_chunk
    from gelly_tpu.engine.aggregation import run_aggregation
    from gelly_tpu.ingest import IngestClient, IngestServer
    from gelly_tpu.library.connected_components import connected_components

    require_native()
    n_e = sz["served_edges"]
    src, dst = edges[0][:n_e], edges[1][:n_e]
    wc = sz["wire_chunk"]
    # The shared client/server codec (examples/connected_components.py
    # --compressed): sparse (vertex, root) pairs.
    agg = connected_components(sz["n_v"], codec="sparse")
    server = IngestServer(host="127.0.0.1", port=0, stop_on_bye=True).start()
    sent: dict = {}

    def client():
        try:
            cli = IngestClient("127.0.0.1", server.port,
                               stack=sz["stack"]).connect()
            frames = 0
            for lo in range(0, n_e, wc):
                s, d = src[lo:lo + wc], dst[lo:lo + wc]
                c = make_chunk(s, d, raw_src=s.astype(np.int64),
                               raw_dst=d.astype(np.int64), capacity=wc,
                               device=False)
                cli.send(agg.host_compress(c), compressed=True)
                frames += 1
            cli.flush(timeout=120)
            sent["acked"] = cli.acked
            cli.close()
            sent["frames"] = frames
        except Exception as e:  # noqa: BLE001 — surfaced by the phase
            sent["error"] = e
            server.stop()

    th = threading.Thread(target=client, name="smoke-client", daemon=True)
    th.start()
    labels = None
    try:
        for labels in run_aggregation(
            agg, server.compressed_payloads(), mesh=one_chip_mesh(),
            merge_every=sz["merge_every"], precompressed=True,
        ):
            pass
    finally:
        server.stop()
        th.join(timeout=120)
    if "error" in sent:
        raise sent["error"]
    served = np.asarray(labels)
    file_fed, _ = file_fed_labels(
        state.get("agg") or compact_cc(sz), src, dst, sz, "served")
    return {
        "parity": bool(np.array_equal(served, file_fed)),
        "edges": n_e, "frames": sent.get("frames"),
        "acked": sent.get("acked"), "stack": sz["stack"],
    }


def phase_mesh(sz: dict, edges, n_chips: int) -> dict:
    """(--chips) compact-codec CC over an n-chip mesh and slot-sharded
    ShardedCC, each vs the same plan on a one-chip mesh."""
    import jax

    from gelly_tpu.parallel.mesh import make_mesh
    from gelly_tpu.parallel.sharded_cc import ShardedCC

    require_native()
    devs = jax.devices()
    if len(devs) < n_chips:
        raise RuntimeError(f"need {n_chips} devices, have {len(devs)}")
    src, dst = edges
    meshes = {n_chips: make_mesh(n_chips, devs[:n_chips]),
              1: one_chip_mesh()}
    in_use0 = [_bytes_in_use(d) for d in devs[:n_chips]]
    labels, sharded = {}, {}
    held = None
    for s, mesh in meshes.items():
        labels[s], windows = file_fed_labels(
            compact_cc(sz), src, dst, sz, f"mesh{s}", mesh=mesh,
            merge_every=sz["mesh_merge_every"])
        scc = ShardedCC(sz["n_v"], mesh=mesh)
        for lo in range(0, src.shape[0], sz["chunk"]):
            scc.fold(src[lo:lo + sz["chunk"]], dst[lo:lo + sz["chunk"]])
        if s == n_chips:
            held = scc  # keep the sharded state alive for the checks
        sharded[s] = scc.labels()
    shard_devs = {sh.device for sh in held.parent.addressable_shards}
    in_use1 = [_bytes_in_use(d) for d in devs[:n_chips]]
    grew = (None if None in in_use0 + in_use1
            else all(b > a for a, b in zip(in_use0, in_use1)))
    if len(shard_devs) != n_chips:
        raise AssertionError(f"ShardedCC state on {len(shard_devs)} devices")
    if grew is False:
        raise AssertionError(f"bytes_in_use did not grow on every device: "
                             f"{in_use0} -> {in_use1}")
    if jax.devices()[0].platform == "tpu" and grew is None:
        raise AssertionError("memory_stats() unavailable on the chip")
    return {
        "parity": bool(np.array_equal(labels[n_chips], labels[1])
                       and np.array_equal(sharded[n_chips], sharded[1])
                       and np.array_equal(labels[1], sharded[1])),
        "chips": n_chips, "edges": int(src.shape[0]),
        "vertex_capacity": sz["n_v"], "windows": windows,
        "shard_devices": len(shard_devs), "bytes_in_use_grew": grew,
        "bytes_in_use": in_use1,
    }


def _bytes_in_use(device):
    stats = device.memory_stats()
    return None if not stats else int(stats.get("bytes_in_use", 0))


# ------------------------------------------------------------------ #
# driver


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = p.parse_args(argv)
    sz = FULL

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    emit({"phase": "device", "device": device, "jax": jax.__version__,
          "libtpu": libtpu_version})
    if dev.platform != "tpu":
        emit({"phase": "device", "parity": False,
              "error": f"no TPU: platform is {dev.platform!r}"})
        return 2
    if device["count"] < args.chips:
        emit({"phase": "device", "parity": False,
              "error": f"--chips {args.chips} but {device['count']} found"})
        return 2

    from bench import synth_edges
    from gelly_tpu.utils.compile_cache import enable_compile_cache

    emit({"phase": "compile_cache", "dir": enable_compile_cache(),
          "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))})
    clock = CompileClock()
    ok = True
    try:
        if args.chips > 1:
            edges = synth_edges(sz["mesh_edges"], sz["n_v"], seed=SEED)
            ok = run_phase("mesh", clock, phase_mesh, sz, edges, args.chips)
        else:
            edges = synth_edges(sz["n_e"], sz["n_v"], seed=SEED)
            state: dict = {}
            for name, fn, fargs in (
                ("stream_cc", phase_stream_cc, (sz, edges, state)),
                ("raw_fold", phase_raw_fold, (sz, edges)),
                ("window_triangles", phase_window_triangles, (sz,)),
                ("served", phase_served, (sz, edges, state)),
            ):
                ok = run_phase(name, clock, fn, *fargs) and ok
    finally:
        shutil.rmtree(DATA_DIR, ignore_errors=True)
    if not ok:
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
