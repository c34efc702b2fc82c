"""Streaming Connected Components — the north-star algorithm.

TPU-native re-design of ``M/library/ConnectedComponents.java:41-127`` and
``ConnectedComponentsTree.java:26-36``: the per-partition ``DisjointSet``
hash-map forest becomes a dense ``i32 parent[]`` array; ``UpdateCC.foldEdges``
(per-edge ``ds.union``) becomes a whole-chunk vectorized union
(:func:`gelly_tpu.ops.unionfind.union_edges`); ``CombineCC.reduce`` (merge
smaller forest into larger) becomes either

- a **butterfly merge-tree** over ICI (`merge="tree"`) — the
  ``SummaryTreeReduce`` log-depth reduction mapped onto the slice topology, or
- an **all_gather + stacked K×N union** (`merge="gather"`) — the flat
  ``timeWindowAll().reduce`` fan-in, vectorized.

The summary is ``(parent[i32 N], seen[bool N])``; emitted labels are the
minimum vertex slot of each component (canonical), decoded to raw ids for the
final parity oracle (component-set equality, as the reference's test asserts,
``T/example/test/ConnectedComponentsTest.java:40-47``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.stream import EdgeStream
from ..engine.aggregation import (  # noqa: F401  (threshold re-exported)
    SPARSE_CODEC_MIN_CAPACITY,
    SummaryAggregation,
    sparse_payload_id_check,
)
from ..obs.bus import get_bus
from ..ops import segments, unionfind
from ..ops.pallas_kernels import pallas_interpret


class CCSummary(NamedTuple):
    parent: jax.Array  # i32[N] union-find forest (canonical min-root)
    seen: jax.Array  # bool[N] vertices observed in the stream


# Raw (codec-off) folds switch from the generic union_edges fixpoint to
# the sort-dedup kernel at this chunk size: below it the dedup sorts
# cost more than the rounds they save.
RAW_DEDUP_MIN_CHUNK = 1 << 22


class CCCompactSummary(NamedTuple):
    """Compact-space CC summary (``codec="compact"``): the forest lives in a
    persistent window-scoped compact id space of M slots (M bounds distinct
    touched vertices, not capacity), with the cid → vertex-slot table as the
    decode side."""

    croot: jax.Array  # i32[M] union-find forest over compact ids
    vertex_of: jax.Array  # i32[M] global vertex slot per cid (-1 unassigned)


class CCWindowPane(NamedTuple):
    """One PANE of the windowed compact plan (``windowed=W``): the pane's
    own forest and first-seen decode rows, plus the exact touched-cid
    mask — the window-membership predicate (a self-loop-only vertex
    never moves ``croot`` off the identity, so ``touched`` is recorded
    from the wire payload lanes, not inferred from the forest) and the
    TTL last-seen source."""

    croot: jax.Array  # i32[M] union-find forest over compact ids
    vertex_of: jax.Array  # i32[M] global vertex slot per cid (-1 unassigned)
    touched: jax.Array  # bool[M] cids referenced by this pane's payloads


def _native_ok() -> bool:
    """Is the native chunk combiner available? (Probed once, negative-cached
    in utils.native so a missing toolchain doesn't re-run g++ per chunk.)"""
    from ..utils import native

    return native.available("chunk_combiner")


def cc_labels_numpy(src: np.ndarray, dst: np.ndarray,
                    valid: np.ndarray | None, n_v: int) -> np.ndarray:
    """Pure-numpy fallback for the native chunk combiner: spanning-forest
    labels i32[n_v] of one chunk (-1 for untouched slots)."""
    if valid is not None:
        m = np.asarray(valid, bool)
        src, dst = np.asarray(src)[m], np.asarray(dst)[m]
    lab = np.full((n_v,), -1, np.int32)
    if src.size == 0:
        return lab
    touched = np.zeros((n_v,), bool)
    touched[src] = True
    touched[dst] = True
    lab[touched] = np.nonzero(touched)[0].astype(np.int32)
    while True:
        prev = lab.copy()
        mn = np.minimum(lab[src], lab[dst]).astype(np.int32)
        np.minimum.at(lab, src, mn)
        np.minimum.at(lab, dst, mn)
        t = np.nonzero(touched)[0]
        lab[t] = np.minimum(lab[t], lab[lab[t]])
        if np.array_equal(lab, prev):
            break
    return lab


def cc_pairs_numpy(src: np.ndarray, dst: np.ndarray,
                   valid: np.ndarray | None, n_v: int):
    """Pure-numpy fallback for the native sparse combiner: counted
    (vertex, root) pairs of one chunk's spanning forest — work and payload
    proportional to touched vertices, never ``n_v``."""
    if valid is not None:
        m = np.asarray(valid, bool)
        src, dst = np.asarray(src)[m], np.asarray(dst)[m]
    src = np.asarray(src)
    dst = np.asarray(dst)
    if src.size == 0:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    ids = np.unique(np.concatenate([src, dst]))
    if ids[0] < 0 or ids[-1] >= n_v:
        raise ValueError("cc_pairs_numpy: vertex slot out of range")
    ls = np.searchsorted(ids, src)
    ld = np.searchsorted(ids, dst)
    lab = np.arange(ids.shape[0], dtype=np.int64)
    while True:
        prev = lab
        mn = np.minimum(lab[ls], lab[ld])
        lab = lab.copy()
        np.minimum.at(lab, ls, mn)
        np.minimum.at(lab, ld, mn)
        lab = np.minimum(lab, lab[lab])
        if np.array_equal(lab, prev):
            break
    return ids.astype(np.int32), ids[lab].astype(np.int32)


def merge_chunk_forest(glob: np.ndarray, lab: np.ndarray) -> np.ndarray:
    """Hook a chunk's spanning-forest labels into a global dense forest
    (host numpy — the vectorized CPU analog of the device union).

    Shiloach-Vishkin shape: hook at LABEL (root) indices — writing at the
    vertex indices would lose transitivity when a later chunk lowers part
    of an old component (the old root never learns) — plus one doubling
    step per round until fixpoint. Returns the updated ``glob``.
    """
    ok = lab >= 0
    v = np.nonzero(ok)[0].astype(np.int32)
    r = lab[v]
    while True:
        prev = glob
        lab_u = glob[v]
        lab_v = glob[r]
        lab_lo = np.minimum(lab_u, lab_v)
        lab_hi = np.maximum(lab_u, lab_v)
        glob = glob.copy()
        np.minimum.at(glob, lab_hi, lab_lo)
        glob = np.minimum(glob, glob[glob])
        if np.array_equal(glob, prev):
            break
    return glob


def lane_segment_starts(ln: jax.Array, capm: int):
    """Segment start of every member lane on the segment wire.

    ``ln`` is i32[K, capr]: each row's segment lengths in lane order,
    zero-padded (a zero may also sit between segments). Returns ``(ri,
    valid)``, both [K, capm]: ``ri`` is the first lane of the segment
    holding each lane, ``valid`` marks lanes below the row's total.

    Each segment's length is scatter-added at its END lane (the next
    segment's start), so a running sum along the lanes reads, at every
    lane, the end of the last segment closed at or before it — its own
    segment's start. O(lanes), no search over the lengths. A full row's
    last end falls past the lanes and drops; ``valid`` masks the lanes
    at and past the total.
    """
    cum = jnp.cumsum(ln, axis=1, dtype=jnp.int32)
    rows = jnp.arange(ln.shape[0], dtype=jnp.int32)[:, None]
    ends = jnp.zeros((ln.shape[0], capm), jnp.int32).at[rows, cum].add(
        ln.astype(jnp.int32), mode="drop"
    )
    ri = jnp.cumsum(ends, axis=1, dtype=jnp.int32)
    valid = jnp.arange(capm, dtype=jnp.int32)[None, :] < cum[:, -1:]
    return ri, valid


def connected_components_compact(
    vertex_capacity: int, merge: str = "gather",
    compact_capacity: int | None = None, wire: str = "auto",
    unit_block: int = 1 << 18, merge_mode: str = "auto",
    delta_auto_rows: int | None = None,
    windowed: int | None = None, ttl_panes: int | None = None,
) -> SummaryAggregation:
    """CC over a **persistent compact root space** — the large-N fast path
    (``codec="compact"``).

    The ``codec="sparse"`` device fold spent ~85% of each dispatch
    re-compacting pair roots on device (sort + 3 binary-search passes,
    ~1.1s/dispatch at n_v=2^24 on v5e). Here the host ingest codec — which
    already hashes every touched vertex to build the chunk forest — assigns
    each vertex a persistent first-seen compact id
    (:class:`~gelly_tpu.ops.compact_space.CompactIdSession`, one table probe
    per *pair*), and ships pairs already dense in ``[0, M)``. The device
    fold is then a pure M-space union fixpoint: no sort, no searchsorted,
    and **no O(vertex_capacity) work per dispatch** — full-capacity arrays
    are touched exactly once per window, in ``transform``, when the labels
    materialize.

    Same final labels as every other CC plan (canonical min vertex slot per
    component, -1 unseen); same reference semantics
    (``M/SummaryBulkAggregation.java:76-83`` — per-partition partial fold,
    periodic global merge). ``M = compact_capacity`` bounds distinct touched
    vertices per run (NOT edges); overflow raises
    :class:`~gelly_tpu.ops.compact_space.CompactSpaceOverflow` with sizing
    guidance. Requires the ingest codec path: raw-chunk folds (window mode,
    ``ingest_combine=False``) must use ``codec="sparse"`` instead.

    ``wire`` picks the payload wire format (VERDICT r4 items 1+7):

    - ``"segments"`` — the fused native unit codec
      (``native/chunk_combiner.cc:cc_unit_forest_segments``): ONE call
      per merge-window unit runs the dedup-blocked two-level combine and
      emits members grouped by component, each component's root FIRST in
      its segment. The device derives every pair's root-row index as its
      segment start, with one scatter of segment ends and a running sum
      along the lanes (:func:`lane_segment_starts`), so the pair wire is
      4 bytes/member + one length per component — half the ``"pairs"``
      bytes — and the per-chunk numpy group-combine disappears.
      ``unit_block`` is the cache-blocking granule of the level-1 pass
      (2^18 edges measured fastest).
    - ``"pairs"`` — the per-chunk sparse combine + (v, root-index) pair
      rows (round 4's format; the no-native-toolchain fallback).
    - ``"auto"`` (default) — segments when the native codec is available.

    ``windowed=W`` builds the PANE-RING variant: the summary type grows
    an exact touched-cid mask (:class:`CCWindowPane`) so the engine's
    ring answers "components over the last W panes" (labels cover only
    window-touched vertices), and the plan exports the persistent-id /
    TTL hooks (``windowed_persist_*``, ``windowed_touched``,
    ``windowed_evict``, ``on_resume_windowed``) the engine's TTL decay
    and exactly-once ring resume ride. ``ttl_panes=T`` (T >= W) arms
    per-vertex decay: a cid slot untouched for T panes is evicted and
    its session capacity reclaimed at the next pane boundary. The
    windowed variant is merge_mode="replicated" only (a pane ring
    retires panes; the dirty-delta merge folds into a carried global —
    exclusive memory models).
    """
    from ..ops.compact_space import CompactIdSession
    from ..utils import native

    n = vertex_capacity
    m = compact_capacity or min(n, 1 << 22)
    session = CompactIdSession(m)
    if wire not in ("auto", "segments", "pairs"):
        raise ValueError(f"wire must be auto/segments/pairs, got {wire}")
    use_segments = wire == "segments" or (
        wire == "auto" and native.unit_segments_available()
    )

    def init() -> CCCompactSummary:
        return CCCompactSummary(
            croot=unionfind.fresh_forest(m),
            vertex_of=jnp.full((m,), -1, jnp.int32),
        )

    def fold(s, chunk):
        raise NotImplementedError(
            "codec='compact' folds compressed payloads only (its id space "
            "is assigned by the host ingest codec); use codec='sparse' for "
            "raw-chunk or window_ms plans"
        )

    def host_compress(chunk) -> dict:
        if native.sparse_codecs_available():
            v, r = native.cc_chunk_combine_sparse(
                np.asarray(chunk.src), np.asarray(chunk.dst),
                np.asarray(chunk.valid), n,
            )
        else:
            v, r = cc_pairs_numpy(chunk.src, chunk.dst, chunk.valid, n)
        return {"v": v, "r": r}

    def host_compress_raw(chunk) -> dict:
        # Segment wire: per-chunk compression is a no-op (zero-copy views)
        # — the WHOLE unit combines in one fused native call in the
        # stacker, where blocking keeps the intern tables cache-resident
        # regardless of the caller's chunk size.
        return {
            "src": np.asarray(chunk.src),
            "dst": np.asarray(chunk.dst),
            "valid": np.asarray(chunk.valid),
        }

    def _count_lanes(members: int, lanes: int) -> None:
        # The share of shipped fold lanes that carry a member: the rest
        # is bucket padding the device fold still gathers over.
        bus = get_bus()
        bus.inc("cc.fold_members", members)
        bus.inc("cc.fold_lanes", lanes)

    def _combine_pairs_idx(av: np.ndarray, ar: np.ndarray):
        """Merge a group's pairs into one forest, with each pair's root
        reported as its INDEX in the output (wire format of the star fold:
        the device resolves root labels by indexing its own chased array,
        saving a second pointer chase per pair)."""
        if native.sparse_idx_available():
            return native.cc_chunk_combine_sparse_idx(av, ar, None, n)
        v, r = cc_pairs_numpy(av, ar, None, n)
        return v, r, np.searchsorted(v, r).astype(np.int32)

    def stack_compact(payloads: list, groups: int = 1,
                      seq: int | None = None) -> dict:
        from ..engine.aggregation import bucket_stack_payloads

        # Stateless group combine first — concurrent stagers keep this
        # (the heavyweight step) parallel.
        size = -(-max(len(payloads), 1) // groups)
        combined = [
            _combine_pairs_idx(
                np.concatenate([q["v"] for q in payloads[i:i + size]]),
                np.concatenate([q["r"] for q in payloads[i:i + size]]),
            )
            for i in range(0, len(payloads), size)
        ]
        # Stateful cid assignment in STREAM order (see CompactIdSession:
        # a unit folded first must carry the first-seen records).
        if seq is not None:
            session.await_turn(seq)
        try:
            rows = []
            for v2, _, ri2 in combined:
                # Persistent cid assignment at pair rate; the root side
                # travels as a row index, so only ``v`` needs the mapping.
                cv, new_ids, base = session.assign(v2)
                rows.append({
                    "v": cv, "ri": ri2, "newv": new_ids,
                    "base": np.asarray(base, np.int32),
                })
            while len(rows) < groups:
                rows.append({
                    "v": np.empty(0, np.int32), "ri": np.empty(0, np.int32),
                    "newv": np.empty(0, np.int32),
                    "base": np.asarray(session.assigned, np.int32),
                })
        finally:
            if seq is not None:
                session.complete_turn(seq)
        # Quantum (not pow-2) buckets: the star fold's gather cost scales
        # with padded lanes, so at multi-M pair counts a pow-2 ladder
        # would waste up to 2x device work for compile-cache stability the
        # coarse quantum already provides. Both the quantum and the floor
        # cap at m: a row can never exceed the compact capacity, so
        # small-M plans must not pad to the large-M granule.
        out = bucket_stack_payloads(
            rows, {"v": -1, "ri": 0, "newv": -1},
            min_bucket=min(1024, m), quantum=min(1 << 18, m),
        )
        _count_lanes(sum(r["v"].shape[0] for r in rows), out["v"].size)
        return out

    def stack_segments(payloads: list, groups: int = 1,
                       seq: int | None = None) -> dict:
        from ..engine.aggregation import bucket_stack_payloads

        # Fused unit combine (stateless, heavy): ONE native call per
        # mesh-shard subgroup over the subgroup's concatenated raw edges
        # — dedup-blocked two-level union-find emitting root-first
        # segments in VERTEX space (cc_unit_forest_segments).
        size = -(-max(len(payloads), 1) // groups)
        combined = []
        for i in range(0, len(payloads), size):
            builder = native.UnitForestBuilder(n, block=unit_block)
            for p in payloads[i:i + size]:
                va = np.asarray(p["valid"])
                builder.add(
                    p["src"], p["dst"], None if bool(va.all()) else va
                )
            combined.append(builder.finish())
        # Stateful cid remap in STREAM order (one session probe pass per
        # member; order-preserving, so the segment structure carries
        # over to cid space unchanged).
        if seq is not None:
            session.await_turn(seq)
        try:
            rows = []
            for mv, ln in combined:
                cids, new_ids, base = session.assign(mv)
                rows.append({
                    "m": cids, "len": ln, "newv": new_ids,
                    "base": np.asarray(base, np.int32),
                })
            while len(rows) < groups:
                rows.append({
                    "m": np.empty(0, np.int32),
                    "len": np.empty(0, np.int32),
                    "newv": np.empty(0, np.int32),
                    "base": np.asarray(session.assigned, np.int32),
                })
        finally:
            if seq is not None:
                session.complete_turn(seq)
        # Per-key buckets: lengths (∝ components) and newv (∝ FRESH
        # vertices) run far below members (∝ touched vertices) — giving
        # each its own quantum ladder instead of the members' bucket was
        # measured as ~1/3 of the wire bytes at Twitter scale.
        out = bucket_stack_payloads(
            rows, {"m": -1, "len": 0, "newv": -1},
            min_bucket=min(1024, m), quantum=min(1 << 18, m),
            per_key={
                "len": (min(1024, m), min(1 << 13, m)),
                "newv": (min(1024, m), min(1 << 16, m)),
            },
        )
        _count_lanes(sum(r["m"].shape[0] for r in rows), out["m"].size)
        return out

    def _append_vertex_of(s: CCCompactSummary, payload) -> jax.Array:
        # Shared decode-table append: rows carry their own base, so
        # staging order never has to match fold order.
        newv = jnp.atleast_2d(payload["newv"])  # global slots of fresh cids
        base = payload["base"].reshape(-1)  # first cid of each fresh block
        k, cap = newv.shape
        pos = base[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
        okn = newv >= 0
        return s.vertex_of.at[
            jnp.where(okn, pos, m).reshape(-1)
        ].set(jnp.where(okn, newv, 0).reshape(-1), mode="drop")

    def fold_compressed(s: CCCompactSummary, payload) -> CCCompactSummary:
        # Leaves arrive [K, cap] from the engine's stacked dispatch, or
        # [cap] when a scan strips the batch axis (the device-bound bench).
        with jax.named_scope("cc.fold"):
            with jax.named_scope("cc.decode"):
                vertex_of = _append_vertex_of(s, payload)
            v = jnp.atleast_2d(payload["v"])
            ri = jnp.atleast_2d(payload["ri"])  # row-local root indices
            kb, capb = v.shape
            ri_flat = (
                ri + capb * jnp.arange(kb, dtype=jnp.int32)[:, None]
            ).reshape(-1)
            v = v.reshape(-1)
            croot = unionfind.union_pairs_star(s.croot, v, ri_flat, v >= 0)
        return CCCompactSummary(croot, vertex_of)

    def fold_segments(s: CCCompactSummary, payload) -> CCCompactSummary:
        # Segment wire: members [K, capm] grouped by component, each
        # component's root FIRST in its segment; lengths [K, capr]. The
        # root-row index of every member lane is its segment START —
        # derived on device by a scatter of segment ends and a running
        # sum along the lanes, replacing the shipped per-pair ri (half
        # the pair bytes on the H2D link).
        with jax.named_scope("cc.fold"):
            with jax.named_scope("cc.decode"):
                vertex_of = _append_vertex_of(s, payload)
            mm = jnp.atleast_2d(payload["m"])
            ln = jnp.atleast_2d(payload["len"])
            kb, capm = mm.shape
            with jax.named_scope("cc.segments"):
                ri, valid = lane_segment_starts(ln, capm)
                ri_flat = (
                    ri + capm * jnp.arange(kb, dtype=jnp.int32)[:, None]
                ).reshape(-1)
            croot = unionfind.union_pairs_star(
                s.croot, mm.reshape(-1), ri_flat, valid.reshape(-1)
            )
        return CCCompactSummary(croot, vertex_of)

    def combine(a: CCCompactSummary, b: CCCompactSummary) -> CCCompactSummary:
        return CCCompactSummary(
            croot=unionfind.merge_forests(a.croot, b.croot),
            # Each cid's vertex is recorded by exactly one payload row;
            # -1 elsewhere, so elementwise max merges the decode tables.
            vertex_of=jnp.maximum(a.vertex_of, b.vertex_of),
        )

    def merge_stacked(st: CCCompactSummary) -> CCCompactSummary:
        return CCCompactSummary(
            croot=unionfind.merge_forest_stack(st.croot),
            vertex_of=jnp.max(st.vertex_of, axis=0),
        )

    def merge_dirty_count(local: CCCompactSummary) -> jax.Array:
        # A window's locals touch a cid either by assigning its decode
        # entry (fresh cids: vertex_of >= 0) or by hooking its root (cids
        # from earlier windows: croot moved off the identity).
        dirty = (local.vertex_of >= 0) | (
            local.croot != jnp.arange(m, dtype=jnp.int32)
        )
        return jnp.sum(dirty.astype(jnp.int32))

    def merge_delta(base: CCCompactSummary, local: CCCompactSummary,
                    bucket: int) -> CCCompactSummary:
        # Dirty-delta mesh merge in cid space: gather (cid, croot,
        # vertex_of) rows for the window's touched cids only. croot rows
        # are union edges (same argument as the CCSummary delta); each
        # cid's vertex is recorded by exactly one row globally, so the
        # max-scatter reproduces the elementwise-max decode-table merge.
        from ..parallel import collectives

        dirty = (local.vertex_of >= 0) | (
            local.croot != jnp.arange(m, dtype=jnp.int32)
        )
        slots, vals, _ = collectives.compact_delta(
            dirty, {"r": local.croot, "v": local.vertex_of}, bucket
        )
        gs, gv = collectives.gather_delta(slots, vals)
        ok = gs >= 0
        si = jnp.where(ok, gs, 0)
        ri = jnp.where(ok, gv["r"], 0)
        # Rows-proportional apply (see _cc_merge_delta): no full-capacity
        # flatten; transform's pointer_jump chases through the depth.
        croot = unionfind.union_pairs_rooted(base.croot, si, ri, ok)
        vertex_of = base.vertex_of.at[jnp.where(ok, gs, m)].max(
            jnp.where(ok, gv["v"], -1), mode="drop"
        )
        return CCCompactSummary(croot, vertex_of)

    def transform(s: CCCompactSummary) -> jax.Array:
        # The ONLY full-capacity op in the plan: materialize i32[n] labels
        # once per window close.
        with jax.named_scope("cc.close"):
            with jax.named_scope("cc.close.jump"):
                root = unionfind.pointer_jump(s.croot)
            with jax.named_scope("cc.close.canon"):
                ok = s.vertex_of >= 0
                canon = jnp.full((m,), segments.INT_MAX, jnp.int32).at[
                    jnp.where(ok, root, m)
                ].min(jnp.where(ok, s.vertex_of, segments.INT_MAX),
                      mode="drop")
            with jax.named_scope("cc.close.labels"):
                lab_c = canon[root]
                return jnp.full((n,), -1, jnp.int32).at[
                    jnp.where(ok, s.vertex_of, n)
                ].set(jnp.where(ok, lab_c, -1), mode="drop")

    def flatten(s: CCCompactSummary) -> CCCompactSummary:
        # Cadenced path flatten: the star/rooted pair folds skip the
        # global flatten per dispatch (their documented contract), so
        # croot chase depth grows on long streams; one pointer_jump at
        # checkpoint cadence bounds it. vertex_of is depth-free.
        return CCCompactSummary(
            unionfind.pointer_jump(s.croot), s.vertex_of
        )

    if windowed is not None:
        return _windowed_compact_variant(
            windowed, ttl_panes, m, n, session,
            init=init, fold=fold, combine=combine, transform=transform,
            merge_stacked=merge_stacked if merge == "gather" else None,
            host_compress=(
                host_compress_raw if use_segments else host_compress
            ),
            fold_compressed=(
                fold_segments if use_segments else fold_compressed
            ),
            stack_payloads=(
                stack_segments if use_segments else stack_compact
            ),
            member_key="m" if use_segments else "v",
        )
    if ttl_panes is not None:
        raise ValueError(
            "ttl_panes requires windowed=W (TTL stamps are last-seen "
            "PANE indices; there is no pane clock without a ring)"
        )
    agg = SummaryAggregation(
        init=init,
        fold=fold,
        combine=combine,
        transform=transform,
        merge_stacked=merge_stacked if merge == "gather" else None,
        transient=False,
        host_compress=host_compress_raw if use_segments else host_compress,
        fold_compressed=fold_segments if use_segments else fold_compressed,
        stack_payloads=stack_segments if use_segments else stack_compact,
        fold_accumulates=True,
        flatten=flatten,
        requires_codec=True,
        stack_ordered=True,
        on_stage_error=session.complete_turn,
        on_run_start=session.reset,
        ordered_wait_s=lambda: session.wait_s,
        on_resume=lambda summary: session.rebuild_from_vertex_of(
            np.asarray(summary.vertex_of)
        ),
        merge_mode=resolve_merge_mode(merge_mode),
        merge_delta=merge_delta,
        merge_dirty_count=merge_dirty_count,
        merge_delta_auto_rows=(
            m // 4 if delta_auto_rows is None else int(delta_auto_rows)
        ),
        name="connected-components-compact",
    )
    agg.session = session
    agg.compact_capacity = m
    return agg


def _windowed_compact_variant(
    windowed: int, ttl_panes: int | None, m: int, n: int, session,
    *, init, fold, combine, transform, merge_stacked, host_compress,
    fold_compressed, stack_payloads, member_key: str,
) -> SummaryAggregation:
    """Assemble the pane-ring compact plan: wrap the base compact fold /
    combine / transform in :class:`CCWindowPane` (an exact touched-cid
    mask rides every pane) and attach the engine's windowed hooks.

    The touched mask is recorded from the WIRE payload's member lanes
    (``v`` on the pairs wire, ``m`` on the segments wire; padding lanes
    are -1), not inferred from the forest — a self-loop-only vertex
    never moves ``croot`` off the identity, yet it IS in the window.

    ``windowed_evict`` (the TTL hook): survivors are renumbered
    order-preserving onto a dense cid prefix, every live pane's leaves
    are gathered through the renumbering, and the session is rebuilt
    from the compacted persistent map — so ``session.assigned`` drops
    back to the live-slot count and the freed capacity is reusable.
    Sound because T >= W (engine-enforced): an evicted cid is untouched
    in every live pane, so its rows are identity/-1/False everywhere
    and no surviving cid's ``croot`` can point at it (a union would
    have stamped it touched).
    """
    if windowed < 1:
        raise ValueError(f"windowed must be >= 1 pane, got {windowed}")
    if ttl_panes is not None and ttl_panes < windowed:
        raise ValueError(
            f"ttl_panes={ttl_panes} < windowed={windowed}: a slot must "
            "outlive the ring (T >= W) so eviction never rewrites a "
            "pane that still references it"
        )

    def init_pane() -> CCWindowPane:
        s = init()
        return CCWindowPane(s.croot, s.vertex_of, jnp.zeros((m,), bool))

    def fold_pane(s: CCWindowPane, payload) -> CCWindowPane:
        base = fold_compressed(
            CCCompactSummary(s.croot, s.vertex_of), payload
        )
        mem = jnp.atleast_2d(payload[member_key]).reshape(-1)
        touched = s.touched.at[jnp.where(mem >= 0, mem, m)].set(
            True, mode="drop"
        )
        return CCWindowPane(base.croot, base.vertex_of, touched)

    def combine_pane(a: CCWindowPane, b: CCWindowPane) -> CCWindowPane:
        c = combine(
            CCCompactSummary(a.croot, a.vertex_of),
            CCCompactSummary(b.croot, b.vertex_of),
        )
        return CCWindowPane(c.croot, c.vertex_of, a.touched | b.touched)

    def merge_stacked_pane(st: CCWindowPane) -> CCWindowPane:
        c = merge_stacked(CCCompactSummary(st.croot, st.vertex_of))
        return CCWindowPane(
            c.croot, c.vertex_of, jnp.any(st.touched, axis=0)
        )

    def transform_pane(s: CCWindowPane) -> jax.Array:
        # Same shape as the base transform, with the WINDOW-membership
        # predicate: labels cover touched cids only (the engine
        # substitutes the persistent vertex_of before this runs, so
        # every touched cid decodes).
        root = unionfind.pointer_jump(s.croot)
        ok = s.touched & (s.vertex_of >= 0)
        canon = jnp.full((m,), segments.INT_MAX, jnp.int32).at[
            jnp.where(ok, root, m)
        ].min(jnp.where(ok, s.vertex_of, segments.INT_MAX), mode="drop")
        lab_c = canon[root]
        return jnp.full((n,), -1, jnp.int32).at[
            jnp.where(ok, s.vertex_of, n)
        ].set(jnp.where(ok, lab_c, -1), mode="drop")

    def flatten_pane(s: CCWindowPane) -> CCWindowPane:
        return CCWindowPane(
            unionfind.pointer_jump(s.croot), s.vertex_of, s.touched
        )

    def windowed_evict(panes, persist, stale):
        # Host-side, called by the engine at a pane boundary with the
        # pipeline quiesced (prefetch_depth=0 / h2d_depth=0 — no
        # staged-but-unfolded payloads carry the old cids).
        assigned = session.assigned
        surv = np.flatnonzero(~np.asarray(stale)[:assigned])
        k = surv.shape[0]
        perm = np.full((m,), -1, np.int32)
        perm[surv] = np.arange(k, dtype=np.int32)
        out = []
        for p in panes:
            croot = np.arange(m, dtype=np.int32)
            croot[:k] = perm[np.asarray(p.croot)[surv]]
            vof = np.full((m,), -1, np.int32)
            vof[:k] = np.asarray(p.vertex_of)[surv]
            tch = np.zeros((m,), bool)
            tch[:k] = np.asarray(p.touched)[surv]
            out.append(CCWindowPane(croot, vof, tch))
        p2 = np.full((m,), -1, np.int32)
        p2[:k] = np.asarray(persist)[surv]
        session.rebuild_from_vertex_of(p2)
        return out, p2, surv

    agg = SummaryAggregation(
        init=init_pane,
        fold=fold,
        combine=combine_pane,
        transform=transform_pane,
        merge_stacked=(
            merge_stacked_pane if merge_stacked is not None else None
        ),
        transient=False,
        host_compress=host_compress,
        fold_compressed=fold_pane,
        stack_payloads=stack_payloads,
        fold_accumulates=True,
        flatten=flatten_pane,
        requires_codec=True,
        stack_ordered=True,
        on_stage_error=session.complete_turn,
        on_run_start=session.reset,
        ordered_wait_s=lambda: session.wait_s,
        merge_mode="replicated",
        name="connected-components-compact-windowed",
    )
    agg.session = session
    agg.compact_capacity = m
    agg.windowed_panes = int(windowed)
    if ttl_panes is not None:
        agg.windowed_ttl_panes = int(ttl_panes)
    agg.windowed_persist_init = lambda: jnp.full((m,), -1, jnp.int32)
    agg.windowed_persist_update = jax.jit(
        lambda p, pane: jnp.maximum(p, pane.vertex_of)
    )
    agg.windowed_query_fixup = lambda q, persist: q._replace(
        vertex_of=persist
    )
    agg.windowed_touched = lambda pane: pane.touched
    agg.windowed_evict = windowed_evict
    agg.on_resume_windowed = lambda persist: session.rebuild_from_vertex_of(
        np.asarray(persist)
    )
    return agg


def resolve_merge_mode(merge_mode: str) -> str:
    """Shared ``merge_mode=`` knob semantics for the cross-shard window
    merge: validate ``"auto"``/``"delta"``/``"replicated"``.

    - ``"replicated"`` — the full-summary merge (butterfly / hierarchical
      tree / gather+stacked union): cost ∝ capacity per window, the
      BENCH_r05 ``sharded_state_cc`` wall (0.58s → 32.2s from 1M → 16M
      slots at a fixed pair count).
    - ``"delta"`` — all_gather only the dirty ``(slot, parent)`` entries
      the window's folds marked and union them into the carried global
      summary: merge cost ∝ hooks-since-last-merge.
    - ``"auto"`` — per-window measured decision: the engine counts the
      dirty entries (one scalar D2H per window close) and takes the delta
      path while the gathered rows stay under the plan's
      ``merge_delta_auto_rows`` bound, falling back to the replicated
      merge (the plan's configured tree — hierarchical when
      ``merge_degree`` is set) on dense windows.
    """
    if merge_mode not in ("auto", "delta", "replicated"):
        raise ValueError(
            f"merge_mode must be auto/delta/replicated, got {merge_mode!r}"
        )
    return merge_mode


def _cc_merge_delta(n: int):
    """Build the CCSummary dirty-delta merge (runs per-shard inside
    ``shard_map``): compact this shard's touched ``(slot, parent)``
    entries, all_gather every shard's rows, and union them into the
    replicated base summary. Exact: a fresh-forest local summary IS its
    edge set ``{(i, parent[i])}`` plus the seen marks, so applying the
    gathered pairs to the base is the same merge ``merge_forest_stack``
    computes — minus the ``S × capacity`` traffic."""
    from ..parallel import collectives

    def merge_dirty_count(local: CCSummary) -> jax.Array:
        dirty = local.seen | (
            local.parent != jnp.arange(n, dtype=jnp.int32)
        )
        return jnp.sum(dirty.astype(jnp.int32))

    def merge_delta(base: CCSummary, local: CCSummary,
                    bucket: int) -> CCSummary:
        dirty = local.seen | (
            local.parent != jnp.arange(n, dtype=jnp.int32)
        )
        slots, vals, _ = collectives.compact_delta(
            dirty, local.parent, bucket
        )
        gs, gv = collectives.gather_delta(slots, vals)
        ok = gs >= 0
        si = jnp.where(ok, gs, 0)
        vi = jnp.where(ok, gv, 0)
        # union_pairs_rooted: EVERY per-round op is sized to the gathered
        # rows (pair-sized chases + one scatter-min), and no full-capacity
        # flatten — the whole point of the delta merge. Depth grows O(1)
        # per window; the transform's label chase and later merges chase
        # through it (their documented contract).
        parent = unionfind.union_pairs_rooted(base.parent, si, vi, ok)
        seen = base.seen.at[jnp.where(ok, gs, n)].set(True, mode="drop")
        return CCSummary(parent, seen)

    return merge_delta, merge_dirty_count


def resolve_fold_backend(fold_backend: str, vertex_capacity: int) -> str:
    """Shared ``fold_backend=`` knob semantics: validate and resolve
    ``"auto"``/``"xla"``/``"pallas"`` for the raw device fold.

    ``"auto"`` resolves to ``"xla"``: the Pallas path's profitability is
    hardware-dependent (it trades MXU flops for HBM random-touch latency;
    see the bench's ``gather_study`` block), so the measured sweep — not
    a heuristic — should flip the default. ``"pallas"`` validates the
    capacity against the kernel's window-blocking requirements up front,
    at plan-build time, instead of failing mid-stream.
    """
    if fold_backend not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"fold_backend must be auto/xla/pallas, got {fold_backend!r}"
        )
    if fold_backend == "pallas":
        from ..ops.pallas_kernels import gatherable

        if not gatherable(vertex_capacity):
            raise ValueError(
                f"fold_backend='pallas' needs a window-blockable vertex "
                f"capacity (multiple of 128 lanes spanning >= 2 windows, "
                f"<= 2^24); got {vertex_capacity}"
            )
        return "pallas"
    return "xla"


def cc_tenant_tier(
    vertex_capacity: int, chunk_capacity: int = 1 << 10,
    fold_backend: str = "auto", delta_auto_rows: int | None = None,
    compressed: bool = False, codec: str = "auto",
) -> tuple[SummaryAggregation, int]:
    """Build a CC plan suitable for one multi-tenant capacity tier
    (``engine/tenants.py``) — returns ``(agg, chunk_capacity)`` for
    ``MultiTenantEngine.add_tier``.

    ``compressed=False`` (default) builds the raw-fold tier: the
    stacked batch vmaps ``fold`` over raw per-tenant chunks.
    ``compressed=True`` keeps the stateless ingest codec ON, for a
    ``add_tier(..., compressed=True)`` tier whose lanes fold
    PRE-COMPRESSED payloads (compressed once at the producer — the
    submitter or a wire client; ``codec`` picks the payload format,
    ``"sparse"`` being the wire-win shape). The stateful compact-id
    codec (``codec="compact"``) stays unusable either way: its
    id-assignment session consumes payloads in global stream order,
    which concurrent tenant lanes cannot provide. ``vertex_capacity``
    is the tier's capacity class: all tenants of the tier share one
    compiled program per lane width, so admit tenants into the
    smallest tier whose capacity covers them.
    """
    agg = connected_components(
        vertex_capacity, merge="gather", ingest_combine=compressed,
        codec=codec,
        fold_backend=fold_backend, delta_auto_rows=delta_auto_rows,
    )
    return agg, int(chunk_capacity)


def connected_components(
    vertex_capacity: int, merge: str = "tree", ingest_combine: bool = True,
    codec: str = "auto", compact_capacity: int | None = None,
    fold_backend: str = "auto", merge_mode: str = "auto",
    delta_auto_rows: int | None = None,
    windowed: int | None = None, ttl_panes: int | None = None,
) -> SummaryAggregation:
    """Build the CC aggregation over a slot space of ``vertex_capacity``.

    ``merge="tree"`` → butterfly merge-tree (ConnectedComponentsTree);
    ``merge="gather"`` → all_gather + stacked union (flat bulk aggregation).

    ``ingest_combine`` (default on) attaches the ingest codec: each chunk is
    pre-reduced on the host to its spanning forest (the reference's
    per-partition partial fold, M/SummaryBulkAggregation.java:76-80, moved
    to the ingest side). The device then unions the (vertex, root) star
    edges, preserving connectivity exactly — 1-2 orders of magnitude fewer
    H2D bytes per edge.

    ``codec`` picks the payload wire format:

    - ``"dense"`` — i32[n_v] label array per chunk. Optimal when the slot
      space is small relative to chunk size (payload is a fixed n_v*4
      bytes and the device fold is a fixed-shape star union).
    - ``"sparse"`` — counted (vertex, root) pairs, bucket-padded per batch
      (:func:`~gelly_tpu.engine.aggregation.bucket_stack_payloads`).
      Payload ∝ touched vertices — required at Twitter-class n_v, where a
      dense payload (e.g. 64 MB at n_v = 2^24) would invert the codec's
      compression. Host combine cost is O(chunk), not O(n_v), matching
      the reference's touched-keys-proportional partial fold
      (M/SummaryBulkAggregation.java:109-130).
    - ``"compact"`` — persistent compact root space
      (:func:`connected_components_compact`): the host codec assigns
      window-scoped compact ids and the device folds in an M-slot space,
      with zero per-dispatch O(capacity) work. The large-N throughput
      plan; requires the ingest codec (no raw-chunk/window_ms fold).
    - ``"auto"`` (default) — sparse iff ``vertex_capacity >=``
      :data:`SPARSE_CODEC_MIN_CAPACITY` (2^20).

    ``merge_mode`` picks the cross-shard window merge
    (:func:`resolve_merge_mode`): ``"delta"`` gathers only the window's
    dirty ``(slot, parent)`` entries (merge ∝ hooks, not capacity),
    ``"replicated"`` keeps the full-summary merge, ``"auto"`` (default)
    measures the dirty count each window close and picks per window.
    Like ``fold_backend``, the engine's compiled-plan cache keys on it.

    ``delta_auto_rows`` overrides the ``"auto"`` crossover bound (max
    gathered delta rows before the replicated merge wins). Default is
    the ``capacity / 4`` structural guess; the bench's
    ``merge_delta_crossover`` block measures the real crossover per
    chip against the ``engine.window_dirty_rows`` gauge — pass the
    calibrated value here (``BENCH_tenants_r01.json`` records one for
    the CPU mesh).

    ``fold_backend`` picks the RAW device fold's kernel backend
    (:func:`resolve_fold_backend`): ``"pallas"`` routes the large-chunk
    sort-dedup fold's sorted chases through the VMEM-blocked gather
    kernel (:func:`~gelly_tpu.ops.pallas_kernels.sorted_window_gather`,
    exact — window misses fall through to the exact tail fixpoint);
    ``"auto"`` stays on XLA until the recorded bench sweep says
    otherwise. The codec plans' device folds are pair/star folds that
    never run the raw dedup kernel, so the knob only shapes the
    codec-off fold path (window mode, ``ingest_combine=False``, and the
    device-bound bench).

    ``windowed=W`` marks the plan for the engine's sliding pane ring
    (``run_aggregation(windowed=...)``): emissions cover the last W
    merge windows instead of the whole stream, at O(1) amortized
    combines per pane close. Forces ``merge_mode="replicated"`` (the
    dirty-delta merge folds into a carried global — incompatible with
    pane retirement). ``ttl_panes=T`` (per-vertex decay) additionally
    needs ``codec="compact"`` — only the compact-id session has an
    eviction hook.
    """
    from ..engine.aggregation import resolve_sparse_codec

    if codec == "compact":
        if not ingest_combine:
            raise ValueError("codec='compact' requires ingest_combine=True")
        return connected_components_compact(
            vertex_capacity, merge=merge, compact_capacity=compact_capacity,
            merge_mode=merge_mode, delta_auto_rows=delta_auto_rows,
            windowed=windowed, ttl_panes=ttl_panes,
        )
    if ttl_panes is not None:
        raise ValueError(
            "ttl_panes needs the compact-id plan (codec='compact'): "
            "per-vertex decay evicts through the CompactIdSession "
            "rebuild hook, which dense/sparse plans have no analog of"
        )
    if windowed is not None:
        if int(windowed) < 1:
            raise ValueError(
                f"windowed must be >= 1 pane, got {windowed}"
            )
        # A pane ring retires panes, so the dirty-delta merge (which
        # folds into a CARRIED global summary) cannot engage — the
        # windowed variant is replicated-merge only, and CCSummary
        # needs no other change: `seen` already gives the window-
        # membership predicate once panes fold from fresh locals.
        merge_mode = "replicated"
    n = vertex_capacity
    sparse = resolve_sparse_codec(codec, n)
    backend = resolve_fold_backend(fold_backend, n)
    mode = resolve_merge_mode(merge_mode)
    # Static per-plan choice: jit specializes the fold on it, and the
    # engine's compiled-plan cache keys on agg.fold_backend.
    interp = None if backend == "xla" else pallas_interpret()

    def init() -> CCSummary:
        return CCSummary(
            parent=unionfind.fresh_forest(n), seen=jnp.zeros((n,), bool)
        )

    def fold(s: CCSummary, chunk) -> CCSummary:
        if chunk.capacity >= RAW_DEDUP_MIN_CHUNK:
            # Large-chunk raw path: sort-dedup + verified hook rounds +
            # compacted exact tail (union_edges_dedup) — ~10x the generic
            # fixpoint at Twitter-scale capacity (its O(capacity) random
            # doubling per round was the measured cost). Caps are perf
            # knobs only; overflow falls back to the exact fixpoint.
            parent = unionfind.union_edges_dedup(
                s.parent, chunk.src, chunk.dst, chunk.valid,
                # 3/16 of the chunk covers the distinct-pair counts of
                # power-law streams with ~1.4x margin (2^25-edge Zipf
                # chunks measure ~13% distinct); fixpoint op cost scales
                # with this cap, and overflow only costs speed (exact
                # full-width fallback), never correctness.
                unique_cap=max(1 << 20, 3 * (chunk.capacity >> 4)),
                backend=backend, interpret=interp,
            )
        else:
            parent = unionfind.union_edges(
                s.parent, chunk.src, chunk.dst, chunk.valid
            )
        seen = segments.mark_seen(s.seen, chunk.src, chunk.valid)
        seen = segments.mark_seen(seen, chunk.dst, chunk.valid)
        return CCSummary(parent, seen)

    def host_compress(chunk) -> np.ndarray:
        if _native_ok():
            from ..utils.native import cc_chunk_combine

            return cc_chunk_combine(
                np.asarray(chunk.src), np.asarray(chunk.dst),
                np.asarray(chunk.valid), n,
            )
        return cc_labels_numpy(chunk.src, chunk.dst, chunk.valid, n)

    def fold_compressed(s: CCSummary, labels: jax.Array) -> CCSummary:
        # labels: i32[K, n] — K chunk forests. Every (v, labels[k, v] >= 0)
        # pair is a union edge; one joint fixpoint unions all K at once
        # (cheaper than K sequential fixpoints — the star edges from
        # different chunks hook through each other in the same rounds).
        k = labels.shape[0]
        present = jnp.any(labels >= 0, axis=0)
        v = jnp.broadcast_to(
            jnp.arange(n, dtype=jnp.int32), (k, n)
        ).reshape(-1)
        lab = labels.reshape(-1)
        ok = lab >= 0
        parent = unionfind.union_edges(
            s.parent, v, jnp.where(ok, lab, 0).astype(jnp.int32), ok
        )
        return CCSummary(parent, s.seen | present)

    def host_compress_sparse(chunk) -> dict:
        from ..utils import native

        if native.sparse_codecs_available():
            v, r = native.cc_chunk_combine_sparse(
                np.asarray(chunk.src), np.asarray(chunk.dst),
                np.asarray(chunk.valid), n,
            )
        else:
            v, r = cc_pairs_numpy(chunk.src, chunk.dst, chunk.valid, n)
        return {"v": v, "r": r}

    def _combine_pairs(av: np.ndarray, ar: np.ndarray):
        # Pairs are union edges: one more sparse-combiner pass merges a
        # whole group's chunk forests into one (the SummaryTreeReduce
        # partial-merge level run on the ingest side).
        from ..utils import native

        if native.sparse_codecs_available():
            return native.cc_chunk_combine_sparse(av, ar, None, n)
        return cc_pairs_numpy(av, ar, None, n)

    def stack_sparse(payloads: list, groups: int = 1) -> dict:
        from ..engine.aggregation import (
            bucket_stack_payloads,
            group_combine_payloads,
        )

        payloads = group_combine_payloads(
            payloads, groups,
            lambda grp: dict(zip(("v", "r"), _combine_pairs(
                np.concatenate([q["v"] for q in grp]),
                np.concatenate([q["r"] for q in grp]),
            ))),
            {"v": np.empty(0, np.int32), "r": np.empty(0, np.int32)},
        )
        return bucket_stack_payloads(payloads, {"v": -1, "r": 0})

    def fold_compressed_sparse(s: CCSummary, payload) -> CCSummary:
        # payload: {"v": i32[K, cap], "r": i32[K, cap]} — K chunks' counted
        # (vertex, root) pairs, -1-padded. The pairs are union edges; one
        # joint fixpoint unions all K chunks at once, in a compacted root
        # space (touched slots << vertex_capacity is exactly the sparse
        # codec's regime — union_pairs_compact keeps per-round work ∝
        # pairs, not capacity).
        v = payload["v"].reshape(-1)
        r = payload["r"].reshape(-1)
        ok = v >= 0
        vi = jnp.where(ok, v, 0)
        if 4 * v.size <= n:
            # Compacted-root-space union: per-round work ∝ pairs. Only a
            # win while the 2L local space is comfortably below the
            # capacity the generic fixpoint would walk per round (shapes
            # are static, so this resolves at trace time).
            parent = unionfind.union_pairs_compact(s.parent, vi, r, ok)
        else:
            parent = unionfind.union_edges(s.parent, vi, r, ok)
        seen = segments.mark_seen(s.seen, vi, ok)
        return CCSummary(parent, seen)

    def combine(a: CCSummary, b: CCSummary) -> CCSummary:
        return CCSummary(
            parent=unionfind.merge_forests(a.parent, b.parent),
            seen=a.seen | b.seen,
        )

    def merge_stacked(st: CCSummary) -> CCSummary:
        return CCSummary(
            parent=unionfind.merge_forest_stack(st.parent),
            seen=jnp.any(st.seen, axis=0),
        )

    def transform(s: CCSummary) -> jax.Array:
        return unionfind.component_labels(s.parent, s.seen)

    def flatten(s: CCSummary) -> CCSummary:
        # Cadenced path flatten (engine runs it at checkpoint cadence):
        # the delta merge's union_pairs_rooted grows chase depth O(1)
        # per window; one full pointer_jump here keeps depth <= 1 across
        # arbitrarily long streams. Labels are unchanged — pointer_jump
        # only shortcuts chains to the same roots.
        return CCSummary(unionfind.pointer_jump(s.parent), s.seen)

    _mk_delta, _mk_count = _cc_merge_delta(n)
    if windowed is not None:
        _mk_delta = _mk_count = None

    agg = SummaryAggregation(
        init=init,
        fold=fold,
        combine=combine,
        transform=transform,
        merge_stacked=merge_stacked if merge == "gather" else None,
        transient=False,
        host_compress=(
            (host_compress_sparse if sparse else host_compress)
            if ingest_combine else None
        ),
        fold_compressed=(
            (fold_compressed_sparse if sparse else fold_compressed)
            if ingest_combine else None
        ),
        stack_payloads=(
            stack_sparse if (ingest_combine and sparse) else None
        ),
        # Wire pad values of the sparse pair payload (consumers that
        # stack per-chunk payloads themselves — the tenant engine's
        # compressed tiers — pad with these; -1 lanes fold as no-ops),
        # and the producer-payload id range check (wire-ingest parity:
        # out-of-range ids raise at staging, never silently clamp).
        codec_pad_values=(
            {"v": -1, "r": 0} if (ingest_combine and sparse) else None
        ),
        codec_payload_check=(
            sparse_payload_id_check(n, "v", "r")
            if (ingest_combine and sparse) else None
        ),
        fold_accumulates=True,  # CC forests are pure edge-set summaries
        flatten=flatten,
        fold_backend=backend,
        merge_mode=mode,
        merge_delta=_mk_delta,
        merge_dirty_count=_mk_count,
        # Auto threshold: delta rows cost ~8 bytes each on the wire +
        # pair-rate union work; past capacity/4 gathered rows the full
        # replicated merge's sequential-scan unions win. The bench's
        # merge_delta_crossover block measures the real bound per chip;
        # delta_auto_rows carries the calibrated value in.
        merge_delta_auto_rows=(
            None if windowed is not None
            else n // 4 if delta_auto_rows is None
            else int(delta_auto_rows)
        ),
        name=f"connected-components-{merge}",
    )
    if windowed is not None:
        agg.windowed_panes = int(windowed)
    return agg


def cc_query(vertex_capacity: int, *, name: str = "cc",
             merge: str = "gather", fold_backend: str = "auto",
             compressed: bool = False, codec: str = "auto"):
    """Fuse-compatible CC query (``engine.multiquery.fuse``), tagged
    with this plan's slot capacity so ``fuse`` can refuse mismatched
    chunk schemas.

    ``compressed=False`` (default) builds the raw fold
    (``ingest_combine=False``): the fused pipeline stages each chunk
    exactly once for every query, and per-query codecs never engage.
    ``compressed=True`` keeps the ingest codec ON — when EVERY query
    of a fused set does, the fused plan's shared compress stage emits
    one multi-query compressed payload per chunk and the folds run
    through ``fold_compressed`` (the codec's ~0.25 B/edge wire win,
    recovered for fused runs). ``codec`` picks the payload format as
    in :func:`connected_components` (``"compact"`` is stack-ordered
    and un-fusable)."""
    from ..engine.multiquery import QuerySpec

    return QuerySpec(
        name=name,
        agg=connected_components(vertex_capacity, merge=merge,
                                 ingest_combine=compressed,
                                 codec=codec,
                                 fold_backend=fold_backend),
        slot_capacity=vertex_capacity,
    )


def connected_components_tree(vertex_capacity: int,
                              degree: int | None = None) -> SummaryAggregation:
    """ConnectedComponentsTree parity alias (merge-tree combine).

    ``degree`` is the SummaryTreeReduce partial-parallelism knob
    (ConnectedComponentsTree.java:28-34 passing through to
    SummaryTreeReduce.java:75): the cross-shard merge runs as a two-phase
    hierarchical tree with ``degree`` group summaries after phase 1."""
    agg = connected_components(vertex_capacity, merge="tree")
    agg.merge_degree = degree
    return agg


def cc_host_precombine(chunk):
    """Host pre-combiner: reduce a chunk to its spanning forest.

    Runs on the ingest/prefetch thread (vectorized numpy min-label
    propagation over the chunk's unique vertices) and replaces the chunk's
    edges with (vertex, chunk-local-root) pairs — connectivity-equivalent,
    but near-tree-shaped, so the device union-find fold converges in far
    fewer hook rounds. This is the reference's partial pre-aggregation
    before the global merge (SummaryBulkAggregation's per-partition fold,
    M/SummaryBulkAggregation.java:76-80) relocated to the host side of the
    ingest pipeline, overlapping device folds of earlier chunks.
    """
    m = np.asarray(chunk.valid)
    s = np.asarray(chunk.src)[m]
    d = np.asarray(chunk.dst)[m]
    if s.size == 0:
        return chunk
    ids = np.unique(np.concatenate([s, d]))
    ls = np.searchsorted(ids, s).astype(np.int64)
    ld = np.searchsorted(ids, d).astype(np.int64)
    lab = np.arange(ids.shape[0], dtype=np.int64)
    while True:
        prev = lab
        mn = np.minimum(lab[ls], lab[ld])
        lab = lab.copy()
        np.minimum.at(lab, ls, mn)
        np.minimum.at(lab, ld, mn)
        lab = np.minimum(lab, lab[lab])
        if np.array_equal(lab, prev):
            break
    # (v, root) pairs for every unique vertex: unions are connectivity-
    # equivalent to the original edges, and self-pairs keep roots "seen".
    n_out = ids.shape[0]
    cap = chunk.capacity
    src2 = np.zeros((cap,), np.int32)
    dst2 = np.zeros((cap,), np.int32)
    valid2 = np.zeros((cap,), bool)
    src2[:n_out] = ids
    dst2[:n_out] = ids[lab]
    valid2[:n_out] = True
    return chunk._replace(
        src=src2, dst=dst2,
        raw_src=np.zeros((cap,), np.int64),
        raw_dst=np.zeros((cap,), np.int64),
        valid=valid2,
    )


def labels_to_components(labels, ctx) -> list[list[int]]:
    """Decode a label array into sorted component lists of raw vertex ids —
    the structured replacement for the reference's DisjointSet.toString()
    parsing oracle (ConnectedComponentsTest.parser, :65-81)."""
    lab = np.asarray(labels)
    slots = np.nonzero(lab >= 0)[0]
    raw = ctx.decode(slots)
    comps: dict[int, list[int]] = {}
    for slot, rid in zip(slots.tolist(), raw.tolist()):
        comps.setdefault(int(lab[slot]), []).append(rid)
    return sorted(sorted(c) for c in comps.values())
