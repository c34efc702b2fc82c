"""Fully-dynamic degree distribution (additions + deletions).

TPU-native re-design of ``M/example/DegreeDistribution.java:42-193``, the
reference's only fully-dynamic pipeline: ±1 per endpoint per event
(``EmitVerticesWithChange``, ``:70-79``), per-vertex running degrees with
zero-degree removal (``VertexDegreeCounts``, ``:84-111``), then a
degree→vertex-count map (``DegreeDistributionMap``, ``:116-132``). Here the
keyed hash-map stages collapse into one jitted step per chunk: a ±1 scatter
into the dense degree array and a histogram rebuild over live vertices —
emission is chunk-grained with identical final state (the ITCase's
deletion-to-zero case is covered by the tests).
"""

from __future__ import annotations

from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.chunk import EdgeChunk
from ..obs.bus import get_bus
from ..ops import segments
from ..parallel import mesh as mesh_lib, partition
from ..parallel.mesh import SHARD_AXIS


def degree_aggregate(vertex_capacity: int, count_out: bool = True,
                     count_in: bool = True, ingest_combine: bool = True,
                     codec: str = "auto", windowed: int | None = None):
    """Continuous degree aggregate as a SummaryAggregation — the engine
    form of ``getDegrees`` (SimpleEdgeStream.java:413-478, BASELINE
    workload #1): summary = dense degree vector, fold = ±1 endpoint
    scatter, combine = elementwise add.

    ``ingest_combine`` attaches the degree codec: each chunk pre-reduces on
    the host to its net degree deltas, shipping those instead of the
    chunk's edges; the device fold is a vector add / scatter-add. Same H2D
    rationale as the CC codec.

    ``codec``: ``"dense"`` (i32[n_v] delta vector per chunk — optimal at
    small n_v) / ``"sparse"`` (counted (vertex, net-delta) pairs — payload
    and host work ∝ touched vertices, the large-n_v format) / ``"auto"``
    (sparse iff ``vertex_capacity >= SPARSE_CODEC_MIN_CAPACITY``).

    ``windowed=W`` marks the plan for the engine's sliding pane ring
    (``run_aggregation(windowed=...)``): emissions are degrees over the
    last W merge windows only. Degree vectors add elementwise, so no
    summary change is needed — panes fold from fresh zeros and the ring
    sums the live suffix at O(1) amortized combines per close.
    """
    from ..engine.aggregation import (
        SummaryAggregation,
        resolve_sparse_codec,
        sparse_payload_id_check,
    )

    n = vertex_capacity
    sparse = resolve_sparse_codec(codec, n)

    def init():
        return jnp.zeros((n,), jnp.int64)

    def fold(deg, chunk):
        with jax.named_scope("deg.fold"):
            delta = jnp.where(chunk.event == 1, -1, 1).astype(jnp.int64)
            if count_out:
                deg = segments.masked_scatter_add(
                    deg, chunk.src, delta, chunk.valid
                )
            if count_in:
                deg = segments.masked_scatter_add(
                    deg, chunk.dst, delta, chunk.valid
                )
            return deg

    def host_compress(chunk):
        m = np.asarray(chunk.valid)
        ev = np.asarray(chunk.event)
        from ..utils import native

        if native.degree_deltas_available():
            # Single native pass over both endpoint columns
            # (native/chunk_combiner.cc:degree_chunk_deltas), ~4x numpy's
            # two bincounts; GIL released, so it overlaps the H2D wait.
            return native.degree_chunk_deltas(
                np.asarray(chunk.src), np.asarray(chunk.dst),
                ev if ev.any() else None, None if m.all() else m,
                n, count_out, count_in,
            )
        all_valid = bool(m.all())
        # Insertion-only chunks (the common case) pass weights=None so
        # np.bincount takes its integer path — ~4.5x faster than the
        # float-weights path the deletion case needs.
        if not ev.any():
            sign = None
        else:
            sign = np.where(ev == 1, -1, 1)
            if not all_valid:
                sign = sign[m]
        out = np.zeros((n,), np.int32)
        for on, ids in ((count_out, chunk.src), (count_in, chunk.dst)):
            if on:
                ids = np.asarray(ids)
                out += np.bincount(
                    ids if all_valid else ids[m], weights=sign, minlength=n
                ).astype(np.int32)
        return out

    def fold_compressed(deg, deltas):  # deltas: i32[K, n]
        return deg + jnp.sum(deltas, axis=0, dtype=jnp.int64)

    def host_compress_sparse(chunk) -> dict:
        m = np.asarray(chunk.valid)
        ev = np.asarray(chunk.event)
        n_valid = int(np.count_nonzero(m))
        from ..utils import native

        if native.sparse_codecs_available():
            v, d = native.degree_chunk_deltas_sparse(
                np.asarray(chunk.src), np.asarray(chunk.dst),
                ev if ev.any() else None, None if n_valid == m.size else m,
                n, count_out, count_in,
            )
        else:
            v, d = degree_pairs_numpy(
                chunk.src, chunk.dst, ev, m, n, count_out, count_in
            )
        get_bus().inc("deg.codec_edges", n_valid)
        return {"v": v, "d": d}

    def stack_sparse(payloads: list, groups: int = 1) -> dict:
        from ..engine.aggregation import (
            bucket_stack_payloads,
            group_combine_payloads,
        )

        def combine(grp: list) -> dict:
            # Net deltas sum by vertex — fewer, duplicate-free device
            # lanes per dispatch. i64 output: a group sums fold_batch
            # chunks' i32 deltas, so the per-chunk bound no longer holds.
            v, d = _sum_deltas(
                np.concatenate([q["v"] for q in grp]),
                np.concatenate([q["d"] for q in grp]).astype(np.int64),
            )
            return {"v": v, "d": d}

        payloads = group_combine_payloads(
            payloads, groups, combine,
            {"v": np.empty(0, np.int32), "d": np.empty(0, np.int64)},
        )
        out = bucket_stack_payloads(payloads, {"v": -1, "d": 0})
        # Pairs over shipped lanes is the fold's lane fill; pairs over
        # the codec's edges is its compression.
        bus = get_bus()
        pairs = sum(q["v"].shape[0] for q in payloads)
        bus.inc("deg.fold_pairs", pairs)
        bus.inc("deg.fold_lanes", out["v"].size)
        # Uncombined i32 deltas: at most ``groups`` rows, one a device,
        # so each fold takes fold_compressed_sparse's i32 scatter.
        bus.inc("deg.fold_i32_pairs",
                pairs if out["d"].dtype == np.int32 else 0)
        return out

    def fold_compressed_sparse(deg, payload):
        # payload: {"v": i32[K, cap], "d": int[K, cap]} counted (vertex,
        # net-delta) pairs, -1-padded. "d" is i32 straight from the
        # per-chunk codec but i64 after the group pre-combine (cross-chunk
        # sums exceed the per-chunk bound) — do NOT narrow it here.
        with jax.named_scope("deg.fold"):
            v = payload["v"].reshape(-1)
            d = payload["d"].reshape(-1)
            ok = v >= 0
            if d.dtype == jnp.int32 and payload["d"].shape[0] == 1:
                # One per-chunk row names each vertex at most once, so an
                # i32 scatter into zeros is exact; the widened delta then
                # takes one i64 add. The TPU has no 64-bit integer unit
                # and runs the i64 scatter-add ~10x slower.
                delta = segments.masked_scatter_add(
                    jnp.zeros(deg.shape, jnp.int32), v, d, ok
                )
                return deg + delta.astype(deg.dtype)
            # Group-combined i64 rows, or several i32 rows that may share
            # a vertex: summing those in i32 could overflow.
            return segments.masked_scatter_add(deg, v, d, ok)

    if windowed is not None and int(windowed) < 1:
        raise ValueError(f"windowed must be >= 1 pane, got {windowed}")
    agg = SummaryAggregation(
        init=init,
        fold=fold,
        combine=lambda a, b: a + b,
        transform=None,
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
        # Sparse-pair wire pad values (tenant compressed tiers stack
        # per-chunk payloads themselves; -1 lanes fold as no-ops) +
        # the producer-payload id range check (wire-ingest parity).
        codec_pad_values=(
            {"v": -1, "d": 0} if (ingest_combine and sparse) else None
        ),
        codec_payload_check=(
            sparse_payload_id_check(n, "v")
            if (ingest_combine and sparse) else None
        ),
        fold_accumulates=True,  # degree vectors add elementwise
        name="degree-aggregate",
    )
    if windowed is not None:
        agg.windowed_panes = int(windowed)
    return agg


def degrees_query(vertex_capacity: int, *, name: str = "degrees",
                  count_out: bool = True, count_in: bool = True,
                  compressed: bool = False, codec: str = "auto"):
    """Fuse-compatible degree query (``engine.multiquery.fuse``): the
    ±1-scatter fold (``ingest_combine=False`` by default — see
    :func:`~gelly_tpu.library.connected_components.cc_query` for the
    shared-chunk rationale; ``compressed=True`` keeps the delta codec
    on for fused codec sharing). ``count_out``/``count_in`` pick the
    direction, so e.g. out- and in-degree can ride one fused dispatch
    as two named queries."""
    from ..engine.multiquery import QuerySpec

    return QuerySpec(
        name=name,
        agg=degree_aggregate(vertex_capacity, count_out=count_out,
                             count_in=count_in,
                             ingest_combine=compressed, codec=codec),
        slot_capacity=vertex_capacity,
    )


def _sum_deltas(ids: np.ndarray, deltas: np.ndarray):
    """Sum deltas by vertex id, dropping zero nets. Accumulates in the
    deltas dtype — callers summing across chunks pass i64."""
    uniq, inv = np.unique(ids, return_inverse=True)
    acc = np.zeros(uniq.shape[0], deltas.dtype)
    np.add.at(acc, inv, deltas)
    nz = acc != 0
    return uniq[nz].astype(np.int32), acc[nz]


def degree_pairs_numpy(src, dst, event, valid, n_v: int,
                       count_out: bool = True, count_in: bool = True):
    """Pure-numpy fallback for the native sparse degree codec: counted
    (vertex, net-delta) pairs (zero net deltas omitted)."""
    m = None if valid is None else np.asarray(valid, bool)
    ev = None if event is None else np.asarray(event)
    ids_parts, delta_parts = [], []
    for on, col in ((count_out, src), (count_in, dst)):
        if not on:
            continue
        col = np.asarray(col)
        d = (
            np.ones(col.shape[0], np.int64) if ev is None or not ev.any()
            else np.where(ev == 1, -1, 1).astype(np.int64)
        )
        if m is not None and not m.all():
            col, d = col[m], d[m]
        ids_parts.append(col)
        delta_parts.append(d)
    if not ids_parts:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    ids = np.concatenate(ids_parts)
    deltas = np.concatenate(delta_parts)
    if ids.size == 0:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    if ids.min() < 0 or ids.max() >= n_v:
        raise ValueError("degree_pairs_numpy: vertex slot out of range")
    v, d = _sum_deltas(ids, deltas)
    return v, d.astype(np.int32)  # per-chunk nets fit i32 (native parity)


def degree_distribution(stream, max_degree: int | None = None
                        ) -> "DegreeDistributionStream":
    return DegreeDistributionStream(stream, max_degree)


class DegreeDistributionStream:
    def __init__(self, stream, max_degree: int | None = None):
        self.stream = stream
        # Degrees are bounded by 2x the edge events touching a vertex; the
        # histogram needs a static size. Default: vertex capacity.
        self.max_degree = (
            int(max_degree) if max_degree is not None
            else stream.ctx.vertex_capacity
        )

    def __iter__(self) -> Iterator[jax.Array]:
        """Yields the degree histogram (i64[max_degree+1], index = degree,
        entry = #vertices with that degree; degree-0/negative vertices are
        excluded per VertexDegreeCounts' removal) after each chunk."""
        n = self.stream.ctx.vertex_capacity
        d_max = self.max_degree

        @jax.jit
        def step(deg, c):
            delta = jnp.where(c.event == 1, -1, 1).astype(jnp.int64)
            deg = segments.masked_scatter_add(deg, c.src, delta, c.valid)
            deg = segments.masked_scatter_add(deg, c.dst, delta, c.valid)
            live = deg > 0
            hist = jnp.zeros((d_max + 1,), jnp.int64)
            idx = jnp.clip(deg, 0, d_max)
            hist = hist.at[jnp.where(live, idx, 0)].add(
                live.astype(jnp.int64), mode="drop"
            )
            return deg, hist, jnp.max(deg)

        deg = jnp.zeros((n,), jnp.int64)
        for c in self.stream:
            deg, hist, peak = step(deg, c)
            if int(peak) > d_max:
                raise ValueError(
                    f"degree {int(peak)} exceeds max_degree {d_max}; "
                    f"raise max_degree"
                )
            yield hist

    def final_distribution(self) -> dict[int, int]:
        hist = None
        for hist in self:
            pass
        if hist is None:
            return {}
        h = np.asarray(hist)
        return {int(d): int(h[d]) for d in np.nonzero(h)[0]}


class ShardedDegrees:
    """Vertex-hash-partitioned degree state over the mesh — the ``keyBy``
    parallelism strategy (SURVEY.md §2.8 row 2: the reference co-locates a
    vertex's edges on one subtask via hash shuffle,
    ``M/SimpleEdgeStream.java:492``).

    Three modes:

    - ``mode="auto"`` (default): the keyed exchange below, but a chunk
      whose exchange buckets overflow is left unapplied and replayed
      through the broadcast step — skewed streams stay correct at
      broadcast cost for the hot chunks only
      (``self.stats["fallback_chunks"]`` counts them).
    - ``mode="exchange"``: the chunk is split evenly across devices; each
      device emits (endpoint, ±1) pairs for its slice and a single
      ``all_to_all`` (:func:`parallel.partition.repartition_by_key`)
      delivers every pair to the device owning that vertex — per-device
      work is O(E/S), the true keyBy shuffle. Bucket overflow is counted
      in ``self.stats["dropped"]`` and raises (strict mode; raise
      ``bucket_slack`` for skewed streams).
    - ``mode="broadcast"``: every device scans the whole replicated chunk
      and masks to its owned endpoints — zero exchange buffers, but
      per-device work stays O(E). The skew-proof fallback.
    """

    def __init__(self, stream, mesh=None, count_out=True, count_in=True,
                 mode: str = "auto", bucket_slack: float = 2.0):
        if mode not in ("auto", "exchange", "broadcast"):
            raise ValueError(f"mode must be auto/exchange/broadcast, got {mode}")
        self.stream = stream
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh()
        self.count_out = count_out
        self.count_in = count_in
        self.mode = mode
        self.bucket_slack = bucket_slack
        self.stats = {"dropped": 0}
        n = stream.ctx.vertex_capacity
        self.per_shard = partition.slots_per_shard(
            n, mesh_lib.num_shards(self.mesh)
        )

    def _step_fn(self, mode: str):
        count_out, count_in = self.count_out, self.count_in
        m = self.mesh
        S = mesh_lib.num_shards(m)
        sharded = NamedSharding(m, P(SHARD_AXIS))

        if mode == "broadcast":
            def body(deg_local, chunk):
                # deg_local: this device's [per] slice; chunk replicated.
                delta = jnp.where(chunk.event == 1, -1, 1).astype(jnp.int64)
                if count_out:
                    mine = partition.owned_mask(chunk.src, S)
                    deg_local = segments.masked_scatter_add(
                        deg_local, partition.to_local_slot(chunk.src, S),
                        delta, chunk.valid & mine,
                    )
                if count_in:
                    mine = partition.owned_mask(chunk.dst, S)
                    deg_local = segments.masked_scatter_add(
                        deg_local, partition.to_local_slot(chunk.dst, S),
                        delta, chunk.valid & mine,
                    )
                return deg_local, jnp.zeros((1,), jnp.int64)

            in_chunk_spec = P()
        else:
            def body(deg_local, chunk_slice):
                # chunk_slice: this device's [1, L] slice of the split chunk.
                c = EdgeChunk(*(x[0] for x in chunk_slice))
                delta = jnp.where(c.event == 1, -1, 1).astype(jnp.int64)
                keys, deltas, valids = [], [], []
                if count_out:
                    keys.append(c.src)
                    deltas.append(delta)
                    valids.append(c.valid)
                if count_in:
                    keys.append(c.dst)
                    deltas.append(delta)
                    valids.append(c.valid)
                key = jnp.concatenate(keys)
                dd = jnp.concatenate(deltas)
                vv = jnp.concatenate(valids)
                cap = partition.default_bucket_capacity(
                    key.shape[0], S, self.bucket_slack
                )
                key_r, dd_r, valid_r, dropped = partition.repartition_by_key(
                    key, dd, vv, S, cap
                )
                applied = segments.masked_scatter_add(
                    deg_local, partition.to_local_slot(key_r, S),
                    dd_r, valid_r,
                )
                # An overflowing chunk is left UNAPPLIED (dropped is the
                # same psum on every device, so all shards agree): auto
                # mode replays it through the broadcast step; strict mode
                # raises with the state still consistent.
                deg_local = jnp.where(dropped == 0, applied, deg_local)
                return deg_local, dropped.astype(jnp.int64)[None]

            in_chunk_spec = P(SHARD_AXIS)

        @partial(jax.jit, out_shardings=(sharded, None))
        def step(deg, chunk):
            if mode != "broadcast":
                chunk = partition.split_chunk(chunk, S)
            deg2, dropped = mesh_lib.shard_map_fn(
                m, body, in_specs=(P(SHARD_AXIS), in_chunk_spec),
                out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
            )(deg, chunk)
            # dropped is identical on every shard (psum); take shard 0.
            return deg2, dropped[0]

        return step

    def final_degrees(self) -> dict[int, int]:
        n = self.stream.ctx.vertex_capacity
        mode = self.mode
        step = self._step_fn("broadcast" if mode == "broadcast" else "exchange")
        fallback = self._step_fn("broadcast") if mode == "auto" else None
        deg = jax.device_put(
            jnp.zeros((n,), jnp.int64), NamedSharding(self.mesh, P(SHARD_AXIS))
        )
        seen = np.zeros((n,), bool)
        pending: list = []  # (chunk, dropped_scalar) awaiting the drop check
        self.stats["fallback_chunks"] = 0

        def check_drops():
            nonlocal deg
            dropped_total = 0
            for c, d in pending:
                nd = int(d)
                if not nd:
                    continue
                if fallback is not None:
                    # The overflowing chunk was left unapplied: replay it
                    # through the skew-proof broadcast step.
                    deg, _ = fallback(deg, c)
                    self.stats["fallback_chunks"] += 1
                else:
                    dropped_total += nd
            pending.clear()
            if dropped_total:
                self.stats["dropped"] += dropped_total
                raise ValueError(
                    f"{dropped_total} endpoint updates overflowed the "
                    f"exchange buckets; raise bucket_slack or use "
                    f"mode='auto' (no silent drops)"
                )

        for i, c in enumerate(self.stream):
            ok = np.asarray(c.valid)
            # Directional parity with DegreeStream: an endpoint is
            # "touched" only for the directions being counted
            # (DegreeTypeSeparator, M/SimpleEdgeStream.java:440-459).
            if self.count_out:
                seen[np.asarray(c.src)[ok]] = True
            if self.count_in:
                seen[np.asarray(c.dst)[ok]] = True
            deg, dropped = step(deg, c)
            if mode != "broadcast":
                pending.append((c, dropped))
                # One host sync every 8 chunks: fail fast (strict) or
                # replay overflowed chunks (auto) without serializing the
                # dispatch pipeline.
                if i % 8 == 7:
                    check_drops()
        check_drops()
        # De-stripe the shard-concatenated state back to global slot order.
        out = partition.unstripe(np.asarray(deg), mesh_lib.num_shards(self.mesh))
        ctx = self.stream.ctx
        slots = np.nonzero(seen)[0]
        raw = ctx.decode(slots)
        return {int(r): int(out[s]) for s, r in zip(slots, raw)}


def sharded_degrees(stream, mesh=None, count_out=True, count_in=True,
                    mode: str = "auto", bucket_slack: float = 2.0
                    ) -> ShardedDegrees:
    return ShardedDegrees(stream, mesh, count_out, count_in, mode,
                          bucket_slack)
