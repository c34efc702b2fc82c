"""The summary-aggregation engine — the heart of the framework.

Re-owns the reference's ``SummaryAggregation`` plugin contract
(``M/SummaryAggregation.java:22-59``): an algorithm supplies only

  {fold, combine, transform, init, transient}

and the engine decides the physical plan. The reference's ``run()`` builds a
Flink dataflow (``SummaryBulkAggregation.run``, ``:68-90``):

  map(PartitionMapper) → keyBy(partition) → timeWindow → fold(initial, partial)
  → timeWindowAll → reduce(combine) → Merger(parallelism=1) → map(transform)

Here the same plan becomes a TPU execution schedule:

  split chunk across shards (→ PartitionMapper) →
  per-device jitted chunk fold into local summary (→ window fold) →
  at each window boundary, an ICI collective merge — butterfly merge-tree
  (→ SummaryTreeReduce) or all_gather+stacked merge (→ timeWindowAll.reduce) →
  Merger semantics on the replicated global summary →
  transform → chunk-grained emission.

Fold functions are **chunk-vectorized** (``fold(summary, EdgeChunk) -> summary``)
rather than per-edge; :func:`edges_fold_adapter` wraps a per-edge
``foldEdges(acc, src, dst, val)`` UDF (the reference's ``EdgesFold``,
``M/EdgesFold.java:33-48``) into a ``lax.scan`` chunk fold for API parity.

Windows: ``merge_every`` chunks (count-based cadence, the throughput path) or
``window_ms`` over the stream's timestamps (tumbling event/ingestion-time
windows matching ``timeWindow(timeMillis)``). Both trigger the same
merge+Merger+emit sequence.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial
from typing import Any, Callable, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.chunk import EdgeChunk, split_chunk_host
from ..obs import bus as obs_bus
from ..obs import tracing as obs_tracing
from ..parallel import collectives, mesh as mesh_lib, partition
from ..parallel.mesh import SHARD_AXIS
from . import faults as faults_mod

Summary = Any


@dataclasses.dataclass(eq=False)
class SummaryAggregation:
    """The four-knob plugin contract (M/SummaryAggregation.java:31-55).

    - ``init()`` → fresh summary pytree (fixed shapes).
    - ``fold(summary, chunk)`` → summary: vectorized per-shard edge fold
      (the EdgesFold updateFun, chunk-at-a-time).
    - ``combine(a, b)`` → summary: associative+commutative cross-partition
      merge (the ReduceFunction combineFun).
    - ``transform(summary)`` → emission (optional MapFunction).
    - ``transient`` — when True the global summary resets every window
      (M/SummaryAggregation.java:113-115); otherwise it accumulates.
    - ``merge_stacked`` — optional ``stacked -> summary`` merging all K
      shard summaries at once (leading axis K); when present the engine
      uses all_gather + merge_stacked instead of the butterfly combine.
    """

    init: Callable[[], Summary]
    fold: Callable[[Summary, EdgeChunk], Summary]
    combine: Callable[[Summary, Summary], Summary]
    transform: Callable[[Summary], Any] | None = None
    transient: bool = False
    # transform is jitted per plan (device transforms, the default); set
    # False for transforms doing host-side / non-traceable work.
    jit_transform: bool = True
    # True when transform's output may PASS THROUGH leaves of the live
    # summary unchanged (e.g. a fused multi-query plan whose
    # transform-less sub-query emits its running state): the accumulate
    # plan then keeps fold donation OFF, exactly like the transform-less
    # accumulate plan — a donated next fold would delete the consumer's
    # held emission out from under it (see the donation contract below).
    transform_may_alias: bool = False
    merge_stacked: Callable[[Summary], Summary] | None = None
    # Optional ingest codec: ``host_compress(chunk) -> payload`` runs on the
    # prefetch thread and pre-aggregates a chunk into a compact numpy pytree
    # (the reference's per-partition partial fold relocated to the ingest
    # side, M/SummaryBulkAggregation.java:76-80); ``fold_compressed(summary,
    # stacked_payload)`` folds a [K]-stacked batch of payloads on device.
    # Both must be set for the codec path to engage; it cuts H2D bytes by
    # 1-2 orders of magnitude, which is the scarce resource on the
    # host->device link. Ignored in window mode (payloads carry no
    # per-edge timestamps).
    host_compress: Callable[[EdgeChunk], Any] | None = None
    fold_compressed: Callable[[Summary, Any], Summary] | None = None
    # Optional payload stacker for variable-length codec payloads:
    # ``stack_payloads(list_of_payloads, groups) -> stacked pytree``
    # (leading axis >= groups, a multiple of it). Sparse touched-slot
    # codecs use it to pad each batch to a power-of-two bucket capacity
    # (wire bytes track the actual touched count; the handful of bucket
    # shapes keep jit retraces bounded), and MAY pre-combine the batch
    # down to ``groups`` payloads on the host (a SummaryTreeReduce
    # partial-merge level on the ingest side). ``groups`` is the mesh
    # shard count (the batch axis splits across devices); 1 on a single
    # shard. None = leaves are equal-shape and np.stack-ed generically.
    stack_payloads: Callable[..., Any] | None = None
    # Optional host-side validator for PRODUCER-COMPRESSED payloads
    # (wire DATA_COMPRESSED frames, tenant submit_payload, the engine's
    # precompressed=True staging): ``codec_payload_check(payload)``
    # raises ValueError on a payload the device fold could only
    # mis-index SILENTLY — out-of-range ids scatter-drop/clamp on
    # device, the exact corruption mode payload_to_chunk's
    # vertex_capacity guard exists to prevent on the raw wire. Checked
    # at the staging/enqueue boundary so the error lands on the
    # producer side, never the scheduler/fold thread.
    codec_payload_check: Callable[[Any], None] | None = None
    # Wire/stacking pad values for the codec payload's VARIABLE-LENGTH
    # dict keys (e.g. the sparse CC pairs' {"v": -1, "r": 0}): consumers
    # that stack per-chunk payloads themselves — the tenant engine's
    # compressed tiers, which stack one payload per LANE instead of K
    # per unit — pad each key to a shared bucket with these values so
    # the padded lanes fold as no-ops exactly like the plan's own
    # stack_payloads padding. None with a dict payload means every key
    # is fixed-shape (stacked as-is); ndarray payloads never need it.
    codec_pad_values: dict | None = None
    # True when stack_payloads mutates per-run state in STREAM order (the
    # compact plans' persistent id assignment): the engine then numbers
    # codec units from 0 per run and passes ``seq=`` to stack_payloads so
    # concurrent ingest workers can take the stateful step in order
    # (everything stateless in the stacker stays parallel).
    stack_ordered: bool = False
    # With stack_ordered, a unit that fails BEFORE taking its assignment
    # turn would park every later unit's worker in await_turn forever; the
    # engine calls this hook (with the failed unit's seq) from the staging
    # error path so the codec can release the turn (idempotent if the
    # unit already completed it).
    on_stage_error: Callable[[int], None] | None = None
    # With stack_ordered, cumulative seconds stagers have spent blocked in
    # the codec's ordered-turn gate (CompactIdSession.await_turn). The
    # engine samples it at run start and teardown and reattributes the
    # delta from ``ingest_compress`` to a ``codec_wait`` timer stage:
    # turn-wait is pipeline serialization, not compress work, and booking
    # it as busy would overstate the serial-cost side of the overlap
    # accounting (a serial run never waits here).
    ordered_wait_s: Callable[[], float] | None = None
    # SummaryTreeReduce's degree knob (M/SummaryTreeReduce.java:75): when
    # set, the cross-shard combine runs as a two-phase hierarchical tree —
    # groups of S/degree shards merge first (ICI-local), then across groups
    # (DCN on multi-host meshes). None = flat butterfly / gather merge.
    merge_degree: int | None = None
    # Stateful-codec lifecycle hooks (e.g. the compact-space CC plan's
    # host id session): ``on_run_start()`` fires at the start of every
    # run_aggregation generator (fresh run = fresh codec state — one live
    # run per aggregation instance at a time); ``on_resume(summary)`` fires
    # after a checkpoint load so host codec state can be rebuilt from the
    # restored device summary.
    on_run_start: Callable[[], None] | None = None
    on_resume: Callable[[Summary], None] | None = None
    # Device-fold kernel backend the plan's fold closures were built for
    # ("xla" | "pallas"): set by the library plan builders (e.g.
    # connected_components(fold_backend=...)), recorded here so the
    # engine's compiled-plan cache keys on it — the same aggregation
    # instance re-jits (rather than silently reusing stale executables)
    # if a caller rebuilds its folds for a different backend.
    fold_backend: str = "xla"
    # Cross-shard window-merge strategy ("replicated" | "delta" | "auto").
    # The replicated merges (butterfly / hierarchical tree / gather) move
    # FULL per-shard summaries — cost ∝ capacity per window regardless of
    # how little the window touched. A plan that supplies ``merge_delta``
    # can instead exchange only the dirty entries its folds marked:
    #
    # - ``merge_dirty_count(local_summary) -> i32`` — per-shard count of
    #   dirty entries (pure jnp; the engine wraps it in shard_map and
    #   reads the max once per window close to size the gather bucket);
    # - ``merge_delta(base, local_summary, bucket) -> summary`` — runs
    #   per-shard INSIDE shard_map: compact this shard's dirty rows to
    #   ``bucket`` lanes (collectives.compact_delta), all_gather every
    #   shard's rows (collectives.gather_delta), and apply them to the
    #   replicated ``base`` (the carried global summary). Replaces BOTH
    #   the cross-shard merge and the Merger combine in one program, so
    #   window-merge cost is ∝ hooks-since-last-merge, not capacity.
    #
    # "auto" decides per window from the measured count: delta while the
    # gathered rows (S * bucket) stay under ``merge_delta_auto_rows``,
    # else the plan's replicated merge. Deltas are measured against a
    # window-fresh locals (init()), which the engine guarantees by
    # rebuilding locals at every window close. Like fold_backend, the
    # compiled-plan cache keys on merge_mode.
    merge_mode: str = "replicated"
    merge_delta: Callable[..., Summary] | None = None
    merge_dirty_count: Callable[[Summary], Any] | None = None
    merge_delta_auto_rows: int | None = None
    # True for plans whose fold exists ONLY through the ingest codec (the
    # compact-space plans: raw chunks carry ids the summary's compact space
    # has no mapping for). The engine then refuses — loudly, at plan time —
    # any configuration where the codec cannot engage (window_ms mode, or a
    # batch that cannot align with the shard count) instead of silently
    # falling back to the raw fold.
    requires_codec: bool = False
    # Optional cadenced path flatten: ``flatten(summary) -> summary``
    # with IDENTICAL labels (e.g. unionfind.pointer_jump on the parent
    # leaf). The pair-sized folds (union_pairs_rooted/star) and the
    # dirty-delta merge deliberately skip the O(capacity) global flatten
    # per dispatch, so transform chase depth grows O(1) per window on
    # long streams; the engine runs this (jitted) once per CHECKPOINT
    # cadence — full-capacity work amortized over the checkpoint
    # interval, keeping chase depth bounded for the whole stream. The
    # flattened summary REPLACES the live state (and is what the
    # checkpoint snapshots).
    flatten: Callable[[Summary], Summary] | None = None
    # Declares fold(combine(a, b), c) == combine(a, fold(b, c)) — folding
    # into an already-combined summary equals combining afterwards (true
    # for pure edge-set summaries: CC forests, parity forests, degree
    # vectors). With it, the single-shard non-transient plan carries ONE
    # running summary across windows and emits transform(local) directly,
    # skipping the per-window Merger combine — which for forest summaries
    # is a full-capacity union fixpoint per window close. Emissions are
    # identical; only the physical plan changes.
    fold_accumulates: bool = False
    name: str = "aggregation"


# Auto-codec threshold: below this slot-space size a dense per-chunk
# payload (n_v * ~4 bytes) is smaller/cheaper than touched-slot pairs;
# above it the dense payload inverts the codec's wire compression.
SPARSE_CODEC_MIN_CAPACITY = 1 << 20

# Smallest dirty-delta gather bucket (pow-2 ladder floor): keeps the
# per-window program count bounded and lets merge_mode="auto" prove at
# PLAN time that delta can never win on tiny capacities (S * floor already
# above the plan's auto-rows bound) — those plans skip the count program
# entirely instead of paying a per-window D2H for a foregone decision.
DELTA_MERGE_MIN_BUCKET = 256


def available_cores() -> int:
    """Cores this process may actually run on (affinity/cgroup-aware)."""
    import os

    try:
        return len(os.sched_getaffinity(0)) or 1
    except AttributeError:
        return os.cpu_count() or 1


def resolve_sparse_codec(codec: str, vertex_capacity: int) -> bool:
    """Shared ``codec=`` knob semantics for the ingest codecs: validate
    and resolve ``"auto"``/``"dense"``/``"sparse"`` to a bool (sparse?).
    """
    if codec not in ("auto", "dense", "sparse"):
        raise ValueError(f"codec must be auto/dense/sparse, got {codec}")
    return codec == "sparse" or (
        codec == "auto" and vertex_capacity >= SPARSE_CODEC_MIN_CAPACITY
    )


def group_combine_payloads(payloads: list, groups: int,
                           combine_fn: Callable[[list], dict],
                           empty_payload: dict) -> list:
    """Host pre-combine for a combining ``stack_payloads``: when the
    batch is larger than ``groups``, merge it down to exactly ``groups``
    payloads (ceil-sized contiguous groups, padded with ``empty_payload``
    rows so the mesh split sees ``groups`` rows).
    ``combine_fn(group_payloads) -> payload``.

    ``len(payloads) <= groups`` returns the list UNCHANGED (no padding):
    the engine's stage path always pre-pads batches to a multiple of the
    shard count, which is what the downstream mesh reshape needs — a
    caller with a short, non-multiple list must pad before the split.
    """
    if len(payloads) <= groups:
        return payloads
    size = -(-len(payloads) // groups)
    combined = [
        combine_fn(payloads[i:i + size])
        for i in range(0, len(payloads), size)
    ]
    while len(combined) < groups:
        combined.append(empty_payload)
    return combined


def bucket_stack_payloads(payloads: list, pad_values: dict,
                          min_bucket: int = 1024,
                          quantum: int | None = None,
                          per_key: dict | None = None) -> dict:
    """Stack variable-length dict payloads to a shared power-of-two bucket.

    ``pad_values`` maps the variable-length array keys to their padding
    value; those leaves are padded to ``max(min_bucket,
    next_pow2(longest))`` before stacking, so the stacked shape (and hence
    the jitted fold program) takes only O(log) distinct values across a
    stream. Keys not in ``pad_values`` (per-payload scalars/fixed shapes)
    are stacked as-is. This is the wire format of the sparse touched-slot
    codecs: payload bytes ∝ the chunk's actual touched count, never the
    vertex capacity.

    ``quantum`` switches the bucket ladder from powers of two to multiples
    of ``quantum``: distinct shapes stay bounded (≤ longest/quantum per
    stream) while padding waste drops from up-to-2x to ≤ quantum lanes —
    the fold kernels' gather cost scales with PADDED lanes, so at
    multi-M pair counts the pow-of-two ladder would buy compile-cache
    stability with up to 2x device work.

    ``per_key`` maps a padded key to its own ``(min_bucket, quantum)``:
    keys whose natural length is far below the others' (e.g. per-segment
    lengths vs per-pair members) then get their own bucket ladder instead
    of inheriting the largest key's capacity — padding a short leaf to
    the long leaves' bucket was measured as ~1/3 of the compact codec's
    wire bytes. Keys not listed share the default ladder as before.
    """
    def _cap(longest, mb, q):
        if q:
            return max(mb, -(-longest // q) * q)
        return max(mb, 1 << max(0, longest - 1).bit_length())

    per_key = per_key or {}
    shared = [k for k in pad_values if k not in per_key]
    longest = max(
        (p[k].shape[0] for p in payloads for k in shared), default=0
    )
    caps = {k: _cap(longest, min_bucket, quantum) for k in shared}
    for k, (mb, q) in per_key.items():
        lk = max((p[k].shape[0] for p in payloads), default=0)
        caps[k] = _cap(lk, mb, q)
    out = {}
    for key in payloads[0]:
        if key in pad_values:
            stacked = np.full(
                (len(payloads), caps[key]), pad_values[key],
                dtype=payloads[0][key].dtype,
            )
            for i, p in enumerate(payloads):
                stacked[i, : p[key].shape[0]] = p[key]
            out[key] = stacked
        else:
            out[key] = np.stack([p[key] for p in payloads])
    return out


def sparse_payload_id_check(vertex_capacity: int, *keys: str):
    """Build a ``codec_payload_check`` (see the SummaryAggregation
    field) validating that every listed key of a sparse codec payload
    carries vertex ids in ``[0, vertex_capacity)`` — the
    ``payload_to_chunk`` range guard's twin for pre-compressed ingest,
    where the payload never passes through a chunk. O(k) numpy min/max
    per key, run on the producer/staging side."""
    def check(payload) -> None:
        if not isinstance(payload, dict):
            raise ValueError(
                f"compressed payload must be a dict of arrays, got "
                f"{type(payload).__name__} — was it compressed by a "
                "different plan/codec?"
            )
        for key in keys:
            if key not in payload:
                raise ValueError(
                    f"compressed payload is missing key {key!r} — was "
                    "it compressed by a different plan/codec?"
                )
            a = np.asarray(payload[key])
            if a.size == 0:
                continue
            lo, hi = int(a.min()), int(a.max())
            if lo < 0 or hi >= vertex_capacity:
                bad = lo if lo < 0 else hi
                raise ValueError(
                    f"compressed payload key {key!r} carries vertex id "
                    f"{bad} out of range for vertex_capacity "
                    f"{vertex_capacity} — compressed by a plan with a "
                    "different capacity? (an out-of-range id would "
                    "silently drop/clamp in the device scatter)"
                )

    return check


def _payload_nbytes(payload) -> int:
    """Host bytes of a staged unit's pytree — span attribution only
    (called on the tracer-enabled path, never the bare unit path)."""
    return int(sum(getattr(l, "nbytes", 0)
                   for l in jax.tree.leaves(payload)))


def _group_edges(group) -> int:
    """Valid-edge count of a unit's chunk group — span/heartbeat
    attribution only (one O(chunk) bool sum per chunk, tracer-enabled
    path only)."""
    return int(sum(int(np.asarray(c.valid).sum()) for c in group))


def edges_fold_adapter(fold_edges: Callable, *, with_value: bool = True):
    """Wrap a per-edge UDF ``foldEdges(acc, src, dst[, val])`` into a chunk fold.

    Parity adapter for the reference's EdgesFold contract
    (M/EdgesFold.java:33-48): runs a sequential ``lax.scan`` over the chunk in
    stream order. Library algorithms should prefer native vectorized folds;
    this exists so arbitrary user folds still run on device.
    """

    def fold(summary, chunk: EdgeChunk):
        def step(acc, inp):
            src, dst, val, ok = inp
            out = (
                fold_edges(acc, src, dst, val)
                if with_value
                else fold_edges(acc, src, dst)
            )
            acc2 = jax.tree.map(
                lambda new, old: jnp.where(ok, new, old), out, acc
            )
            return acc2, None

        acc, _ = jax.lax.scan(
            step, summary, (chunk.src, chunk.dst, chunk.val, chunk.valid)
        )
        return acc

    return fold


class SummaryStream:
    """Lazy stream of per-window emissions from a running aggregation.

    Iterating yields ``transform(global_summary)`` once per closed window
    (plus once at end-of-stream for the final partial window). ``result()``
    drains the stream and returns the last emission — the reference tests'
    "take the final summary" oracle
    (T/example/test/ConnectedComponentsTest.java:65-81).
    """

    def __init__(self, gen_fn: Callable[[], Iterator]):
        self._gen_fn = gen_fn

    def __iter__(self):
        return self._gen_fn()

    def result(self):
        last = None
        for last in self:
            pass
        return last


class WindowedStream(SummaryStream):
    """A :class:`SummaryStream` over a pane ring (``windowed=W``), plus
    the queryable epoch handle: ``snapshot()`` returns the latest
    ``{"window", "labels"}`` emission under a lock, readable from any
    thread while the stream advances. Staleness is bounded by ONE pane
    (the value published at the most recent pane close) — the same
    contract as tenant/multiquery snapshots. Returns ``None`` before
    the first pane closes.
    """

    def __init__(self, gen_fn: Callable[[], Iterator], holder: dict):
        super().__init__(gen_fn)
        self._holder = holder

    def snapshot(self):
        with self._holder["lock"]:
            val = self._holder["val"]
        obs_bus.get_bus().inc("windows.snapshot_reads")
        return val


def _compiled_plan(agg: SummaryAggregation, m):
    # Jitted physical plans are memoized on the aggregation instance itself:
    # jax.jit caches executables by function identity, so rebuilding the
    # closures on every run_aggregation call would recompile the whole plan
    # each time (26 s of compile for the 2^24-slot compact plan on a v5e,
    # chip_smoke PR 21). Storing on the instance ties the cache (and its
    # compiled executables) to the agg's lifetime.
    # EVERY scalar knob this builder reads must appear in the key (the
    # plancheck PC101 contract): a knob read but not keyed means mutating
    # it on a live instance silently returns the stale compiled plan.
    key = (tuple(d.id for d in m.devices.flat), m.axis_names,
           agg.fold_backend, agg.merge_mode, agg.merge_degree,
           agg.merge_delta_auto_rows, agg.transient,
           agg.fold_accumulates, agg.transform_may_alias,
           agg.jit_transform)
    per_agg = agg.__dict__.setdefault("_plan_cache", {})
    if key in per_agg:
        return per_agg[key]

    S = mesh_lib.num_shards(m)
    shard_leaf = lambda tree: jax.tree.map(lambda l: l[None], tree)
    unshard_leaf = lambda tree: jax.tree.map(lambda l: l[0], tree)
    sharded = NamedSharding(m, P(SHARD_AXIS))

    # Fresh [S, ...]-stacked local summaries, rebuilt at EVERY window
    # close (folds donate their input, so a shared locals0 object would
    # be consumed by the first fold that sees it). Jitted so the rebuild
    # is one cached on-device dispatch — the eager host-broadcast +
    # device_put version costs a full H2D per window, which at
    # merge_every=1 means per chunk.
    @partial(jax.jit, out_shardings=sharded)
    def locals0_fn():
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (S,) + l.shape), agg.init()
        )

    # Fold state is DONATED (donate_argnums=0): the steady-state pipeline
    # re-dispatches the fold dozens of times per merge window, and without
    # donation every dispatch allocates a fresh full-capacity summary.
    # With it, XLA writes the new summary into the old one's buffers —
    # zero allocation after the first fold. The engine upholds the
    # donation contract by never reading a summary object after passing it
    # to a fold (locals are rebound by every fold call and rebuilt fresh
    # at each window close; see close_window). The jitlint GL006 rule
    # guards the same contract statically. The ONE plan shape where a
    # summary ESCAPES to the caller is the accumulate plan without a
    # transform: close_window yields the live fold state itself, and a
    # donated next fold would delete the consumer's held emission out
    # from under it — donation stays off exactly there.
    accum_plan = agg.fold_accumulates and not agg.transient and S == 1
    donate = () if (
        accum_plan and (agg.transform is None or agg.transform_may_alias)
    ) else (0,)
    if S == 1:
        # Single-shard specialization: the shard_map + collective plumbing
        # is identity at S=1 and only adds dispatch/layout overhead.
        locals0_fn = jax.jit(agg.init)  # noqa: F811

        fold_step = jax.jit(agg.fold, donate_argnums=donate)
        merge_locals = jax.jit(lambda s: s)

        @partial(jax.jit, donate_argnums=donate)
        def fold_many(s, stacked_chunk):
            # K chunks in one dispatch: scan the fold over the stacked
            # leading axis. Per-dispatch fixed costs amortize K-fold.
            def step(acc, ck):
                return agg.fold(acc, ck), None

            s, _ = jax.lax.scan(step, s, stacked_chunk)
            return s

        if agg.fold_compressed is not None:
            fold_codec = jax.jit(agg.fold_compressed, donate_argnums=donate)
        else:
            fold_codec = None
    else:
        @partial(jax.jit, out_shardings=sharded, donate_argnums=0)
        def fold_step(locals_, chunk):
            # Split fused into the same program as the fold: one dispatch
            # per chunk (per-dispatch fixed costs dominate small chunks).
            chunk_split = partition.split_chunk(chunk, S)

            def body(loc, ck):
                s = unshard_leaf(loc)
                c = EdgeChunk(*(x[0] for x in ck))
                return shard_leaf(agg.fold(s, c))

            return mesh_lib.shard_map_fn(
                m, body, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                out_specs=P(SHARD_AXIS),
            )(locals_, chunk_split)

        @jax.jit
        def merge_locals(locals_):
            def body(loc):
                s = unshard_leaf(loc)
                if agg.merge_degree is not None:
                    g = collectives.hierarchical_merge(
                        agg.combine, s, S, min(agg.merge_degree, S)
                    )
                elif agg.merge_stacked is not None:
                    g = collectives.gather_merge(agg.merge_stacked, s)
                else:
                    g = collectives.butterfly_merge(agg.combine, s, S)
                return shard_leaf(g)

            merged = mesh_lib.shard_map_fn(
                m, body, in_specs=(P(SHARD_AXIS),), out_specs=P(SHARD_AXIS),
            )(locals_)
            # All shards hold the identical global merge; take shard 0.
            return unshard_leaf(merged)

        @partial(jax.jit, out_shardings=sharded, donate_argnums=0)
        def fold_many(locals_, stacked_chunk):
            # K chunks in one dispatch on the sharded raw path (VERDICT r2
            # item 7): each chunk of the host-stacked [K, C] batch splits
            # across shards ([S, K, C/S]) and the per-shard fold scans the
            # batch inside a single shard_map program — the same K-fold
            # dispatch amortization as the S=1 fold_many. The split itself
            # is fold_step's split_chunk, vmapped over the batch axis.
            split = jax.vmap(
                lambda c: partition.split_chunk(c, S)
            )(stacked_chunk)
            chunk_split = EdgeChunk(*(x.swapaxes(0, 1) for x in split))

            def body(loc, ckb):
                s = unshard_leaf(loc)

                def step(acc, ck):
                    return agg.fold(acc, ck), None

                s, _ = jax.lax.scan(
                    step, s, EdgeChunk(*(x[0] for x in ckb))
                )
                return shard_leaf(s)

            return mesh_lib.shard_map_fn(
                m, body, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                out_specs=P(SHARD_AXIS),
            )(locals_, chunk_split)

        if agg.fold_compressed is not None:
            # Codec payloads are data-parallel over the chunk axis: a batch
            # of K payloads arrives as [S, K/S, ...]-sharded leaves and each
            # device folds its K/S payloads into its local summary.
            @partial(jax.jit, out_shardings=sharded, donate_argnums=0)
            def fold_codec(locals_, payload):
                def body(loc, pl):
                    s = unshard_leaf(loc)
                    p = jax.tree.map(lambda x: x[0], pl)
                    return shard_leaf(agg.fold_compressed(s, p))

                return mesh_lib.shard_map_fn(
                    m, body, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                    out_specs=P(SHARD_AXIS),
                )(locals_, payload)
        else:
            fold_codec = None

    @jax.jit
    def merger_step(window_summary, global_summary):
        # The parallelism-1 Merger (M/SummaryAggregation.java:107-119):
        # incremental non-blocking global combine.
        return agg.combine(window_summary, global_summary)

    # Dirty-delta merge programs (merge_mode="delta"/"auto", S > 1 plans
    # that supply merge_delta): one tiny count program sizing the gather
    # bucket, and one merge program per bucket (a bounded pow-2 ladder —
    # O(log capacity) distinct programs per stream). The merge fuses the
    # cross-shard merge AND the Merger combine: it applies every shard's
    # gathered dirty rows directly to the carried global summary, so the
    # per-window merge cost is ∝ hooks, not ∝ capacity.
    delta_count_fn = None
    merge_delta_for = None
    if agg.merge_mode not in ("replicated", "delta", "auto"):
        # Fail loudly like every other plan knob: a typo'd mode on a
        # hand-built SummaryAggregation would otherwise silently run the
        # capacity-proportional replicated merge — the exact wall the
        # delta path exists to avoid. (Library plans validate earlier in
        # resolve_merge_mode; the engine is a public path too.)
        raise ValueError(
            f"plan {agg.name!r}: merge_mode must be 'replicated', "
            f"'delta' or 'auto', got {agg.merge_mode!r}"
        )
    if S > 1 and agg.merge_mode == "delta" and agg.merge_delta is None:
        raise ValueError(
            f"plan {agg.name!r} sets merge_mode='delta' but supplies no "
            "merge_delta — the delta merge is summary-specific and must "
            "come from the plan (see SummaryAggregation.merge_delta); "
            "use merge_mode='replicated' for plans without one"
        )
    if (S > 1 and agg.merge_delta is not None
            and (agg.merge_mode == "delta"
                 or (agg.merge_mode == "auto"
                     and agg.merge_delta_auto_rows is not None
                     and S * DELTA_MERGE_MIN_BUCKET
                     <= agg.merge_delta_auto_rows))):
        if agg.merge_dirty_count is None:
            raise ValueError(
                f"plan {agg.name!r} supplies merge_delta without "
                "merge_dirty_count — the engine sizes the delta gather "
                "bucket from the measured count; supply both or neither"
            )

        @jax.jit
        def delta_count_fn(locals_):  # noqa: F811
            def body(loc):
                return agg.merge_dirty_count(unshard_leaf(loc))[None]

            return mesh_lib.shard_map_fn(
                m, body, in_specs=(P(SHARD_AXIS),),
                out_specs=P(SHARD_AXIS),
            )(locals_)

        _delta_cache: dict = {}

        def merge_delta_for(bucket):  # noqa: F811
            fn = _delta_cache.get(bucket)
            if fn is None:
                @jax.jit
                def fn(locals_, global_summary):
                    def body(loc, g):
                        merged = agg.merge_delta(
                            g, unshard_leaf(loc), bucket
                        )
                        return shard_leaf(merged)

                    out = mesh_lib.shard_map_fn(
                        m, body, in_specs=(P(SHARD_AXIS), P()),
                        out_specs=P(SHARD_AXIS),
                    )(locals_, global_summary)
                    # Every shard applied the identical gathered delta to
                    # the identical base; take shard 0 (same convention
                    # as merge_locals).
                    return unshard_leaf(out)

                _delta_cache[bucket] = fn
            return fn

    # transform runs jitted by default: an eager lax.while_loop (e.g. the CC
    # label pointer-jump) re-dispatches per call and dominates the window
    # cost. Host-side transforms set jit_transform=False.
    if agg.transform is None:
        transform_fn = None
    elif agg.jit_transform:
        transform_fn = jax.jit(agg.transform)
    else:
        transform_fn = agg.transform

    # The cadenced path flatten, jitted but NOT donating: at checkpoint
    # cadence the pre-flatten summary may still be held by a consumer
    # (the accumulate plan yields the live state), so the old buffers
    # must survive the call.
    flatten_fn = jax.jit(agg.flatten) if agg.flatten is not None else None

    plan = (fold_step, merge_locals, merger_step, locals0_fn,
            transform_fn, fold_many, fold_codec, delta_count_fn,
            merge_delta_for, flatten_fn)
    per_agg[key] = plan
    return plan


class TenantPlan(NamedTuple):
    """Compiled vmapped physical plan for one tenant tier (see
    ``engine/tenants.py``): every function operates on summaries STACKED
    along a leading tenant axis of static width ``lanes``, so one donated
    dispatch advances every lane of the tier."""

    init: Callable[[], Summary]  # -> [lanes, ...]-stacked fresh summaries
    fold: Callable[..., Summary]  # (stacked, stacked_chunk, active) -> stacked
    merger: Callable[[Summary, Summary], Summary]  # vmapped combine
    transform: Callable[[Summary], Any] | None  # vmapped transform
    snapshot: Callable[[Summary], Any]  # query-safe copy (never aliases)
    flatten: Callable[[Summary], Summary] | None  # vmapped path flatten
    lanes: int
    # Vmapped compressed fold for codec tiers: (stacked, stacked_payload,
    # active) -> stacked, each lane folding its own pre-compressed
    # [1, ...]-batched payload (None for plans without fold_compressed).
    fold_codec: Callable[..., Summary] | None = None


def _compiled_tenant_plan(agg: SummaryAggregation, lanes: int,
                          mesh=None) -> TenantPlan:
    """Build (and memoize on the aggregation instance, like
    :func:`_compiled_plan`) the vmapped tenant-tier plan.

    The tenant axis replaces the shard axis as the data-parallel axis:
    ``fold``/``combine``/``transform`` are ``jax.vmap``-ed over a leading
    axis of ``lanes`` tenants, and the fold DONATES the stacked state —
    one dispatch, zero steady-state allocation, N tenants advanced.
    ``active`` masks no-op lanes (a tenant with no pending chunk keeps
    its summary bit-unchanged via a per-lane select), so stragglers
    never stall the batch. Tiers share one compiled program per
    ``lanes`` width (widths grow by doubling, so a stream of admissions
    compiles O(log N) programs, not O(N)).

    With ``mesh`` spanning S > 1 devices and ``lanes % S == 0`` the
    TENANT axis itself is sharded across the mesh — the lanes are
    data-parallel with no cross-lane collectives, so XLA partitions the
    vmapped program for free.

    Plans whose codec is a STATEFUL ordered stacker (``stack_ordered``)
    are refused loudly: their id-assignment session consumes payloads in
    global stream order, which concurrent tenant lanes cannot provide.
    Plain codec plans (``host_compress``/``fold_compressed``, incl.
    ``requires_codec``) compile a vmapped ``fold_codec`` next to the raw
    fold — the compressed-tier dispatch path. Host-side transforms
    (``jit_transform=False``) are refused too — queries read device
    snapshots.
    """
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    windowed_panes = getattr(agg, "windowed_panes", None)
    if windowed_panes is not None:
        raise ValueError(
            f"aggregation '{agg.name}' carries a pane ring "
            f"(windowed_panes={windowed_panes}): the ring's two-stack "
            "state and TTL session rebuilds are single-stream host "
            "structures the vmapped tenant lanes cannot share — run "
            "the windowed query as its own stream, or add the "
            "non-windowed builder variant as the tier plan"
        )
    if agg.stack_ordered:
        raise ValueError(
            f"aggregation '{agg.name}' uses an ordered stacker "
            "(stack_ordered: its codec session assigns compact ids in "
            "GLOBAL STREAM order — per-run host state no concurrent "
            "tenant lane order can reproduce); build the tier plan on a "
            "stateless codec (e.g. codec='sparse') or the raw fold "
            "(ingest_combine=False)"
        )
    if agg.requires_codec and agg.fold_compressed is None:
        raise ValueError(
            f"aggregation '{agg.name}' sets requires_codec but supplies "
            "no fold_compressed — the tier has no fold to compile"
        )
    if agg.transform is not None and not agg.jit_transform:
        raise ValueError(
            f"aggregation '{agg.name}' uses a host-side transform "
            "(jit_transform=False); tenant snapshots are device-resident "
            "vmapped transforms"
        )
    mesh_key = None
    sharding = None
    if mesh is not None and mesh_lib.num_shards(mesh) > 1:
        S = mesh_lib.num_shards(mesh)
        if lanes % S:
            raise ValueError(
                f"tenant lanes {lanes} must be a multiple of the "
                f"{S}-device mesh to shard the tenant axis"
            )
        mesh_key = (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)
        sharding = NamedSharding(mesh, P(SHARD_AXIS))
    key = ("tenants", lanes, agg.fold_backend, agg.merge_mode, mesh_key)
    per_agg = agg.__dict__.setdefault("_plan_cache", {})
    if key in per_agg:
        return per_agg[key]

    jit_kw = {} if sharding is None else {"out_shardings": sharding}

    @partial(jax.jit, **jit_kw)
    def batch_init():
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (lanes,) + l.shape),
            agg.init(),
        )

    def _lane_fold(s, chunk, active):
        # Masked no-op lane: the fold still runs (static shapes, one
        # program) but an inactive lane's summary is selected back
        # bit-unchanged — the fairness contract's "no-op masked lane".
        s2 = agg.fold(s, chunk)
        return jax.tree.map(
            lambda new, old: jnp.where(active, new, old), s2, s
        )

    # The tenant-axis donation: steady-state tenant folds write the new
    # stacked summary into the old one's buffers (same contract as the
    # single-stream fold_step — the engine rebinds the state on every
    # call and snapshots only through `snapshot`, which never aliases).
    batch_fold = jax.jit(jax.vmap(_lane_fold), donate_argnums=0, **jit_kw)

    batch_fold_codec = None
    if agg.fold_compressed is not None:
        def _lane_fold_codec(s, payload, active):
            # Each lane folds its own [1, ...]-batched compressed payload
            # (the engine's stacked-unit contract at K=1, so the very
            # same fold_compressed serves both paths); inactive lanes
            # select back bit-unchanged like the raw masked lane.
            s2 = agg.fold_compressed(s, payload)
            return jax.tree.map(
                lambda new, old: jnp.where(active, new, old), s2, s
            )

        batch_fold_codec = jax.jit(
            jax.vmap(_lane_fold_codec), donate_argnums=0, **jit_kw
        )

    batch_merger = jax.jit(jax.vmap(agg.combine), **jit_kw)

    batch_transform = (
        jax.jit(jax.vmap(agg.transform), **jit_kw)
        if agg.transform is not None else None
    )

    if batch_transform is not None:
        snapshot_fn = batch_transform
    else:
        # Query snapshots must never alias the live (donated-into-next-
        # fold) state buffers: jnp.copy dispatched EAGERLY is a real
        # device copy — a jitted identity could alias its input.
        def snapshot_fn(s):
            return jax.tree.map(jnp.copy, s)

    batch_flatten = (
        jax.jit(jax.vmap(agg.flatten), **jit_kw)
        if agg.flatten is not None else None
    )

    plan = TenantPlan(
        init=batch_init, fold=batch_fold, merger=batch_merger,
        transform=batch_transform, snapshot=snapshot_fn,
        flatten=batch_flatten, lanes=lanes, fold_codec=batch_fold_codec,
    )
    per_agg[key] = plan
    return plan


def run_aggregation(
    agg: SummaryAggregation,
    stream,
    mesh=None,
    merge_every: int | None = None,
    window_ms: int | None = None,
    checkpoint_path: str | None = None,
    checkpoint_every: int = 1,
    resume: bool = False,
    prefetch_depth: int | None = None,
    device_fields: tuple[str, ...] | None = None,
    host_precombine: Callable | None = None,
    fold_batch: int = 1,
    ingest_workers: int | None = None,
    codec_workers: int | None = None,
    h2d_depth: int | None = None,
    allowed_lateness: int = 0,
    timer=None,
    source_provider=None,
    queries=None,
    precompressed: bool = False,
    windowed: int | None = None,
    ttl_panes: int | None = None,
) -> SummaryStream:
    """Execute ``agg`` over ``stream`` — the TPU ``run()``.

    ``merge_every`` (chunks) or ``window_ms`` (timestamp-tumbling) sets the
    merge/emit cadence; default is merge_every=1 (a merge after every chunk,
    the closest analog of the reference's per-window emission).

    ``prefetch_depth`` chunks of host ingest (parse/densify/H2D) overlap
    device folds on a background thread; 0 disables. Default (None) is
    ``max(2, ingest_workers)`` so the worker pool stays fed; an EXPLICIT
    value is honored exactly — it is the caller's bound on in-flight
    staged units (host/device memory ∝ depth × unit size), and capping it
    below the worker count deliberately idles workers for memory.

    ``device_fields`` names chunk fields to device_put on the prefetch
    thread (e.g. ``("src", "dst", "valid")`` for CC): the H2D of exactly
    the fields the fold reads then overlaps compute, while unused fields
    stay host-side (jit prunes dead args, so they are never transferred).

    ``host_precombine(chunk) -> chunk`` runs on the prefetch thread before
    staging — an ingest-side partial pre-aggregation (e.g.
    ``cc_host_precombine`` reduces each chunk to its spanning forest).
    Ignored in window mode: a pre-combiner may not preserve per-edge
    timestamps.

    ``checkpoint_path`` snapshots the global summary + stream position every
    ``checkpoint_every`` closed windows (the Merger's ListCheckpointed analog,
    M/SummaryAggregation.java:127-135); ``resume=True`` reloads it and skips
    the already-folded chunks.

    ``fold_batch`` groups up to that many chunks into one device dispatch
    (clamped to a divisor of ``merge_every``): the fold scans the stacked
    batch in a single program, amortizing per-dispatch latency. When the
    aggregation defines an ingest codec (``host_compress``/
    ``fold_compressed``), batches are compressed payload stacks instead of
    raw chunks — the high-throughput path on a bandwidth-limited
    host->device link. Sharded-codec floor: with a codec on S > 1 shards
    the payload batch axis is split across devices, so the effective batch
    is promoted to a multiple of S — in particular ``fold_batch=1`` with
    ``merge_every % S == 0`` silently becomes ``batch=S`` (S stacked
    payloads per dispatch: more per-dispatch host memory/latency than
    requested, but the only aligned batching).

    ``timer`` (a ``utils.metrics.StageTimer``) accumulates per-stage BUSY
    time: ``ingest_compress`` (codec worker pool), ``h2d`` (the dedicated
    transfer thread), ``fold_dispatch`` / ``merge_emit`` (consumer).
    Stages overlap, so their sum can exceed — and with a healthy pipeline
    total wall SHOULD undercut — the serial sum. Also exposed as
    ``stream.timer``.

    **Pipelined executor** (merge_every mode): the fold path runs as a
    three-stage pipeline —

      produce → [K codec workers: host compress]
              → [1 H2D thread: device_put chunk i+1 while chunk i folds]
              → [consumer: async fold dispatch]

    ``codec_workers`` (alias of ``ingest_workers``; passing both raises)
    sizes the compress pool; ``h2d_depth`` bounds the transferred units
    resident on device ahead of the fold (default 2 — classic double
    buffering; 0 stages transfers inline on the consumer). Fold state is
    donated (``donate_argnums``), so steady-state folds reallocate
    nothing, and the consumer synchronizes ONCE per merge window (the
    ``merge_emit`` block) instead of per chunk.

    **Observability**: install an ``obs.SpanTracer`` (``with
    gelly_tpu.obs.install(SpanTracer()): ...``) around the run and every
    pipeline unit records produce → compress (worker) → H2D (buffer
    slot) → fold spans with queue-depth and payload-size attribution;
    window closes, checkpoints, retries and injected faults land as
    spans/instant events; and a periodic heartbeat line reports eps,
    queue depths and the last-retired position. Export with
    ``obs.write_chrome_trace`` (Perfetto-loadable). Without a tracer the
    unit path performs ZERO extra observability work. Counters
    (units/chunks folded, windows closed, checkpoint bytes) land on
    ``obs.get_bus()`` either way.

    **Sharded source readers** (``source_provider``): pass a
    ``gelly_tpu.ingest.ShardedEdgeSource`` (or ``True`` to use
    ``stream.source``) and the produce-compress leg is replaced
    entirely — S reader lanes each parse their own byte range of the
    edge file AND run the compress stage on their own thread, handing
    COMPLETED units to the H2D/fold stages in the provider's
    deterministic merge order. There is no shared produce iterator
    left: a trace capture shows one ``compress/gelly-reader_<s>`` track
    per lane instead of a serial produce span train. Provider mode is
    merge_every-only (sharded ranges carry no global arrival order) and
    refuses ordered stackers (``stack_ordered`` codecs assign ids in
    global stream order, which sharded lanes cannot provide). Resume
    composes with the last-retired-chunk rule below: the provider maps
    the single recorded position onto per-shard seek offsets.

    **Fused multi-query execution** (``queries=[...]``): pass a list of
    query specs instead of ``agg`` and the engine fuses them into ONE
    plan (``engine/multiquery.py``): each chunk is produced, staged and
    transferred H2D exactly once and every query's fold runs inside the
    same compiled program — one fold dispatch per chunk regardless of
    Q. The returned stream is a
    :class:`~gelly_tpu.engine.multiquery.MultiQueryStream` (emission
    dicts keyed by query name + live per-query ``snapshot`` reads with
    a one-window staleness bound). Merge-every mode only; see the
    multiquery module docs for fusion eligibility.

    **Pre-compressed payload streams** (``precompressed=True``): the
    stream yields per-chunk COMPRESSED payloads (the plan's
    ``host_compress`` output — e.g. wire ``DATA_COMPRESSED`` frames a
    client compressed before send) instead of chunks. The staging
    workers then skip ``host_compress`` entirely: each unit is stacked
    (``stack_payloads``) and transferred as received, so a traced run
    shows ZERO ``compress`` spans — the per-unit staging work lands on
    a ``stack`` span/timer stage instead. The shared-compression-plane
    contract: a chunk is compressed once, at the producer, and every
    downstream consumer folds the compressed payload directly.
    Requires a plan whose codec can engage here (``host_compress`` +
    ``fold_compressed``, and a batch the mesh can align — the same
    rules as ``requires_codec``); merge_every mode only (payloads
    carry no per-edge timestamps), and ``host_precombine`` /
    ``source_provider`` are chunk-path knobs it refuses. The
    last-retired-chunk checkpoint rule counts payload units exactly
    like chunks, so exactly-once resume composes unchanged.

    **Sliding pane-ring windows** (``windowed=W``): the emission covers
    only the last W *panes* instead of the whole stream, where one pane
    is one merge window (``merge_every`` chunks — the pane size knob).
    Pane summaries live in a ring and the W-pane window is answered by
    a two-stack suffix aggregation (the FOO/DABA shape), so a pane
    close costs O(1) amortized ``combine`` dispatches — never a W-pane
    re-merge and never a replay — and the per-close cost scales with
    the PANE size, not the window length. ``ttl_panes=T`` (T >= W,
    compact-id plans only) adds per-vertex decay: each compact-id slot
    carries a last-seen pane stamp, and slots idle for T panes are
    evicted through the plan's ``windowed_evict`` hook at a pane
    boundary (the ``CompactIdSession`` rebuild), so steady-state device
    capacity is bounded by the ACTIVE vertex set, not the stream
    history. TTL requires a quiesced pipeline (``prefetch_depth=0,
    h2d_depth=0``): the session rebuild renumbers compact ids, which is
    only sound with no staged-but-unfolded assignments in flight. The
    returned stream is a :class:`WindowedStream`; ``snapshot()`` reads
    the latest ``{"window", "labels"}`` emission under a lock while the
    stream advances (one-pane staleness — the same contract as
    tenant/multiquery snapshots). Checkpoints snapshot the ring, the
    persistent id map and the TTL stamps under the same single recorded
    position (pane boundaries are the only checkpoint points in
    windowed mode), so exactly-once resume covers the ring + pane index
    bit-identically.

    **Exactly-once resume — the last-retired-chunk rule**: the recorded
    checkpoint position counts only chunks whose fold was *dispatched*
    (retired from the pipeline); units still in the compress/H2D double
    buffers are NOT counted. The snapshot's device_get barrier guarantees
    every retired fold is in the snapshot, so resume re-reads exactly the
    un-retired suffix — bit-identical to an uninterrupted run even when
    the crash lands with chunks in flight (stateful codec sessions are
    rebuilt from the restored summary via ``on_resume``, dropping any
    staged-but-unfolded assignments).
    """
    if queries is not None:
        # The fused multi-query entry point: compose the queries into
        # one MultiQueryPlan (engine/multiquery.py) so every question
        # rides ONE produce/compress/H2D leg and ONE fold dispatch per
        # chunk. The emission stream is wrapped in a MultiQueryStream
        # (live per-query snapshots) at the bottom of this function.
        if agg is not None:
            raise ValueError(
                "pass a single aggregation OR queries=[...], not both "
                "(queries are fused into one plan by engine.multiquery)"
            )
        from .multiquery import fuse

        agg = fuse(queries)
    if agg is None:
        raise ValueError("an aggregation is required (or pass queries=[...])")
    # Normalized QuerySpec tuple of a fused plan; None for plain plans.
    fused = getattr(agg, "queries", None) or None
    if merge_every is not None and window_ms is not None:
        raise ValueError("pass at most one of merge_every / window_ms")
    if allowed_lateness and window_ms is None:
        raise ValueError(
            "allowed_lateness requires window_ms (merge_every mode is "
            "count-based and does not reorder by timestamp)"
        )
    if merge_every is None and window_ms is None:
        merge_every = 1
    if agg.merge_degree is not None:
        d = agg.merge_degree
        if d <= 0 or (d & (d - 1)):
            raise ValueError(
                f"merge_degree must be a positive power of two, got {d}"
            )

    if source_provider is True:
        source_provider = getattr(stream, "source", None)
        if source_provider is None:
            raise ValueError(
                "source_provider=True needs a stream whose .source is a "
                "sharded provider (edge_stream_from_sharded_file); this "
                "stream has none"
            )
    if source_provider is not None:
        if not hasattr(source_provider, "stage_units"):
            raise ValueError(
                f"source_provider {type(source_provider).__name__} does "
                "not implement stage_units(stage_fn, batch, start, depth, "
                "cancel, gauge) — pass a gelly_tpu.ingest."
                "ShardedEdgeSource or an object with that protocol"
            )
        if window_ms is not None:
            raise ValueError(
                "source_provider is merge_every-only: sharded reader "
                "lanes have no global arrival order, so timestamp-"
                "tumbling windows cannot be formed from them"
            )
        if agg.stack_ordered:
            raise ValueError(
                f"aggregation '{agg.name}' uses an ordered stacker "
                "(stack_ordered codec session assigning ids in global "
                "stream order); sharded reader lanes compress "
                "concurrently with no global order — use the "
                "single-iterator path or a stateless codec"
            )
        if codec_workers is not None or ingest_workers is not None:
            raise ValueError(
                "codec_workers/ingest_workers size the prefetch_map "
                "compress pool, which a source_provider replaces "
                "entirely — the provider's shard count IS the lane "
                "count (e.g. ShardedEdgeSource(shards=...)); drop the "
                "worker knob or the provider"
            )
    if codec_workers is not None:
        if ingest_workers is not None:
            raise ValueError(
                "pass codec_workers or ingest_workers, not both (they are "
                "the same knob; codec_workers is the executor-facing name)"
            )
        ingest_workers = codec_workers
    if h2d_depth is None:
        h2d_depth = 2  # double buffer: chunk i+1 transfers while i folds
    if h2d_depth < 0:
        raise ValueError(f"h2d_depth must be >= 0, got {h2d_depth}")
    if ingest_workers is None:
        # One codec worker per AVAILABLE core (affinity/cgroup-aware, not
        # installed count): the native combiners release the GIL, so
        # staging units scale with cores — each worker owns whole units
        # (chunks are never split across workers), so per-worker combiner
        # hash tables stay private and there is no cross-worker eviction
        # thrash. On a single-core host this degenerates to one worker
        # (two workers there evict each other's tens-of-MB working sets
        # and run ~2-4x slower than one). Capped at 8: staged units hold
        # host payloads plus H2D device buffers, so an uncapped default
        # would scale peak staging memory linearly with core count on
        # large hosts — callers wanting more pass ingest_workers
        # explicitly (the explicit value is honored unbounded).
        ingest_workers = min(available_cores(), 8)
    if prefetch_depth is None:
        # Defaults track the (already-capped) worker count; an EXPLICIT
        # ingest_workers above the default cap gets the matching depth —
        # capping here too would permanently idle the extra workers.
        prefetch_depth = max(2, ingest_workers)

    # Pane-ring eligibility (PC4xx refusal matrix): the knobs a pane
    # ring composes with are exactly the merge_every pipeline's — every
    # incompatible axis is refused loudly here, never silently ignored.
    if windowed is None:
        windowed = getattr(agg, "windowed_panes", None)
    if ttl_panes is None:
        ttl_panes = getattr(agg, "windowed_ttl_panes", None)
    windowed_evict = getattr(agg, "windowed_evict", None)
    win_touched = getattr(agg, "windowed_touched", None)
    win_persist_init = getattr(agg, "windowed_persist_init", None)
    win_persist_update = getattr(agg, "windowed_persist_update", None)
    win_query_fixup = getattr(agg, "windowed_query_fixup", None)
    win_on_resume = getattr(agg, "on_resume_windowed", None)
    if windowed is not None:
        windowed = int(windowed)
        if windowed < 1:
            raise ValueError(f"windowed must be >= 1 pane, got {windowed}")
        if window_ms is not None:
            raise ValueError(
                "windowed panes ride the merge_every cadence (one pane "
                "per merge window, merge_every chunks each); event-time "
                "window_ms is a different cadence axis — size the pane "
                "with merge_every instead"
            )
        if fused:
            raise ValueError(
                f"fused plan '{agg.name}' cannot carry a pane ring: the "
                "ring combines ONE plan's pane summaries, and per-query "
                "cadences (QuerySpec.every) would desynchronize the "
                "shared pane boundary — run the windowed query as its "
                "own stream"
            )
        if agg.transient:
            raise ValueError(
                f"aggregation '{agg.name}' is transient (emit-and-reset "
                "Merger): its windows are already independent, so a "
                "pane ring over them has nothing to combine — drop "
                "windowed= or use a non-transient plan"
            )
        if source_provider is not None:
            raise ValueError(
                "windowed panes are single-iterator only: sharded "
                "reader lanes retire units in provider merge order, "
                "and the pane boundary (merge_every chunks) must land "
                "on the exactly-once stream order the ring checkpoints"
            )
        if precompressed:
            raise ValueError(
                "windowed panes refuse precompressed payload streams "
                "for now: pre-grouped STACKED units may straddle a "
                "pane boundary, which would fold one frame into two "
                "panes — feed raw chunks (the engine compresses "
                "per-pane)"
            )
        if agg.merge_mode == "delta" or agg.merge_delta is not None:
            raise ValueError(
                f"aggregation '{agg.name}' supplies a dirty-delta merge "
                "(merge_mode/merge_delta): the delta path folds dirty "
                "rows into a CARRIED global summary, but a pane ring "
                "retires panes — the two memory models are exclusive; "
                "use the windowed builder variant (merge_delta=None)"
            )
    if ttl_panes is not None:
        ttl_panes = int(ttl_panes)
        if windowed is None:
            raise ValueError(
                "ttl_panes requires windowed=W: TTL stamps are "
                "last-seen PANE indices, and eviction runs at pane "
                "boundaries — there is no pane clock without a ring"
            )
        if ttl_panes < windowed:
            raise ValueError(
                f"ttl_panes={ttl_panes} < windowed={windowed}: a slot "
                "must outlive the ring (T >= W) so an evicted id is "
                "guaranteed untouched in every live pane — otherwise "
                "eviction would rewrite panes that still reference it"
            )
        if windowed_evict is None or win_touched is None:
            raise ValueError(
                f"aggregation '{agg.name}' has no TTL eviction hooks "
                "(windowed_evict + windowed_touched): per-vertex decay "
                "needs a compact-id plan that can renumber its session "
                "— build one with connected_components(compact=..., "
                "windowed=W, ttl_panes=T)"
            )
        if prefetch_depth != 0 or h2d_depth != 0:
            raise ValueError(
                "ttl_panes needs a quiesced pipeline: pass "
                "prefetch_depth=0 and h2d_depth=0 so no compact-id "
                "assignment is staged but unfolded when the session "
                "renumbers at a pane boundary (in-flight payloads "
                "would still carry the OLD ids)"
            )
    m = mesh if mesh is not None else mesh_lib.make_mesh()
    S = mesh_lib.num_shards(m)
    if fused:
        if window_ms is not None:
            raise ValueError(
                f"fused plan '{agg.name}' is merge_every-only: per-query "
                "cadences (QuerySpec.every) count chunks, and event-time "
                "windows cannot mask the shared fused fold per query"
            )
        if host_precombine is not None:
            raise ValueError(
                "host_precombine rewrites the shared chunk for ONE "
                "query's benefit; a fused plan folds EVERY query from "
                "the same chunk — drop it (fold the pre-combine into "
                "that query's own fold instead)"
            )
        if S > 1 and any(not q.accum or q.every != 1 for q in fused):
            raise ValueError(
                f"fused plan '{agg.name}' carries a non-accumulating "
                "query (or a per-query merge window > 1): its in-fold "
                "merges are per-partition, so the fused plan is "
                f"single-shard — run on a 1-device mesh (S={S} here); "
                "scale out by sharding the TENANT axis via "
                "MultiTenantEngine(mesh=...) instead"
            )
    plan = _compiled_plan(agg, m)
    (fold_step, merge_locals, merger_step, locals0_fn,
     transform_fn, fold_many, fold_codec, delta_count_fn,
     merge_delta_for, flatten_fn) = plan

    if timer is None:
        from ..utils.metrics import StageTimer

        timer = StageTimer()

    # Window-mode codec (VERDICT r3 item 8; mesh form r4 item 5): the
    # tumbling iterator masks each chunk to ONE window before the fold,
    # so compressing the masked chunk needs no per-edge timestamps on the
    # wire — the payload is implicitly scoped to its window. On S > 1
    # shards the masked chunk splits into S host slices whose payloads
    # ride the same [S, 1, ...] batch-axis split as merge_every staging
    # (the reference's full-parallelism per-window fold,
    # M/SummaryBulkAggregation.java:78-83).
    use_codec = (
        agg.host_compress is not None
        and agg.fold_compressed is not None
    )
    # Effective batch: a divisor of merge_every so window boundaries align
    # with batch boundaries; on a sharded codec plan, also a multiple of S
    # (the payload batch axis is split across devices).
    batch = 1
    if window_ms is None:
        batch = max(1, min(fold_batch, merge_every))
        while merge_every % batch:
            batch -= 1
        if use_codec and S > 1:
            if batch % S:
                batch = S if merge_every % S == 0 else 1
            if batch % S:
                use_codec = False  # no aligned batching possible

    # The precompressed checks come FIRST: a stack_ordered plan must be
    # named for its ordered session, not for a batch-alignment detail.
    if precompressed:
        if window_ms is not None:
            raise ValueError(
                "precompressed=True is merge_every-only: codec payloads "
                "carry no per-edge timestamps to form event-time "
                "windows from"
            )
        if host_precombine is not None:
            raise ValueError(
                "host_precombine rewrites raw chunks; a precompressed "
                "stream carries codec payloads the producer already "
                "reduced — drop one of the two"
            )
        if source_provider is not None:
            raise ValueError(
                "source_provider parses raw edge files; a precompressed "
                "stream already carries codec payloads — drop one of "
                "the two"
            )
        if agg.stack_ordered:
            raise ValueError(
                f"aggregation '{agg.name}' uses an ordered stacker "
                "(stack_ordered): its codec session assigns compact "
                "ids in global stream order on THIS side, and its "
                "per-chunk host_compress ships raw edge views — a "
                "producer cannot meaningfully pre-compress for it; "
                "use a stateless codec (e.g. codec='sparse') on the "
                "wire"
            )
        if not use_codec:
            raise ValueError(
                f"precompressed=True needs a codec-capable plan: "
                f"'{agg.name}' must supply host_compress + "
                "fold_compressed (and the payload batch must align "
                f"with the {S}-shard mesh) so the pre-compressed "
                "payloads have a fold to land in"
            )
    if agg.requires_codec and not use_codec:
        raise ValueError(
            f"aggregation '{agg.name}' folds only through its ingest codec, "
            "but the codec cannot engage here: "
            f"merge_every={merge_every} cannot align a payload "
            f"batch with the {S}-shard mesh (make merge_every a "
            "multiple of the shard count)"
        )

    stats = {"late_edges": 0, "windows_closed": 0, "chunks": 0,
             "merge_modes": {"delta": 0, "replicated": 0}}

    # Queryable epoch snapshot holder (windowed mode): the latest
    # {window, labels} emission, readable under a lock while the stream
    # advances — published at every pane close, so a reader is at most
    # one pane stale (the tenant/multiquery snapshot contract).
    win_holder = None
    if windowed is not None:
        win_holder = {"lock": threading.Lock(), "val": None}

    # The accumulate plan (see SummaryAggregation.fold_accumulates): one
    # running summary, no per-window Merger combine. A pane ring opts
    # out: panes must fold from FRESH locals so each pane summary covers
    # exactly its own merge window (the ring supplies the accumulation).
    accum = (agg.fold_accumulates and not agg.transient and S == 1
             and windowed is None)

    def gen():
        if agg.on_run_start is not None:
            agg.on_run_start()
        # Observability bindings, resolved ONCE per run: `tracer` is None
        # unless an obs.SpanTracer is installed, and every span site below
        # is guarded by that None check — the disabled unit path performs
        # zero extra allocations (not even a clock read). The bus is
        # always on; it is only touched at unit/window cadence.
        tracer = obs_tracing.active_tracer()
        bus = obs_bus.get_bus()
        # Serving-plane telemetry (histograms + e2e watermarks), bound
        # ONCE per run under the same zero-cost-when-disabled contract
        # as the tracer: `telemetry` is False (and `wm` is None) unless
        # a tracer is installed or obs.bus.recording() is on, and every
        # recording site below is guarded by it — the disabled unit
        # path performs no histogram work, not even a clock read.
        telemetry = obs_bus.telemetry_on()
        wm = bus.watermarks if telemetry else None
        # Sharded-provider unit seqs are lane-interleaved
        # (``local_unit * shards + shard``, resume offset baked into the
        # lane starts — readers.stage_units), so ``skip_until + seq *
        # batch`` does NOT map onto consumption positions there: stamps
        # would land above the positions retire_fold/retire_durable ever
        # reach and read as permanent backlog. Provider-path stamps draw
        # dense positions from this allocator instead (staging order ≈
        # consumption order within the prefetch depth; every allocated
        # position is < total chunks, so all stamps retire).
        wm_alloc = None
        if wm is not None and source_provider is not None:
            _wm_lock = threading.Lock()
            _wm_next = [0]

            def wm_alloc() -> int:
                # skip_until is read at call time: it is final (resume
                # position loaded) before any unit is staged.
                with _wm_lock:
                    pos = skip_until + _wm_next[0]
                    _wm_next[0] += 1
                    return pos

        staged_hw = 0  # staged-depth high-water since the last beat
        # Per-query span attribution for fused plans: every fold span
        # names the queries riding the dispatch (the MultiQueryStream
        # wrapper adds the per-query window tracks).
        fold_attrs = (
            {"queries": ",".join(q.name for q in fused)} if fused else {}
        )
        hb = None
        meter = None
        if tracer is not None:
            from ..utils.metrics import ThroughputMeter

            meter = ThroughputMeter()
            if tracer.heartbeat_every_s is not None:
                from ..obs.heartbeat import Heartbeat

                hb = Heartbeat(tracer.heartbeat_every_s)
        # Ordered-wait baseline for this run (the codec session resets in
        # on_run_start, but sample rather than assume zero): the delta to
        # teardown is reclassified ingest_compress -> codec_wait.
        wait0 = (
            agg.ordered_wait_s() if agg.ordered_wait_s is not None else 0.0
        )
        # Fresh locals per run AND per window (never a shared ``locals0``
        # object): folds donate their summary argument, so a reused
        # initial summary would be consumed by the first fold that sees
        # it and poison every later window.
        locals_ = locals0_fn()
        global_summary = agg.init()
        current_window = None
        dirty = False  # locals hold edges not yet merged into a window result
        chunks_in_window = 0
        chunks_consumed = 0
        skip_until = 0
        windows_closed = 0
        last_ckpt_windows = 0

        # Pane-ring state (windowed=W): a ring of pane summaries
        # answered by two-stack suffix aggregation (core/windows.py),
        # plus the compact-plan sidecars — the persistent id map
        # (superset of every live pane's assignments) and the per-slot
        # TTL last-seen stamps.
        ring = None
        persist_vof = None
        last_seen = None
        if windowed is not None:
            from ..core.windows import PaneRing

            ring = PaneRing(
                windowed, merger_step,
                on_combine=lambda n: bus.inc(
                    "windows.combine_dispatches", n),
            )
            if win_persist_init is not None:
                persist_vof = win_persist_init()
            if ttl_panes is not None:
                last_seen = np.zeros(
                    int(persist_vof.shape[0]), dtype=np.int64
                )

        def _win_like():
            # Static checkpoint template: [W, ...] stacked pane leaves
            # (live panes padded with init panes at save time), so the
            # on-disk shape never depends on ring occupancy.
            panes = jax.tree.map(
                lambda l: jnp.zeros((windowed,) + l.shape, l.dtype),
                agg.init(),
            )
            like = {"panes": panes}
            if persist_vof is not None:
                like["persist"] = jnp.zeros_like(persist_vof)
            if last_seen is not None:
                like["last_seen"] = jnp.zeros(last_seen.shape, jnp.int64)
            return like

        lat_handle: dict = {}
        lat_state = None
        if resume:
            if not checkpoint_path:
                raise ValueError("resume=True requires checkpoint_path")
            from .checkpoint import load_checkpoint

            if windowed is not None:
                snap_in, skip_until, meta_in = load_checkpoint(
                    checkpoint_path, like=_win_like()
                )
                snap_in = jax.tree.map(jnp.asarray, snap_in)
                live_n = int(meta_in.get("ring_live", 0))
                panes = [
                    jax.tree.map(lambda l, i=i: l[i], snap_in["panes"])
                    for i in range(live_n)
                ]
                current_window = meta_in.get("current_window")
                windows_closed = last_ckpt_windows = meta_in.get(
                    "windows", 0)
                ring.reload(panes, windows_closed)
                if persist_vof is not None:
                    persist_vof = snap_in["persist"]
                if last_seen is not None:
                    last_seen = np.asarray(snap_in["last_seen"]).copy()
                if win_on_resume is not None:
                    # Rebuild the compact-id session from the PERSISTENT
                    # map — a superset of every live pane's assignments —
                    # never from any single pane (panes only record
                    # FIRST-seen rows).
                    win_on_resume(np.asarray(persist_vof))
            else:
                global_summary, skip_until, meta_in = load_checkpoint(
                    checkpoint_path, like=global_summary
                )
                global_summary = jax.tree.map(jnp.asarray, global_summary)
                if agg.on_resume is not None:
                    agg.on_resume(global_summary)
                current_window = meta_in.get("current_window")
                windows_closed = last_ckpt_windows = meta_in.get(
                    "windows", 0)
                if accum:
                    # The running summary IS the restored global: folds
                    # resume into it directly.
                    locals_ = global_summary
            if allowed_lateness:
                import os as _os

                # Position-stamped sidecar names make the pair crash-safe:
                # the sidecar for position P is written BEFORE the main
                # file advances to P, and sidecars for older positions are
                # pruned only AFTER the main os.replace succeeds — so
                # whichever position the main file holds, its matching
                # sidecar is on disk. The unstamped name is the legacy
                # (pre-stamping) format, still position-checked.
                side = f"{checkpoint_path}.lateness.{skip_until}"
                if not _os.path.exists(side):
                    side = checkpoint_path + ".lateness"
                if _os.path.exists(side):
                    flat, side_pos, side_meta = load_checkpoint(side)
                    if side_pos != skip_until:
                        raise ValueError(
                            f"lateness sidecar position {side_pos} does "
                            f"not match checkpoint position {skip_until} "
                            "(crash between the paired writes?) — the "
                            "reorder buffer cannot be restored "
                            "consistently"
                        )
                    nf = len(EdgeChunk._fields)
                    lat_state = {
                        "wins": side_meta["wins"],
                        "chunks": [
                            EdgeChunk(*flat[i * nf:(i + 1) * nf])
                            for i in range(len(side_meta["wins"]))
                        ],
                        "closed_upto": side_meta["closed_upto"],
                        "max_ts": side_meta["max_ts"],
                    }

        if wm is not None:
            # (Re)seed the e2e ledger at the exactly-once resume point:
            # after a crash the low watermark re-seeds from the RESUMED
            # POSITION's re-read time — never the wall clock, so
            # backlog age cannot time-travel across a SIGKILL.
            wm.seed("stream", skip_until)

        def publish_watermarks():
            # Backlog-age low watermark after a window close / durable
            # point. Without a checkpoint path the window close IS the
            # run's retirement point (there is no later durability),
            # so the ledger drains there.
            if wm is None:
                return
            if not checkpoint_path:
                wm.retire_durable("stream", chunks_consumed, bus=bus,
                                  prefix="engine")
            bus.gauge("engine.backlog_age_s",
                      round(wm.backlog_age("stream"), 6))

        def close_window():
            nonlocal locals_, global_summary, windows_closed, dirty
            if accum:
                global_summary = locals_  # carried across windows, no reset
                dirty = False
                windows_closed += 1
                stats["windows_closed"] = windows_closed
                bus.inc("engine.windows_closed")
                if tracer is not None:
                    tracer.instant("window_close", window=windows_closed,
                                   mode="accumulate")
                return (
                    transform_fn(global_summary)
                    if transform_fn else global_summary
                )
            # The cross-shard merge boundary: seeded FaultPlans can
            # raise/hang here (a collective that dies mid-window), the
            # same way they drive the native/H2D/step/checkpoint paths.
            faults_mod.inject("collective")
            merged = None
            mode = "replicated"
            if delta_count_fn is not None:
                # Measured per-window decision: one scalar D2H (the count)
                # sizes the gather bucket; the delta program fuses the
                # cross-shard merge and the Merger combine, so the close
                # moves S * bucket dirty rows instead of S full summaries.
                count = int(np.max(np.asarray(delta_count_fn(locals_))))
                # The measured count IS hooks-since-last-merge — the
                # per-window visibility the delta-merge crossover lever
                # needs (ROADMAP: merge_delta_auto_rows is a host-side
                # heuristic pending a measured sweep).
                bus.gauge("engine.window_dirty_rows", count)
                bucket = max(DELTA_MERGE_MIN_BUCKET,
                             1 << max(0, count - 1).bit_length())
                limit = agg.merge_delta_auto_rows
                if agg.merge_mode == "delta" or (
                    limit is not None and S * bucket <= limit
                ):
                    merged = merge_delta_for(bucket)(locals_, global_summary)
                    stats["merge_modes"]["delta"] += 1
                    bus.inc("engine.dirty_rows_gathered", S * bucket)
                    mode = "delta"
            if merged is None:
                # Replicated path (the reference Merger shape): full
                # cross-shard merge, then combine into the global summary.
                # Counted here — not in the delta-decision else — so
                # replicated-only plans (merge_mode="replicated", S == 1,
                # no merge_delta) report their merges too.
                window_summary = merge_locals(locals_)
                merged = merger_step(window_summary, global_summary)
                stats["merge_modes"]["replicated"] += 1
            if agg.transient:
                # Reference Merger with transientState: emit
                # combine(input, summary) then reset summary to the initial
                # value (M/SummaryAggregation.java:107-119). `init` must be
                # the combine identity. After a resume, global carries the
                # restored partial window and is folded into the first emit.
                out = merged
                global_summary = agg.init()
            else:
                global_summary = merged
                out = global_summary
            locals_ = locals0_fn()
            dirty = False
            windows_closed += 1
            stats["windows_closed"] = windows_closed
            bus.inc("engine.windows_closed")
            if tracer is not None:
                tracer.instant("window_close", window=windows_closed,
                               mode=mode)
            return transform_fn(out) if transform_fn else out

        def close_pane():
            # Pane boundary (windowed mode): capture this merge window's
            # summary from fresh locals, push it into the ring, decay
            # TTL slots, and answer the W-pane window by suffix
            # aggregation — O(1) amortized combine dispatches per close,
            # never a W-pane re-merge and never a replay.
            nonlocal locals_, windows_closed, dirty, persist_vof, \
                last_seen
            faults_mod.inject("collective")
            t_h = time.perf_counter() if telemetry else 0.0
            pane = merge_locals(locals_)
            # Rebind BEFORE the pane enters the ring: folds donate their
            # summary argument, so the captured pane buffer must never
            # be passed to a fold again.
            locals_ = locals0_fn()
            dirty = False
            if win_persist_update is not None:
                persist_vof = win_persist_update(persist_vof, pane)
            ring.push(pane)
            windows_closed += 1
            stats["windows_closed"] = windows_closed
            stats["ring_combines"] = ring.combines
            bus.inc("engine.windows_closed")
            bus.inc("windows.panes_closed")
            if last_seen is not None:
                touched = np.asarray(win_touched(pane))
                last_seen[touched] = windows_closed
                assigned = int(agg.session.assigned)
                stale = np.zeros(last_seen.shape[0], dtype=bool)
                if assigned:
                    stale[:assigned] = (
                        windows_closed - last_seen[:assigned]
                    ) >= ttl_panes
                if stale.any():
                    # T >= W guarantees a stale slot is untouched in
                    # every live pane, so the hook can renumber the
                    # survivors to a dense prefix (reclaiming session
                    # capacity) and remap each pane without losing any
                    # window-visible state.
                    n_evict = int(stale.sum())
                    panes_np = [
                        jax.tree.map(np.asarray, p)
                        for p in ring.export_panes()
                    ]
                    panes2, persist2, surv = windowed_evict(
                        panes_np, np.asarray(persist_vof), stale
                    )
                    persist_vof = jnp.asarray(persist2)
                    ls2 = np.zeros_like(last_seen)
                    ls2[:len(surv)] = last_seen[surv]
                    last_seen = ls2
                    ring.reload(
                        [jax.tree.map(jnp.asarray, p) for p in panes2],
                        ring.panes_closed,
                    )
                    bus.inc("windows.evicted_slots", n_evict)
                bus.gauge("windows.live_slots", int(agg.session.assigned))
            q = ring.query()
            if win_query_fixup is not None:
                q = win_query_fixup(q, persist_vof)
            out = transform_fn(q) if transform_fn else q
            bus.gauge("windows.ring_live", ring.live)
            if telemetry:
                bus.observe("windows.pane_close_ms",
                            (time.perf_counter() - t_h) * 1e3)
            if tracer is not None:
                tracer.instant("pane_close", window=windows_closed,
                               ring_live=ring.live,
                               combines=ring.combines)
            with win_holder["lock"]:
                win_holder["val"] = {"window": windows_closed,
                                     "labels": out}
            return out

        close_fn = close_pane if windowed is not None else close_window

        def maybe_checkpoint(force=False):
            # Chunk-boundary-only checkpoints: every consumed edge is in
            # global_summary or locals_ — or, with allowed_lateness, in
            # the reorder buffer, which is serialized to a ``.lateness``
            # sidecar so resume re-seeds it (no drops). The sidecar is
            # written FIRST; resume verifies both files carry the same
            # position, so a crash between the two writes is detected
            # loudly instead of silently dropping buffered edges.
            nonlocal last_ckpt_windows, locals_, global_summary
            if not checkpoint_path:
                return
            if not force and windows_closed - last_ckpt_windows < checkpoint_every:
                return
            last_ckpt_windows = windows_closed
            t_ck = tracer.now() if tracer is not None else 0.0
            # Cadenced path flatten (SummaryAggregation.flatten): bound
            # the transform chase depth the pair-sized folds and delta
            # merges let grow, exactly at the cadence the full-capacity
            # cost is already being paid (the snapshot's device_get).
            # The flattened summary REPLACES the live state — labels
            # are identical by the flatten contract.
            if flatten_fn is not None and windowed is None:
                if accum:
                    locals_ = flatten_fn(locals_)
                else:
                    global_summary = flatten_fn(global_summary)
            if windowed is not None:
                # Ring snapshot: live panes stacked onto the STATIC
                # [W, ...] template (padded with init panes), plus the
                # persistent id map and TTL stamps — one recorded
                # position covers the ring AND the pane index, and
                # windowed checkpoints only ever fire at pane
                # boundaries (the cadence check above trips right after
                # a close, before any chunk folds into the next pane).
                panes = ring.export_panes()
                pads = [agg.init() for _ in range(windowed - len(panes))]
                snap = {
                    "panes": jax.tree.map(
                        lambda *ls: jnp.stack(ls), *(panes + pads)
                    )
                }
                if persist_vof is not None:
                    snap["persist"] = persist_vof
                if last_seen is not None:
                    snap["last_seen"] = jnp.asarray(last_seen)
            elif accum:
                snap = locals_  # the running summary holds every edge
            else:
                snap = (
                    merger_step(merge_locals(locals_), global_summary)
                    if dirty
                    else global_summary
                )
            from .checkpoint import save_checkpoint

            if allowed_lateness and "export" in lat_handle:
                st = lat_handle["export"]()
                save_checkpoint(
                    f"{checkpoint_path}.lateness.{chunks_consumed}",
                    st["chunks"],
                    position=chunks_consumed,
                    meta={
                        "wins": [int(w) for w in st["wins"]],
                        "closed_upto": st["closed_upto"],
                        "max_ts": st["max_ts"],
                    },
                )
            t_wall = time.perf_counter()
            ck_meta = {
                "name": agg.name,
                "windows": windows_closed,
                "current_window": current_window,
            }
            if windowed is not None:
                ck_meta["ring_live"] = ring.live
                ck_meta["windowed"] = windowed
            save_checkpoint(
                checkpoint_path, snap, position=chunks_consumed,
                meta=ck_meta,
            )
            ck_bytes = obs_bus.publish_checkpoint(bus, "engine",
                                                  checkpoint_path,
                                                  t0=t_wall)
            if wm is not None:
                # The durability point: every position the checkpoint
                # covers retires from the e2e ledger (ingress→durable
                # histogram) and the low watermark advances.
                wm.retire_durable("stream", chunks_consumed, bus=bus,
                                  prefix="engine")
                bus.gauge("engine.backlog_age_s",
                          round(wm.backlog_age("stream"), 6))
            if tracer is not None:
                cctx = tracer.ctx(("fold", chunks_consumed))
                clink = ({"trace": cctx[0], "parent": cctx[1]}
                         if cctx is not None else {})
                tracer.span("checkpoint", "checkpoint", t_ck,
                            position=chunks_consumed,
                            windows=windows_closed, bytes=ck_bytes,
                            **clink)
            if allowed_lateness:
                # Only after the main write is durable: stale sidecars
                # (older positions, or the legacy unstamped name) are no
                # longer the matching pair for ANY reachable resume.
                import glob as _glob
                import os as _os

                keep = f"{checkpoint_path}.lateness.{chunks_consumed}"
                for old in _glob.glob(
                    _glob.escape(checkpoint_path) + ".lateness*"
                ):
                    if old != keep:
                        try:
                            _os.unlink(old)
                        except OSError:
                            pass

        from ..utils.prefetch import prefetch

        def counted_chunks():
            # Window-mode ingest: chunks stay host-side through the
            # prefetch queue — the tumbling iterator reads ts/valid per
            # chunk on the host, and jit prunes dead arguments at
            # dispatch so only the fields the fold actually reads are
            # transferred. (The merge_every path's precombine and
            # device_fields H2D staging live in stage_unit/h2d_unit;
            # this iterator feeds window mode only.)
            nonlocal chunks_consumed
            for chunk in prefetch(iter(stream), prefetch_depth):
                # In window mode checkpoints fire only here, at chunk
                # boundaries: every edge of the chunks counted so far is in
                # locals_ or global_summary, so the recorded position is
                # consistent. (Mid-chunk "close" events are not safe points:
                # the chunk's later-window edges are not folded yet.)
                if window_ms is not None and chunks_consumed > skip_until:
                    maybe_checkpoint()
                chunks_consumed += 1
                stats["chunks"] = chunks_consumed
                if chunks_consumed <= skip_until:
                    continue
                if wm is not None:
                    wm.stamp("stream", chunks_consumed - 1)
                yield chunk

        # Exact 0-based stream position of each produced unit's first
        # chunk (written before the unit is yielded, read by stage_unit
        # possibly on a worker thread — strictly happens-after). Needed
        # because pre-grouped stacked units make unit sizes VARIABLE,
        # so ``skip_until + seq * batch`` no longer reconstructs the
        # position; the provider path keeps its wm_alloc counter.
        unit_base: dict = {}

        def produced_units():
            # Batched producer for merge_every mode: groups of up to
            # ``batch`` host chunks, numbered in stream order (the seq
            # feeds ordered stackers). Resume-skipped chunks are dropped
            # here (they were consumed in the checkpointed run;
            # chunks_consumed starts at skip_until). A LIST stream item
            # is a pre-grouped staged unit — a STACKED wire frame
            # (``IngestServer.compressed_payload_units`` /
            # ``chunk_units``) — and is yielded as its own unit: one
            # fold dispatch per frame, never re-split or merged with
            # neighbouring chunks.
            idx = 0
            seq = 0
            group: list = []
            group_lo = 0
            it = iter(stream)
            t_unit = tracer.now() if tracer is not None else 0.0
            while True:
                with timer("ingest_chunks"):
                    chunk = next(it, None)
                if chunk is None:
                    break
                if isinstance(chunk, list):
                    # Pre-grouped unit. Flush the accumulated per-chunk
                    # group first (stream order is the fold order).
                    if group:
                        unit_base[seq] = group_lo
                        if tracer is not None:
                            tracer.span("produce", "produce", t_unit,
                                        unit=seq, chunks=len(group))
                        yield seq, group
                        seq += 1
                        group = []
                        if tracer is not None:
                            t_unit = tracer.now()
                    lo = idx
                    idx += len(chunk)
                    if idx <= skip_until:
                        continue  # whole unit folded pre-checkpoint
                    if lo < skip_until:
                        # Mid-frame resume: the checkpoint position
                        # landed INSIDE this frame. The wire re-delivers
                        # the covering frame; only the unseen suffix
                        # folds — the exactly-once contract at chunk
                        # granularity over frame-granularity redelivery.
                        chunk = chunk[skip_until - lo:]
                        lo = skip_until
                    if len(chunk) > batch:
                        raise ValueError(
                            f"stacked unit of {len(chunk)} chunks "
                            f"exceeds fold_batch {batch} — size the "
                            "consumer's fold_batch to at least the wire "
                            "stack size (client stack=K)"
                        )
                    unit_base[seq] = lo
                    if tracer is not None:
                        tracer.span("produce", "produce", t_unit,
                                    unit=seq, chunks=len(chunk))
                    yield seq, chunk
                    seq += 1
                    if tracer is not None:
                        t_unit = tracer.now()
                    continue
                idx += 1
                if idx <= skip_until:
                    continue
                if not group:
                    group_lo = idx - 1
                group.append(chunk)
                if len(group) == batch:
                    unit_base[seq] = group_lo
                    if tracer is not None:
                        tracer.span("produce", "produce", t_unit,
                                    unit=seq, chunks=batch)
                    yield seq, group
                    seq += 1
                    group = []
                    if tracer is not None:
                        t_unit = tracer.now()
            if group:
                unit_base[seq] = group_lo
                if tracer is not None:
                    tracer.span("produce", "produce", t_unit,
                                unit=seq, chunks=len(group))
                yield seq, group

        def _pad_group(group):
            # Pad the final partial batch to the static batch size so the
            # stacked shapes (and hence the compiled program) never change.
            if len(group) == batch:
                return group
            c0 = group[0].to_numpy()
            zero = EdgeChunk(*(np.zeros_like(f) for f in c0))
            return group + [zero] * (batch - len(group))

        identity_payload = None
        if use_codec:
            from ..core.chunk import make_chunk

            empty = make_chunk(
                np.zeros(0, np.int64), np.zeros(0, np.int64),
                capacity=1, device=False,
            )
            identity_payload = agg.host_compress(empty)
        # Precompressed streams skip host_compress entirely, so the
        # per-unit staging work is attributed to a ``stack`` span/timer
        # stage — a traced run proves structurally that the consumer
        # paid ZERO compress time for bytes the producer shipped
        # compressed.
        stage_span = "stack" if precompressed else "compress"
        stage_timer_name = (
            "ingest_stack" if precompressed else "ingest_compress"
        )

        def stage_unit(unit):
            # Pipeline stage 1 — HOST compress only (the K-worker pool):
            # builds the unit's host payload; the H2D transfer is stage 2
            # (h2d_unit, a dedicated thread), so compress of unit i+2,
            # transfer of unit i+1 and the fold of unit i all overlap.
            # The unit's trace context is its seq: the compress span here,
            # the H2D span (buffer slot) and the fold span all carry it,
            # so a stalled chunk is attributable end to end.
            seq, group = unit
            # Pop unconditionally — with telemetry off nothing else
            # would, and the map must not grow with the stream.
            unit_base_seq = unit_base.pop(seq, None)
            if wm is not None:
                # Ingress stamp at reader parse/staging time (both the
                # single-iterator and sharded-provider paths stage
                # through here). First-stamp-wins: a wire-receive stamp
                # for the same position is never overwritten. On the
                # single-iterator path unit seq × batch maps exactly
                # onto the exactly-once chunk positions the
                # fold/checkpoint will retire; provider seqs are
                # lane-interleaved, so their positions come from the
                # dense wm_alloc counter instead (see its definition).
                if wm_alloc is not None:
                    for _ in range(len(group)):
                        wm.stamp("stream", wm_alloc())
                else:
                    # Exact recorded base (variable-size stacked units
                    # broke the uniform seq × batch arithmetic).
                    base = unit_base_seq
                    if base is None:
                        base = skip_until + seq * batch
                    for j in range(len(group)):
                        wm.stamp("stream", base + j)
            try:
                faults_mod.inject("codec")
                t0 = tracer.now() if tracer is not None else 0.0
                payload, k = _stage_unit_inner(seq, group)
                edges = None
                if tracer is not None:
                    # Payload items carry no valid mask: edge attribution
                    # is a chunk-path extra the compressed wire forgoes.
                    edges = (
                        None if precompressed else _group_edges(group)
                    )
                    tracer.span(
                        stage_span,
                        f"{stage_span}/"
                        f"{threading.current_thread().name}",
                        t0, unit=seq, chunks=k, edges=edges,
                        payload_bytes=_payload_nbytes(payload),
                        queue_depth=bus.gauges.get(
                            "pipeline.staged_depth", 0),
                    )
                return payload, k, seq, edges
            except BaseException:
                # Release the unit's assignment turn so units parked
                # behind it in await_turn unwind instead of hanging the
                # pool at interpreter exit (the error itself still
                # propagates to the consumer via prefetch_map).
                if agg.stack_ordered and agg.on_stage_error is not None:
                    agg.on_stage_error(seq)
                raise

        def _stage_unit_inner(seq, group):
            k = len(group)
            if use_codec:
                with timer(stage_timer_name):
                    if precompressed:
                        # Producer-compressed payloads ride as-is: the
                        # stack/pad/mesh-split below is the ONLY staging
                        # work left on this side — plus the plan's id
                        # range check (payload_to_chunk parity: an
                        # out-of-range id must raise HERE, not silently
                        # drop/clamp in the device scatter).
                        payloads = [
                            jax.tree.map(np.asarray, p) for p in group
                        ]
                        if agg.codec_payload_check is not None:
                            for p in payloads:
                                agg.codec_payload_check(p)
                    else:
                        payloads = [agg.host_compress(c) for c in group]
                    if k < batch:
                        payloads += [identity_payload] * (batch - k)
                    if agg.stack_payloads is not None:
                        if agg.stack_ordered:
                            stacked = agg.stack_payloads(
                                payloads, max(S, 1), seq=seq
                            )
                        else:
                            stacked = agg.stack_payloads(
                                payloads, max(S, 1)
                            )
                    else:
                        stacked = jax.tree.map(
                            lambda *ls: np.stack(ls), *payloads
                        )
                    if S > 1:
                        # [K', ...] -> [S, K'/S, ...]: chunk-data-parallel
                        # split of the batch axis across devices (a
                        # combining stacker may have reduced K to K' =
                        # any multiple of S).
                        stacked = jax.tree.map(
                            lambda x: x.reshape(
                                (S, x.shape[0] // S) + x.shape[1:]
                            ),
                            stacked,
                        )
                return stacked, k
            with timer("ingest_compress"):
                if batch > 1:
                    group = [
                        host_precombine(c) if host_precombine else c
                        for c in group
                    ]
                    group = [c.to_numpy() for c in _pad_group(group)]
                    stacked = EdgeChunk(
                        *(np.stack(fs) for fs in zip(*group))
                    )
                    return stacked, k
                c = group[0]
                if host_precombine is not None:
                    c = host_precombine(c)
                return c, k

        def h2d_unit(staged):
            # Pipeline stage 2 — the double-buffered H2D leg: device_put
            # of unit i+1 is issued (and, with h2d_depth > 0, completed on
            # its own thread) while the fold of unit i is in flight. The
            # block lands HERE, not on the consumer, so the recorded h2d
            # time is the real transfer and the fold dispatch never waits
            # on an in-flight upload.
            payload, k, seq, edges = staged
            faults_mod.inject("h2d")
            t0 = tracer.now() if tracer is not None else 0.0
            with timer("h2d"):
                if use_codec:
                    if S > 1:
                        dev = mesh_lib.device_put_sharded_leading(m, payload)
                    else:
                        dev = jax.device_put(payload)
                    jax.block_until_ready(dev)
                elif device_fields:
                    dev = payload._replace(**{
                        f: jax.device_put(getattr(payload, f))
                        for f in device_fields
                    })
                    jax.block_until_ready(
                        [getattr(dev, f) for f in device_fields]
                    )
                else:
                    dev = payload
            if tracer is not None:
                # Slot attribution: which double buffer this unit landed
                # in (seq mod depth — the rotation the prefetch leg runs).
                slot = seq % h2d_depth if h2d_depth > 0 else 0
                tracer.span(
                    "h2d", f"h2d/slot{slot}", t0, unit=seq, chunks=k,
                    slot=slot,
                    queue_depth=bus.gauges.get("pipeline.h2d_depth", 0),
                )
            return dev, k, seq, edges

        if window_ms is not None:
            # Tumbling timestamp windows via the shared iterator
            # (core/windows.py): no-data windows never fire, late edges are
            # dropped+counted (ascending-ts contract, allowedLateness=0).
            from ..core.windows import tumbling_window_events

            try:
                win_seq = 0
                wm_unit = 0  # span unit id (window mode is consumer-serial)
                for kind, w, chunk, _n in tumbling_window_events(
                    counted_chunks(), window_ms, stats,
                    initial_window=current_window,
                    allowed_lateness=allowed_lateness,
                    state_handle=lat_handle, initial_state=lat_state,
                ):
                    if kind == "close":
                        t_merge = tracer.now() if tracer is not None else 0.0
                        t_h = time.perf_counter() if telemetry else 0.0
                        out = close_window()
                        if telemetry:
                            bus.observe("engine.merge_emit_ms",
                                        (time.perf_counter() - t_h) * 1e3)
                            wm.retire_fold("stream", chunks_consumed,
                                           bus=bus, prefix="engine")
                        if tracer is not None:
                            tracer.span("merge_emit", "merge_emit", t_merge,
                                        window=windows_closed)
                        publish_watermarks()
                        yield out
                    elif use_codec:
                        # The chunk is masked to window ``w``: compress it and
                        # fold the payload — the windowed wire rides the codec
                        # (the consumer loop is single-threaded, so stream
                        # order is the call order). On a mesh the chunk splits
                        # into S host slices, one payload row per device —
                        # the same batch-axis split as merge_every staging.
                        current_window = w
                        t0 = tracer.now() if tracer is not None else 0.0
                        with timer("ingest_compress"):
                            if S > 1:
                                parts = split_chunk_host(chunk, S)
                            else:
                                parts = [chunk]
                            payloads = [agg.host_compress(c) for c in parts]
                            if agg.stack_payloads is not None:
                                if agg.stack_ordered:
                                    stacked = agg.stack_payloads(
                                        payloads, S, seq=win_seq
                                    )
                                    win_seq += 1
                                else:
                                    stacked = agg.stack_payloads(payloads, S)
                            else:
                                stacked = jax.tree.map(
                                    lambda *ls: np.stack(
                                        [np.asarray(x) for x in ls]
                                    ),
                                    *payloads,
                                )
                            if S > 1:
                                stacked = jax.tree.map(
                                    lambda x: x.reshape(
                                        (S, x.shape[0] // S) + x.shape[1:]
                                    ),
                                    stacked,
                                )
                        if tracer is not None:
                            tracer.span("compress", "compress/window", t0,
                                        unit=wm_unit, window=int(w),
                                        payload_bytes=_payload_nbytes(stacked))
                            t0 = tracer.now()
                        with timer("h2d"):
                            if S > 1:
                                dev = mesh_lib.device_put_sharded_leading(
                                    m, stacked
                                )
                            else:
                                dev = jax.device_put(stacked)
                        if tracer is not None:
                            tracer.span("h2d", "h2d/slot0", t0, unit=wm_unit,
                                        slot=0)
                            t0 = tracer.now()
                        t_h = time.perf_counter() if telemetry else 0.0
                        with timer("fold_dispatch"):
                            locals_ = fold_codec(locals_, dev)
                        if telemetry:
                            bus.observe("engine.fold_dispatch_ms",
                                        (time.perf_counter() - t_h) * 1e3)
                        if tracer is not None:
                            tracer.span("fold", "fold", t0, unit=wm_unit,
                                        window=int(w))
                        wm_unit += 1
                        dirty = True
                    else:
                        current_window = w
                        t0 = tracer.now() if tracer is not None else 0.0
                        t_h = time.perf_counter() if telemetry else 0.0
                        locals_ = fold_step(locals_, chunk)
                        if telemetry:
                            bus.observe("engine.fold_dispatch_ms",
                                        (time.perf_counter() - t_h) * 1e3)
                        if tracer is not None:
                            tracer.span("fold", "fold", t0, unit=wm_unit,
                                        window=int(w))
                        wm_unit += 1
                        dirty = True
                # The iterator closes the final partial window itself; just make
                # sure the last state is durably checkpointed.
                if checkpoint_path and stats["windows_closed"]:
                    maybe_checkpoint(force=True)
            finally:
                # Stage accounting lands on the registry on ANY
                # exit — normal end, error, or the consumer
                # abandoning the emission stream mid-window (same
                # contract as the pipeline branch's teardown).
                timer.publish(bus)
        else:
            chunks_consumed = skip_until
            if use_codec:
                fold_unit = fold_codec
            elif batch > 1:
                fold_unit = fold_many
            else:
                fold_unit = fold_step
            from ..utils.prefetch import prefetch_map

            # The pipelined executor: compress on K workers, H2D on its
            # own thread (h2d_depth in-flight device buffers), folds
            # dispatched asynchronously by this consumer. The only
            # consumer-side synchronization is the merge_emit block at
            # each window close — steady-state folds neither block nor
            # allocate (state is donated).
            pipe_cancel = threading.Event()
            # Queue-depth gauges ride the prefetch enqueue hook only when
            # tracing (the bus write per unit is cheap, but the disabled
            # path stays contractually untouched).
            staged_gauge = h2d_gauge = None
            if tracer is not None:
                staged_gauge = lambda d: bus.gauge(  # noqa: E731
                    "pipeline.staged_depth", d)
                h2d_gauge = lambda d: bus.gauge(  # noqa: E731
                    "pipeline.h2d_depth", d)
            if source_provider is not None:
                # Sharded reader lanes: parse + compress run per-lane on
                # the provider's threads; the engine's stage closure is
                # handed over so codec/batch/precombine semantics (and
                # the compress spans, now on gelly-reader_<s> tracks)
                # stay identical to the single-iterator path.
                staged = source_provider.stage_units(
                    stage_unit, batch=batch, start=skip_until,
                    depth=prefetch_depth, cancel=pipe_cancel,
                    gauge=staged_gauge,
                )
            else:
                staged = prefetch_map(
                    stage_unit, produced_units(), depth=prefetch_depth,
                    workers=ingest_workers, cancel=pipe_cancel,
                    gauge=staged_gauge,
                )
            transferred = map(h2d_unit, staged)
            if h2d_depth > 0:
                transferred = prefetch(transferred, depth=h2d_depth,
                                       gauge=h2d_gauge)
            transferred = iter(transferred)
            try:
                while True:
                    # The fold dispatcher's wait for a staged, transferred
                    # unit (the reader, codec and H2D stages behind it).
                    with timer("consumer_wait"):
                        item = next(transferred, None)
                    if item is None:
                        break
                    unit, k, seq, edges = item
                    # Last-retired-chunk rule: a chunk counts toward the
                    # checkpoint position exactly when its fold is
                    # dispatched here; units still in the compress/H2D
                    # buffers are re-read on resume.
                    chunks_consumed += k
                    stats["chunks"] = chunks_consumed
                    t_fold = tracer.now() if tracer is not None else 0.0
                    t_h = time.perf_counter() if telemetry else 0.0
                    with timer("fold_dispatch"):
                        locals_ = fold_unit(locals_, unit)
                    bus.inc("engine.units_folded")
                    bus.inc("engine.chunks_folded", k)
                    if telemetry:
                        bus.observe("engine.fold_dispatch_ms",
                                    (time.perf_counter() - t_h) * 1e3)
                        staged_hw = max(staged_hw, bus.gauges.get(
                            "pipeline.staged_depth", 0))
                        wm.retire_fold("stream", chunks_consumed,
                                       bus=bus, prefix="engine")
                    if tracer is not None:
                        # Causal link to the wire: the server's staging
                        # bound each chunk position to its frame's
                        # trace context (ingest/server.py); the unit's
                        # first position carries it onto the fold span,
                        # and the fold frontier is re-bound under a
                        # distinct key so the covering checkpoint/merge
                        # can pick the chain up without clobbering
                        # staging bindings for incoming positions.
                        fctx = tracer.ctx(chunks_consumed - k)
                        fold_sid = tracer.next_span_id()
                        link = ({"trace": fctx[0], "parent": fctx[1]}
                                if fctx is not None else {})
                        tracer.span("fold", "fold", t_fold, unit=seq,
                                    chunks=k, edges=edges, span=fold_sid,
                                    **link, **fold_attrs)
                        tracer.bind_ctx(
                            ("fold", chunks_consumed),
                            fctx[0] if fctx is not None else tracer.trace_id,
                            fold_sid)
                        if edges:
                            meter.record(edges)
                            bus.inc("engine.edges_folded", edges)
                            meter.publish(bus, prefix="engine.throughput")
                        if hb is not None and hb.due():
                            # due() guards the field building: per-unit
                            # heartbeat cost is one clock compare.
                            hb.tick(
                                position=chunks_consumed,
                                eps=meter.snapshot()["edges_per_sec"],
                                windows=windows_closed,
                                staged_depth=bus.gauges.get(
                                    "pipeline.staged_depth", 0),
                                h2d_depth=bus.gauges.get(
                                    "pipeline.h2d_depth", 0),
                                # The serving-plane signals: staged
                                # high-water since the last beat, p99
                                # fold dispatch, worst backlog age.
                                staged_hw=staged_hw,
                                fold_p99_ms=round(bus.quantile(
                                    "engine.fold_dispatch_ms", 0.99), 3),
                                backlog_age_max_s=round(
                                    bus.watermarks.max_backlog_age(), 3),
                                slo_breaching=int(bus.gauges.get(
                                    "slo.breaching", 0)),
                            )
                            staged_hw = 0
                    chunks_in_window += k
                    dirty = True
                    if chunks_in_window >= merge_every:
                        t_merge = (tracer.now() if tracer is not None
                                   else 0.0)
                        t_h = (time.perf_counter() if telemetry
                               else 0.0)
                        with timer("merge_emit"):
                            out = close_fn()
                            # The window's ONE completion barrier: the
                            # emission (and with it every fold of the
                            # window) is ready before it is yielded.
                            jax.block_until_ready(out)
                        if telemetry:
                            bus.observe("engine.merge_emit_ms",
                                        (time.perf_counter() - t_h) * 1e3)
                        if tracer is not None:
                            mctx = tracer.ctx(("fold", chunks_consumed))
                            mlink = ({"trace": mctx[0], "parent": mctx[1]}
                                     if mctx is not None else {})
                            tracer.span("merge_emit", "merge_emit",
                                        t_merge, window=windows_closed,
                                        **mlink)
                        chunks_in_window = 0
                        publish_watermarks()
                        yield out
                    maybe_checkpoint()
                if dirty:
                    t_merge = tracer.now() if tracer is not None else 0.0
                    t_h = time.perf_counter() if telemetry else 0.0
                    with timer("merge_emit"):
                        out = close_fn()
                        jax.block_until_ready(out)
                    if telemetry:
                        bus.observe("engine.merge_emit_ms",
                                    (time.perf_counter() - t_h) * 1e3)
                    if tracer is not None:
                        mctx = tracer.ctx(("fold", chunks_consumed))
                        mlink = ({"trace": mctx[0], "parent": mctx[1]}
                                 if mctx is not None else {})
                        tracer.span("merge_emit", "merge_emit", t_merge,
                                    window=windows_closed, final=True,
                                    **mlink)
                    publish_watermarks()
                    yield out
                    maybe_checkpoint(force=True)
            finally:
                # Tear the pipeline down outermost-first on ANY exit —
                # normal end, error, or the caller abandoning the
                # emission generator mid-stream. ``pipe_cancel`` goes
                # FIRST: the H2D prefetch thread may be parked inside
                # ``staged.__next__`` on a stalled source, where a
                # generator close cannot reach it ("generator already
                # executing") — the event ends that parked get within
                # one poll, making the closes below deterministic rather
                # than best-effort, so abandoning the emission stream can
                # never leave compress workers consuming the source (and
                # advancing a stateful codec session) in the background.
                import time as _time

                pipe_cancel.set()
                close = getattr(transferred, "close", None)
                if close is not None:
                    close()
                deadline = _time.monotonic() + 2.0
                while True:
                    try:
                        staged.close()
                        break
                    except ValueError:
                        if _time.monotonic() >= deadline:
                            break  # daemon threads; cancel backstop
                        _time.sleep(0.01)
                if agg.ordered_wait_s is not None:
                    # Compress workers are torn down: move the turn-wait
                    # they accrued this run out of the compress stage —
                    # await_turn blocks INSIDE the ingest_compress timer
                    # context, and with K workers that wait would read as
                    # busy compress time in the overlap accounting.
                    timer.reattribute(
                        "ingest_compress", "codec_wait",
                        agg.ordered_wait_s() - wait0,
                    )
                # Stage accounting lands on the registry at teardown so
                # bench/tests read busy seconds off the bus without
                # holding the timer object.
                timer.publish(bus)

    if windowed is not None:
        out_stream = WindowedStream(gen, win_holder)
    else:
        out_stream = SummaryStream(gen)
    out_stream.stats = stats
    out_stream.timer = timer
    if fused:
        from .multiquery import MultiQueryStream

        out_stream = MultiQueryStream(out_stream, agg)
    return out_stream
