"""Fused multi-query execution: one shared ingest pipeline, N questions.

The r05 capture shows the wall is ingest, not compute: host compress
5.36s + H2D 2.51s against a 0.0009s fold dispatch on
``streaming_cc_large``. Every additional aggregation folded over the
*same* edge stream is therefore nearly free — if it shares the
produce/compress/H2D leg instead of re-running it. That sharing is the
reference's own execution model (one ``SimpleEdgeStream``, many
summaries: CC, degrees, bipartiteness, spanner — PAPER.md §L1), and the
natural serving shape for "millions of users asking different questions
of one traffic stream".

:func:`fuse` composes Q heterogeneous :class:`~gelly_tpu.engine.
aggregation.SummaryAggregation` plans into ONE
:class:`MultiQueryPlan` — itself a ``SummaryAggregation`` whose summary
is a dict of per-query summaries (plus a fold-step counter leaf). The
fused fold applies every query's fold to the SAME chunk inside one
compiled program, so the whole engine carries it unchanged:

- **Pipelined executor**: each chunk is produced, staged and
  transferred H2D exactly once; the fold dispatch count per chunk is 1
  regardless of Q (``run_aggregation(queries=[...])`` or
  ``stream.aggregate(None, queries=[...])``).
- **Per-query merge windows**: a non-accumulating query (e.g. the
  spanner, whose cross-window merge is the reference's
  ``CombineSpanners``) carries ``{local, global}`` sub-state and its
  merge runs INSIDE the fused fold as a masked no-op sub-fold — the
  same ``jnp.where``-select machinery as the tenant engine's masked
  lanes — firing only when the query's own ``QuerySpec.every`` window
  closes. Accumulating queries (CC forests, degree vectors, parity
  forests) carry one running summary, exactly like their standalone
  accumulate plans.
- **Checkpointing**: the fused state is one pytree, so the engine's
  existing exactly-once machinery snapshots every query's leaves in
  ONE rotation at ONE position (the last-retired-chunk rule; the step
  counter rides the same snapshot, so masked merge windows resume
  bit-identically — ``tests/_multiquery_crash_child.py`` proves it
  under SIGKILL).
- **Live snapshots** (:class:`MultiQueryStream`): per-query reads off
  the last closed window's emission dict, staleness bounded by one
  merge window, lock held only for the reference swap.
- **Multi-tenant tiers**: a ``MultiQueryPlan`` is a valid tier plan for
  :class:`~gelly_tpu.engine.tenants.MultiTenantEngine` — N tenants
  × Q queries ride one vmapped donated dispatch.

Fusion eligibility (refused loudly at :func:`fuse` time):

- plans whose codec is a STATEFUL ordered stacker (``stack_ordered``:
  the compact-id session consumes payloads in global stream order,
  which the shared per-chunk compress stage cannot provide);
- ``requires_codec`` plans in a set whose shared codec cannot engage
  (see below) — their raw fold does not exist;
- ``transient`` plans — their emit-and-reset window contract needs the
  engine's Merger path, which the fused accumulate plan bypasses;
- host-side transforms (``jit_transform=False``) — fused emissions are
  one jitted dict program;
- mismatched chunk schemas: queries declaring different
  ``slot_capacity`` read the same shared chunk, and a query built for
  a smaller slot space would silently mis-index it (JAX clamps);
- per-query ``every`` > 1 on an accumulating plan (no merge window to
  defer) and duplicate / reserved query names.

**Fused codec sharing** (the shared compression plane): when EVERY
query supplies a stateless ingest codec (``host_compress`` +
``fold_compressed``) and accumulates, the fused plan grows its own
shared compress stage — ONE multi-query compressed payload per chunk
(``{query_name: per-query payload}``), staged and transferred H2D
once, with every query's ``fold_compressed`` running inside the one
fused dispatch. The ~0.25 B/edge codec wire win then covers fused
runs too. Build the sub-queries with their codecs on (the library
``*_query`` helpers' ``compressed=True``); mixed or non-accumulating
sets fall back to the raw-chunk fused fold (per-query masked merge
windows need the per-chunk raw fold — a K-stacked payload dispatch
cannot interleave fold and merge at chunk grain). ``share_codec``
forces the decision: ``True`` refuses sets the codec cannot cover,
``False`` pins the raw path, ``"auto"`` (default) engages when
eligible.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import bus as obs_bus
from ..obs import tracing as obs_tracing
from .aggregation import SummaryAggregation, SummaryStream

# Reserved leaf: the fused fold's chunk counter (drives the masked
# per-query merge windows; rides the checkpoint like any other leaf).
STEP_KEY = "_step"


class QuerySpec(NamedTuple):
    """One query riding the fused plan.

    - ``name`` — key of this query's summary/emission in the fused
      dicts (unique per plan; ``"_step"`` is reserved).
    - ``agg`` — the query's ``SummaryAggregation`` (raw fold; see the
      module eligibility rules).
    - ``every`` — merge-window cadence in CHUNKS for non-accumulating
      plans: the query's ``combine(local, global)`` sub-fold fires on
      every ``every``-th fused fold and is a masked no-op otherwise.
      Must be 1 for accumulating plans (nothing to defer).
    - ``slot_capacity`` — optional declared vertex slot-space size;
      ``fuse`` refuses to mix differing declared capacities (the
      queries read the same shared chunk).
    """

    name: str
    agg: SummaryAggregation
    every: int = 1
    slot_capacity: int | None = None

    @property
    def accum(self) -> bool:
        return self.agg.fold_accumulates and not self.agg.transient


@dataclasses.dataclass(eq=False)
class MultiQueryPlan(SummaryAggregation):
    """The fused plan: a ``SummaryAggregation`` over the dict-of-
    summaries state, built by :func:`fuse`. ``queries`` holds the
    normalized :class:`QuerySpec` tuple; everything else is the
    standard plugin contract, so the engine (and the tenant engine)
    need no new physical plan."""

    queries: tuple = ()

    @property
    def query_names(self) -> tuple:
        return tuple(q.name for q in self.queries)


def _as_spec(q) -> QuerySpec:
    if isinstance(q, QuerySpec):
        return q
    if isinstance(q, SummaryAggregation):
        return QuerySpec(name=q.name, agg=q)
    if isinstance(q, tuple) and len(q) == 2:
        return QuerySpec(name=q[0], agg=q[1])
    raise ValueError(
        f"cannot fuse {type(q).__name__}: pass a QuerySpec, a "
        "SummaryAggregation, or a (name, aggregation) pair"
    )


def fuse(queries, *, name: str | None = None,
         share_codec="auto") -> MultiQueryPlan:
    """Stack Q heterogeneous aggregations into one fused plan.

    ``queries`` — iterable of :class:`QuerySpec` /
    ``SummaryAggregation`` / ``(name, aggregation)`` pairs. Returns a
    :class:`MultiQueryPlan` whose fold advances EVERY query from the
    same chunk in one compiled program; run it through
    ``run_aggregation(queries=...)`` (which wraps the emission stream
    in a :class:`MultiQueryStream`) or hand it to
    ``MultiTenantEngine.add_tier`` as a tier plan.

    ``share_codec`` — the fused-codec knob (module docs): ``"auto"``
    engages the shared compress stage when every query is
    codec-capable and accumulating, ``True`` REQUIRES it (ValueError
    otherwise), ``False`` pins the raw-chunk fused fold.
    """
    # Identity checks, not membership: 1 == True under `in`, but the
    # strictness branches below test `is True` / `is False` — an int
    # must not silently demote to "auto" semantics.
    if not (share_codec is True or share_codec is False
            or share_codec == "auto"):
        raise ValueError(
            f"share_codec must be 'auto', True or False, got "
            f"{share_codec!r}"
        )
    specs = [_as_spec(q) for q in queries]
    if not specs:
        raise ValueError("fuse needs at least one query")
    seen: set = set()
    caps: dict = {}
    for q in specs:
        if not isinstance(q.agg, SummaryAggregation):
            raise ValueError(
                f"query {q.name!r}: agg must be a SummaryAggregation, "
                f"got {type(q.agg).__name__}"
            )
        if isinstance(q.agg, MultiQueryPlan):
            raise ValueError(
                f"query {q.name!r} is already a fused MultiQueryPlan — "
                "pass its sub-queries instead of nesting fusions"
            )
        if not q.name or q.name == STEP_KEY:
            raise ValueError(
                f"query name {q.name!r} is empty or reserved "
                f"({STEP_KEY!r} is the fused step-counter leaf)"
            )
        if q.name in seen:
            raise ValueError(f"duplicate query name {q.name!r}")
        seen.add(q.name)
        if q.agg.stack_ordered:
            raise ValueError(
                f"query {q.name!r} ({q.agg.name}) uses an ordered "
                "stacker (stack_ordered: its codec session assigns "
                "compact ids in GLOBAL STREAM order); the fused "
                "shared-compress stage compresses every query from the "
                "same chunk with no cross-query ordering to offer — "
                "build the query on a stateless codec (codec='sparse') "
                "or the raw fold (ingest_combine=False)"
            )
        if q.agg.transient:
            raise ValueError(
                f"query {q.name!r} ({q.agg.name}) is transient "
                "(emit-and-reset windows); the fused accumulate plan "
                "has no per-window Merger to reset through — un-fusable"
            )
        windowed_panes = getattr(q.agg, "windowed_panes", None)
        if windowed_panes is not None:
            raise ValueError(
                f"query {q.name!r} ({q.agg.name}) carries a pane ring "
                f"(windowed_panes={windowed_panes}): the ring's "
                "two-stack suffix aggregation and TTL session rebuilds "
                "are single-stream host structures the shared fused "
                "fold cannot mask per query — run the windowed query "
                "as its own stream (windowed= on run_aggregation)"
            )
        if q.agg.transform is not None and not q.agg.jit_transform:
            raise ValueError(
                f"query {q.name!r} ({q.agg.name}) uses a host-side "
                "transform (jit_transform=False); fused emissions are "
                "one jitted dict program — un-fusable"
            )
        if not isinstance(q.every, int) or q.every < 1:
            raise ValueError(
                f"query {q.name!r}: every must be an int >= 1, got "
                f"{q.every!r}"
            )
        if q.accum and q.every != 1:
            raise ValueError(
                f"query {q.name!r} ({q.agg.name}) accumulates "
                "(fold_accumulates); it has no merge window to defer — "
                "every must be 1"
            )
        if q.slot_capacity is not None:
            caps[q.name] = int(q.slot_capacity)
    if len(set(caps.values())) > 1:
        raise ValueError(
            "mismatched chunk schemas: fused queries read the SAME "
            "shared chunk but declare different slot capacities "
            f"({caps}) — a query built for a smaller slot space would "
            "silently mis-index it (JAX clamps out-of-range ids)"
        )
    specs = tuple(specs)
    plan_name = name or "multiquery(" + "+".join(q.name for q in specs) + ")"

    # Fused codec sharing: engages only when EVERY query supplies a
    # stateless codec AND accumulates — a non-accumulating query's
    # masked merge window fires at CHUNK grain inside the raw fold,
    # which a K-stacked payload dispatch cannot interleave with.
    codec_capable = [
        q for q in specs
        if q.agg.host_compress is not None
        and q.agg.fold_compressed is not None
    ]
    codec_ok = len(codec_capable) == len(specs) and all(
        q.accum for q in specs
    )
    use_codec = codec_ok and share_codec in ("auto", True)
    if share_codec is True and not codec_ok:
        raise ValueError(
            "share_codec=True but the shared compress stage cannot "
            "cover this set: every query must supply host_compress + "
            "fold_compressed AND accumulate (codec-capable: "
            f"{[q.name for q in codec_capable]} of "
            f"{[q.name for q in specs]}; non-accumulating: "
            f"{[q.name for q in specs if not q.accum]}) — build the "
            "sub-queries with compressed=True, or drop share_codec"
        )
    codec_only = [q.name for q in specs if q.agg.requires_codec]
    if codec_only and not use_codec:
        raise ValueError(
            f"queries {codec_only} fold ONLY through their ingest "
            "codec (requires_codec) but the fused shared-compress "
            "stage is not engaged here"
            + (" (share_codec=False pins the raw path)"
               if share_codec is False else
               ": every fused query must be codec-capable and "
               "accumulating for it to engage")
            + " — their raw fold does not exist, so the set is "
            "un-fusable as-is"
        )

    def init():
        st: dict = {STEP_KEY: jnp.zeros((), jnp.int64)}
        for q in specs:
            if q.accum:
                st[q.name] = q.agg.init()
            else:
                st[q.name] = {"local": q.agg.init(),
                              "global": q.agg.init()}
        return st

    def fold(state, chunk):
        step = state[STEP_KEY] + 1
        out: dict = {STEP_KEY: step}
        for q in specs:
            if q.accum:
                out[q.name] = q.agg.fold(state[q.name], chunk)
                continue
            sub = state[q.name]
            local = q.agg.fold(sub["local"], chunk)
            # The per-query merge window as a masked no-op sub-fold
            # (the tenant engine's masked-lane machinery): the merge
            # is computed every chunk but SELECTED in only when this
            # query's own window closes — one program, no host
            # branching, vmap-safe under a tenant tier.
            boundary = (step % q.every) == 0
            merged = q.agg.combine(local, sub["global"])
            fresh = q.agg.init()
            out[q.name] = {
                "local": jax.tree.map(
                    lambda f, l: jnp.where(boundary, f, l), fresh, local
                ),
                "global": jax.tree.map(
                    lambda m, g: jnp.where(boundary, m, g),
                    merged, sub["global"],
                ),
            }
        return out

    def combine(a, b):
        # Cross-partition merge of fused states (per-query combine,
        # component-wise over the local/global sub-states). Sound for
        # accumulating sub-queries only — which is exactly the shape
        # run_aggregation admits at S > 1 (non-accum queries are
        # refused there: their in-fold merges are per-partition).
        out: dict = {STEP_KEY: jnp.maximum(a[STEP_KEY], b[STEP_KEY])}
        for q in specs:
            if q.accum:
                out[q.name] = q.agg.combine(a[q.name], b[q.name])
            else:
                out[q.name] = {
                    "local": q.agg.combine(a[q.name]["local"],
                                           b[q.name]["local"]),
                    "global": q.agg.combine(a[q.name]["global"],
                                            b[q.name]["global"]),
                }
        return out

    def transform(state):
        out: dict = {}
        for q in specs:
            if q.accum:
                view = state[q.name]
            else:
                # Merge-on-read: the emission always includes the
                # un-merged window tail, matching the standalone
                # plan's close-at-emission semantics (at a boundary,
                # local is freshly reset and combine(init, g) == g by
                # the Merger identity contract).
                view = q.agg.combine(state[q.name]["local"],
                                     state[q.name]["global"])
            if q.agg.transform is not None:
                out[q.name] = q.agg.transform(view)
            else:
                out[q.name] = view
        return out

    fused_host_compress = None
    fused_stack_payloads = None
    fused_fold_compressed = None
    fused_payload_check = None
    if use_codec:
        def fused_host_compress(chunk):
            # ONE multi-query compressed payload per chunk: each query's
            # own codec reduces the SAME chunk, and the dict rides the
            # pipeline as one unit — one staging pass, one H2D, one
            # fused dispatch. (The engine's empty identity chunk is not
            # a stream chunk; keep the counter honest.)
            if bool(np.any(np.asarray(chunk.valid))):
                obs_bus.get_bus().inc("multiquery.compressed_chunks")
            return {q.name: q.agg.host_compress(chunk) for q in specs}

        def fused_stack_payloads(payloads: list, groups: int = 1) -> dict:
            out: dict = {}
            for q in specs:
                subs = [p[q.name] for p in payloads]
                if q.agg.stack_payloads is not None:
                    out[q.name] = q.agg.stack_payloads(subs, groups)
                else:
                    out[q.name] = jax.tree.map(
                        lambda *ls: np.stack(
                            [np.asarray(x) for x in ls]
                        ),
                        *subs,
                    )
            return out

        def fused_payload_check(payload):
            # Producer-payload validation fans out per query (each
            # codec knows its own id ranges); a payload compressed by
            # a DIFFERENT fused set is named here, not at a KeyError
            # inside the fold.
            missing = [q.name for q in specs
                       if not isinstance(payload, dict)
                       or q.name not in payload]
            if missing:
                raise ValueError(
                    f"fused compressed payload is missing per-query "
                    f"sub-payloads {missing} — was it compressed by a "
                    "different fused plan?"
                )
            for q in specs:
                fn = q.agg.codec_payload_check
                if fn is not None:
                    fn(payload[q.name])

        def fused_fold_compressed(state, payload):
            # One dispatch folds a stacked unit of payload-chunks
            # ([K, ...] leaves, K static under jit). The step counter
            # is INERT on the codec path (all-accumulating by the
            # eligibility rule — no masked merge windows key off it);
            # it advances by the unit's widest per-query batch so it
            # stays monotone and unit-aligned. It is NOT numerically
            # equal to the raw twin's chunk count when every query's
            # stacker group-combines (fold_batch > 1) or on a sharded
            # mesh — the engine's checkpoint POSITION, not this leaf,
            # is the exactly-once authority either way.
            k = max(
                jax.tree.leaves(payload[q.name])[0].shape[0]
                for q in specs
            )
            out = {STEP_KEY: state[STEP_KEY] + k}
            for q in specs:
                out[q.name] = q.agg.fold_compressed(
                    state[q.name], payload[q.name]
                )
            return out

    fused_flatten = None
    if any(q.agg.flatten is not None for q in specs):
        def fused_flatten(state):
            out: dict = {STEP_KEY: state[STEP_KEY]}
            for q in specs:
                f = q.agg.flatten
                if q.accum:
                    out[q.name] = (f(state[q.name]) if f is not None
                                   else state[q.name])
                elif f is not None:
                    out[q.name] = {
                        "local": f(state[q.name]["local"]),
                        "global": f(state[q.name]["global"]),
                    }
                else:
                    out[q.name] = state[q.name]
            return out

    return MultiQueryPlan(
        init=init,
        fold=fold,
        combine=combine,
        transform=transform,
        flatten=fused_flatten,
        host_compress=fused_host_compress,
        fold_compressed=fused_fold_compressed,
        stack_payloads=fused_stack_payloads,
        codec_payload_check=fused_payload_check,
        # With the shared codec engaged, a codec-only sub-query makes
        # the WHOLE fused plan codec-only: the engine must refuse a
        # configuration where the codec cannot engage (mesh-unaligned
        # batch) instead of falling into the raw fold that would raise
        # mid-stream.
        requires_codec=use_codec and bool(codec_only),
        # The fused plan presents as ONE accumulating summary: per-query
        # windowing (for non-accum sub-queries) happens inside the fold,
        # so the engine's single-running-state physical plan carries
        # every query with zero per-window Merger work of its own.
        fold_accumulates=True,
        transient=False,
        jit_transform=True,
        # An accumulating sub-query without a transform passes its live
        # state leaves through the fused emission — the engine must not
        # donate buffers an emission may still alias (the same rule as
        # its transform-less accumulate plan).
        transform_may_alias=any(
            q.accum and q.agg.transform is None for q in specs
        ),
        merge_mode="replicated",
        name=plan_name,
        queries=specs,
    )


class MultiQueryStream(SummaryStream):
    """Emission stream of a fused run + live per-query snapshot reads.

    Iterating yields the fused emission dict (``{query_name:
    emission}``) once per closed merge window, exactly like
    ``SummaryStream``. While a consumer drives the stream,
    :meth:`snapshot` answers per-query reads from the LAST yielded
    window — staleness bounded by one merge window — from any thread;
    the lock is held only for the reference swap, never for D2H.
    """

    def __init__(self, inner: SummaryStream, plan: MultiQueryPlan):
        self._inner = inner
        self.plan = plan
        self._lock = threading.Lock()
        self._latest = None
        self._window = 0
        super().__init__(self._gen)
        self.stats = getattr(inner, "stats", None)
        self.timer = getattr(inner, "timer", None)

    def _gen(self):
        bus = obs_bus.get_bus()
        tracer = obs_tracing.active_tracer()
        # Serving-plane telemetry guard, bound once per run (the same
        # discipline as the executor's): multiquery.emit_ms measures
        # the emission SNAPSHOT PUBLICATION — lock wait + reference
        # swap, the latency live snapshot() readers can induce on the
        # stream (the window's compute wall is engine.merge_emit_ms,
        # recorded inside the inner executor).
        telemetry = obs_bus.telemetry_on()
        names = self.plan.query_names
        bus.gauge("multiquery.fused_queries", len(names))
        bus.inc("multiquery.runs")
        it = iter(self._inner)
        import time as _time

        while True:
            t0 = tracer.now() if tracer is not None else 0.0
            try:
                out = next(it)
            except StopIteration:
                return
            t_h = _time.perf_counter() if telemetry else 0.0
            with self._lock:
                self._latest = out
                self._window += 1
                w = self._window
            if telemetry:
                bus.observe("multiquery.emit_ms",
                            (_time.perf_counter() - t_h) * 1e3)
            bus.inc("multiquery.emissions", len(names))
            if tracer is not None:
                # Per-query attribution: one span per query per window
                # on its own multiquery/<name> track, covering the
                # window's wall — the exported trace shows the single
                # compress/H2D/fold pipeline feeding Q query tracks.
                for n in names:
                    tracer.span("multiquery", f"multiquery/{n}", t0,
                                query=n, window=w)
            yield out

    def snapshot(self, query: str | None = None):
        """Host copy of the named query's last-window emission (or the
        whole ``{name: emission}`` dict with ``query=None``). Returns
        ``None`` before the first window close."""
        with self._lock:
            latest = self._latest
        if latest is None:
            return None
        obs_bus.get_bus().inc("multiquery.snapshot_reads")
        if query is None:
            return {n: jax.tree.map(np.asarray, latest[n])
                    for n in self.plan.query_names}
        if query not in latest:
            raise ValueError(
                f"unknown query {query!r} (fused: "
                f"{list(self.plan.query_names)})"
            )
        return jax.tree.map(np.asarray, latest[query])

    def snapshot_window(self) -> int:
        """Merge-window number :meth:`snapshot` currently answers from
        (0 = none closed yet) — the staleness handle."""
        with self._lock:
            return self._window


def run_multiquery(queries, stream, **runner_kw) -> MultiQueryStream:
    """Convenience front end: ``run_aggregation(None, stream,
    queries=queries, **runner_kw)`` — one shared ingest pipeline, every
    query answered per chunk."""
    from .aggregation import run_aggregation

    return run_aggregation(None, stream, queries=queries, **runner_kw)
