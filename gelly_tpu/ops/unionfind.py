"""Array union-find: scatter-min hooking + pointer jumping.

TPU-native equivalent of the reference's ``DisjointSet``
(``M/summaries/DisjointSet.java``): instead of a ``HashMap<R,R>`` with
recursive path compression (``:66-80``) and per-edge ``union`` (``:92-118``),
the forest is a dense ``i32 parent[capacity]`` array over vertex slots, and a
whole chunk of edges is unioned at once:

  repeat until fixpoint:
    1. full path compression by pointer doubling (``parent = parent[parent]``)
    2. hook: for every edge, link ``max(root(u), root(v)) -> min(...)`` via a
       single masked scatter-min

Both loops are ``lax.while_loop``s with array-wide bodies — no data-dependent
Python control flow, so the whole union of a 4k-edge chunk is one fused XLA
computation. At convergence every vertex's parent is the **minimum vertex slot
in its component**, which doubles as a canonical component label (the
reference's roots are arbitrary; its tests compare component *sets*, so a
canonical label satisfies the same oracle,
``T/example/test/ConnectedComponentsTest.java:65-81``).

``merge_forests`` reproduces ``DisjointSet.merge``'s
"union every (key, parent) entry of the other" (``:127-131``) by treating the
other forest's parent array as an edge list.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .segments import INT_MAX, masked_scatter_min


def fresh_forest(capacity: int) -> jax.Array:
    """parent[i] = i — every slot its own singleton root."""
    return jnp.arange(capacity, dtype=jnp.int32)


def pointer_jump(parent: jax.Array) -> jax.Array:
    """Full path compression: parent <- parent[parent] until fixpoint."""

    def cond(p):
        return jnp.any(p[p] != p)

    def body(p):
        return p[p]

    return jax.lax.while_loop(cond, body, parent)


def union_edges(parent: jax.Array, src: jax.Array, dst: jax.Array,
                valid: jax.Array) -> jax.Array:
    """Union all valid (src, dst) edges into the forest; returns compressed forest.

    Equivalent to folding ``DisjointSet.union`` over the chunk
    (``M/library/ConnectedComponents.java:82-87`` does exactly this per edge),
    but order-free: hooking always links larger root to smaller, so the result
    is the same canonical forest regardless of edge order.

    Shiloach-Vishkin shape: each round does one masked scatter-min hook and
    ONE pointer-doubling step, converging in O(log n) rounds total. (A full
    path compression per hook round — the naive nesting — costs ~depth
    gathers per round; interleaving instead keeps the whole union at ~log
    rounds of one gather+scatter each, which is what the TPU's serialized
    while_loop iterations want.)

    Invariants: ``parent[i] <= i`` and updates only decrease entries, so the
    loop is monotone and terminates. At a no-change fixpoint the forest is
    flat (else doubling would change it) and every valid edge has equal
    labels (else the hook's scatter-min onto the flat root would lower it).
    """

    def body(state):
        p, _ = state
        lu = p[src]
        lv = p[dst]
        lo = jnp.minimum(lu, lv)
        hi = jnp.maximum(lu, lv)
        live = valid & (lo != hi)
        p2 = masked_scatter_min(p, hi, lo, live)
        p2 = p2[p2]  # one doubling step (monotone: p2[i] <= i elementwise)
        return p2, jnp.any(p2 != p)

    def cond(state):
        return state[1]

    p, _ = jax.lax.while_loop(cond, body, (parent, jnp.bool_(True)))
    return pointer_jump(p)


def union_pairs_compact(parent: jax.Array, src: jax.Array, dst: jax.Array,
                        valid: jax.Array) -> jax.Array:
    """Union (src, dst) pairs via a compacted root space — the large-N
    fast path for payload folds where touched slots << capacity.

    REQUIRES a flat forest (``parent[parent] == parent``), which
    :func:`union_edges` and this function both (re)establish — the
    invariant every fold/merge in the engine maintains. The generic
    :func:`union_edges` fixpoint pays O(capacity) per round (the pointer
    doubling walks the whole parent array); here each round works on
    arrays sized to the pair count instead:

    1. gather the pairs' current roots (one flat lookup);
    2. compact them: sort + searchsorted gives each distinct root a
       stable local id, ORDER-PRESERVING (local id order == root order,
       so min-local-id unions keep the canonical min-slot convention);
    3. run the :func:`union_edges` fixpoint in the local space (arrays
       ∝ pairs, not capacity);
    4. scatter each distinct root's new global root back, then one
       doubling pass — after the scatter the forest has depth ≤ 2
       (untouched slot → old root → new root), so a single
       ``parent[parent]`` restores flatness.

    Measured ~4x faster than :func:`union_edges` on Twitter-scale payload
    folds (2^24 slots, 2^21-edge chunk forests).
    """
    roots = jnp.concatenate([parent[src], parent[dst]])
    ok2 = jnp.concatenate([valid, valid])
    sorted_roots = jnp.sort(jnp.where(ok2, roots, INT_MAX))
    # Local id of a root = position of its first occurrence in the sorted
    # array: unique per root, ascending with root value.
    lsrc = jnp.searchsorted(sorted_roots, parent[src]).astype(jnp.int32)
    ldst = jnp.searchsorted(sorted_roots, parent[dst]).astype(jnp.int32)
    local = union_edges(
        fresh_forest(sorted_roots.shape[0]), lsrc, ldst, valid
    )
    # Scatter every occurrence's new root to its global slot. Non-first
    # occurrences of a root were never union endpoints (their local id is
    # their own position), so route each occurrence through its FIRST
    # occurrence's local root — every occurrence of a root then writes the
    # identical value. The .min (vs .set) is belt-and-braces on top: with
    # the min-root convention new_root <= old root always holds.
    first = jnp.searchsorted(sorted_roots, sorted_roots).astype(jnp.int32)
    new_root = sorted_roots[local[first]]
    live = sorted_roots != INT_MAX
    parent = parent.at[jnp.where(live, sorted_roots, 0)].min(
        jnp.where(live, new_root, INT_MAX), mode="drop"
    )
    return parent[parent]


def _chase_roots(p: jax.Array, x: jax.Array) -> jax.Array:
    """Pair-sized pointer chase to the TRUE roots of x (exact, while-based)."""

    def cond(st):
        x_, g = st
        return jnp.any(g != x_)

    def body(st):
        x_, g = st
        return g, p[g]

    with jax.named_scope("uf.chase"):
        x, _ = jax.lax.while_loop(cond, body, (x, p[x]))
    return x


def union_pairs_rooted(parent: jax.Array, src: jax.Array, dst: jax.Array,
                       valid: jax.Array) -> jax.Array:
    """Union (src, dst) pairs with ALL per-round work sized to the pairs —
    the generic exact kernel of the compact-space plans (the hot star-
    forest fold, :func:`union_pairs_star`, runs it on the pairs its
    unrolled fast rounds leave unresolved).

    Unlike :func:`union_edges` (whose every round walks the full parent
    array for the doubling step) and :func:`union_pairs_compact` (which
    re-compacts roots per call with a sort + three binary-search passes,
    ~5M lookups/s on TPU), each round here:

    1. chases both endpoints' labels to their TRUE roots with a pair-sized
       pointer chase (inner while_loop of pair-sized gathers);
    2. hooks root-to-root with one masked scatter-min;

    and exits when every valid pair's roots agree. Invariants: hooks write
    ``lo < p[hi] = hi`` at true roots only, so chains stay strictly
    decreasing (acyclic, ``p[i] <= i``) and every live round strictly
    lowers some entry (termination). At exit all pairs connect (equal
    roots) and hooks only ever merge pair-connected trees (no spurious
    unions).

    The forest is returned **without** a global flatten — depth can grow by
    O(1) per call; later calls chase through it and the window-close
    transform runs one :func:`pointer_jump` over the full array. That is
    the point: per-dispatch cost ∝ pairs, full-capacity work once per
    window (VERDICT r3 item 1).
    """
    src = jnp.where(valid, src, 0)
    dst = jnp.where(valid, dst, 0)

    def cond(state):
        return state[1]

    def body(state):
        p, _ = state
        ru = _chase_roots(p, src)
        rv = _chase_roots(p, dst)
        with jax.named_scope("uf.hook"):
            lo = jnp.minimum(ru, rv)
            hi = jnp.maximum(ru, rv)
            live = valid & (lo != hi)
            p2 = masked_scatter_min(p, hi, lo, live)
            return p2, jnp.any(live)

    with jax.named_scope("uf.fixpoint"):
        p, _ = jax.lax.while_loop(cond, body, (parent, jnp.bool_(True)))
    return p


def star_tail_width(lanes: int) -> int:
    """Lanes of the star fold's exact tail for a payload of ``lanes``
    lanes: 1/32 of them, at least 1024, at most all of them."""
    return min(lanes, max(1024, lanes // 32))


def _star_check(parent: jax.Array, v: jax.Array, ri: jax.Array,
                valid: jax.Array):
    """Steps 1 and 2 of :func:`union_pairs_star` over pairs ``(v[j],
    v[ri[j]])`` with ``v`` already zeroed at invalid lanes: the fast
    rounds, then the depth-3 check. Returns the forest after the fast
    rounds, each lane's two depth-3 labels ``(a, b)``, and the lanes
    whose labels differ (``live``)."""

    def chase_fixed(p, x, depth):
        g = p[x]
        for _ in range(depth - 1):
            g = p[g]
        return g

    p = parent
    with jax.named_scope("uf.fast"):
        for depth in (2, 3):
            ru = chase_fixed(p, v, depth)
            rv = ru[ri]
            lo = jnp.minimum(ru, rv)
            hi = jnp.maximum(ru, rv)
            # Hook ONLY at verified roots: a depth-limited chase can stop
            # at an interior node, and a scatter-min there would REPLACE
            # its real parent edge — disconnecting its ancestor chain and
            # silently splitting a component built by earlier dispatches
            # (a root's self-loop is the only edge safe to overwrite).
            # Pairs whose chase fell short stay live for the check below
            # and resolve in the exact tail.
            live = valid & (lo != hi) & (p[hi] == hi)
            p = masked_scatter_min(p, hi, lo, live)

    with jax.named_scope("uf.check"):
        a = chase_fixed(p, v, 3)
        b = a[ri]
        live = valid & (a != b)
    return p, a, b, live


def _live_tail(p: jax.Array, a: jax.Array, b: jax.Array, live: jax.Array,
               width: int) -> jax.Array:
    """Union the pairs ``(a[j], b[j])`` of the ``live`` lanes exactly,
    ``width`` at a time.

    One running sum ranks the live lanes. Each batch scatters the lane
    indices of ranks ``[k * width, (k + 1) * width)`` into ``width``
    slots, gathers their pairs and runs the exact fixpoint on them,
    chasing both sides; batches repeat while ranks remain, so no live
    lane is ever dropped. A payload with no live lane runs no batch.

    On a v5e, over 1,572,864 lanes, the running sum and scatter take
    ~8.4 ms a batch, less than ``jnp.nonzero`` (~110 ms) or
    ``collectives.compact_delta`` (~18 ms) take (PERF.md §6).
    """
    lanes = live.shape[0]
    rank = jnp.cumsum(live.astype(jnp.int32)) - 1
    n_live = jnp.sum(live.astype(jnp.int32))
    lane = jnp.arange(lanes, dtype=jnp.int32)
    slot = jnp.arange(width, dtype=jnp.int32)

    def cond(state):
        return state[1] < n_live

    def body(state):
        p, first = state
        at = rank - first
        take = live & (at >= 0) & (at < width)
        # Slot ``width`` lies past the end: lanes outside the batch drop.
        idx = jnp.zeros((width,), jnp.int32).at[
            jnp.where(take, at, width)].set(lane, mode="drop")
        ok = slot < n_live - first
        return union_pairs_rooted(p, a[idx], b[idx], ok), first + width

    p, _ = jax.lax.while_loop(cond, body, (p, jnp.int32(0)))
    return p


def union_pairs_star(parent: jax.Array, v: jax.Array, ri: jax.Array,
                     valid: jax.Array) -> jax.Array:
    """Union star-forest payload rows — the hot compact-codec fold kernel.

    ``(v[j], v[ri[j]])`` are the pairs: every payload row is a host-combined
    spanning forest whose root is itself a row entry, and the codec ships
    the root's row INDEX (``ri``), so the root side of each pair resolves
    with one pair-sized gather from the already-chased array (``rv =
    ru[ri]``) instead of a second pointer chase.

    Structure (everything sized to the pairs — no O(M) work):

    1. two UNROLLED rounds: a pointer chase of 2, then 3, levels
       (straight-line gathers), each followed by one scatter-min hook
       MASKED to verified roots (``p[hi] == hi``) — a hook at an
       interior node would replace a real parent edge and disconnect
       its ancestors, losing earlier dispatches' unions.
    2. a depth-limited check: each lane's two depth-3 labels ``(a, b)``.
       Equal labels imply the same tree (chases are deterministic), and
       unions only merge trees, so such a lane is resolved for good;
       the lanes whose labels differ are ``live``.
       On Twitter-2010's degree law about 1% of a dispatch's lanes are
       live here (PERF.md §5).
    3. an exact tail over the live lanes only: they are ranked by one
       running sum and taken :func:`star_tail_width` of the lane count
       at a time, each batch running the exact fixpoint
       (:func:`union_pairs_rooted`: a true-root chase of both sides and
       one hook per round) on its pairs ``(a[j], b[j])``. Those are ancestors of ``v[j]`` and
       ``v[ri[j]]``, so their true roots, and every hook, are those of
       the pairs themselves. Batches repeat while live lanes remain, so
       correctness never depends on the unrolled depth or the width.
       On a v5e a chase step costs what its gather costs, ~30 ns a lane
       (1.5 ms over 49,152 lanes, 30–46 ms over 1,572,864); the loop's
       own condition takes ~1 µs a step (PERF.md §5).

    With every live lane in one batch, each round hooks exactly the
    ``(hi, lo)`` roots a fixpoint over all lanes would (lanes resolved
    at the check never hook), so the forest is bit-identical to that
    fixpoint's. With several batches the components are the same; which
    root hooks under which may differ.

    Like :func:`union_pairs_rooted`, the forest is returned without a
    global flatten; the window-close transform pays the one full-array
    pointer_jump.
    """
    v = jnp.where(valid, v, 0)
    p, a, b, live = _star_check(parent, v, ri, valid)
    with jax.named_scope("uf.tail"):
        return _live_tail(p, a, b, live, star_tail_width(v.shape[0]))


def union_edges_dedup(parent: jax.Array, src: jax.Array, dst: jax.Array,
                      valid: jax.Array, unique_cap: int,
                      tail_cap: int | None = None) -> jax.Array:
    """Sort-dedup raw-edge fold — the large-chunk RAW device path
    (VERDICT r4 item 4: the generic :func:`union_edges` fixpoint paid
    O(capacity) random gathers per round and ran below one CPU core).

    Random access (element-granule ``p[idx]`` gathers and scatters) is
    the cost the design cuts: it spends REGULAR ops (sorts, cumsum) to
    shrink the random-access working set before any union-find work:

    1. canonicalize + 2-key sort + first-occurrence mask: exact
       UNDIRECTED dedup. On the power-law streams CC targets, 2^25-edge
       chunks carry ~13% distinct pairs — a 7x cut in every later op.
    2. stable partition of the distinct pairs into ``unique_cap`` lanes.
    3. three unrolled hook rounds at depths 1/2/3: chase both endpoints,
       hook lo under hi MASKED to verified roots (``p[hi] == hi`` — an
       unverified hook would overwrite a real parent edge and split a
       component).
    4. survivors (pre-hook depth-3 view, conservative) compact into
       ``tail_cap`` lanes via cumsum+scatter and finish in the EXACT
       pair-sized fixpoint (:func:`union_pairs_rooted`).
    5. one ``p[p]`` halving keeps entry depth low for the next chunk.

    Exactness never depends on the caps: ``unique_cap`` overflow (more
    distinct pairs than lanes) falls back to the exact full-width
    fixpoint over the ORIGINAL pairs, ``tail_cap`` overflow re-runs the
    exact fixpoint over the distinct pairs — both compiled as
    ``lax.cond`` branches that cost nothing when the caps hold.

    Labels match the chunked numpy oracle exactly. No benchmark cell
    runs the raw fold, so its speed on the chip is not recorded
    (``PERF.md``).
    """
    unique_cap = min(unique_cap, src.shape[0])
    if tail_cap is None:
        tail_cap = max(1 << 16, unique_cap // 4)
    tail_cap = min(tail_cap, unique_cap)
    sentinel = jnp.int32(INT_MAX)
    u = jnp.minimum(src, dst)
    v = jnp.maximum(src, dst)
    u = jnp.where(valid, u, sentinel)
    v = jnp.where(valid, v, sentinel)
    su, sv = jax.lax.sort((u, v), num_keys=2)
    first = ((su != jnp.roll(su, 1)) | (sv != jnp.roll(sv, 1)))
    first = first.at[0].set(True) & (su != sentinel)
    flag = (~first).astype(jnp.int32)
    _, uu, vv = jax.lax.sort((flag, su, sv), num_keys=1, is_stable=True)
    ucount = jnp.sum(first.astype(jnp.int32))
    uu_c = uu[:unique_cap]
    vv_c = vv[:unique_cap]
    live0 = (
        jnp.arange(unique_cap, dtype=jnp.int32)
        < jnp.minimum(ucount, unique_cap)
    )

    def deduped_fold(p):
        alive = live0
        for depth in (1, 2, 3):
            g = p[uu_c]
            for _ in range(depth - 1):
                g = p[g]
            h = p[vv_c]
            for _ in range(depth - 1):
                h = p[h]
            lo = jnp.minimum(g, h)
            hi = jnp.maximum(g, h)
            alive = live0 & (lo != hi)
            hook = alive & (p[hi] == hi)
            p = masked_scatter_min(p, hi, lo, hook)
        pos = jnp.cumsum(alive.astype(jnp.int32)) - 1
        nalive = jnp.sum(alive.astype(jnp.int32))
        tgt = jnp.where(alive & (pos < tail_cap), pos, tail_cap)
        cu = jnp.zeros((tail_cap + 1,), jnp.int32).at[tgt].set(
            uu_c, mode="drop")[:tail_cap]
        cv = jnp.zeros((tail_cap + 1,), jnp.int32).at[tgt].set(
            vv_c, mode="drop")[:tail_cap]
        clive = (
            jnp.arange(tail_cap, dtype=jnp.int32)
            < jnp.minimum(nalive, tail_cap)
        )
        p = union_pairs_rooted(p, cu, cv, clive)
        # Tail overflow: exact fixpoint over ALL distinct pairs (no-op
        # rounds for the already-resolved ones).
        return jax.lax.cond(
            nalive > tail_cap,
            lambda q: union_pairs_rooted(q, uu_c, vv_c, live0),
            lambda q: q,
            p,
        )

    # unique_cap overflow: distinct pairs beyond the cap were sliced
    # away, so fall back to the exact full-width fixpoint over the
    # ORIGINAL pairs (adversarial all-distinct chunks only).
    p = jax.lax.cond(
        ucount > unique_cap,
        lambda q: union_pairs_rooted(
            q, jnp.where(valid, src, 0), jnp.where(valid, dst, 0), valid
        ),
        deduped_fold,
        parent,
    )
    return p[p]


def merge_forests(a: jax.Array, b: jax.Array) -> jax.Array:
    """Union two forests over the same slot space (DisjointSet.merge :127-131)."""
    idx = jnp.arange(a.shape[0], dtype=jnp.int32)
    return union_edges(a, idx, b, jnp.ones_like(idx, dtype=bool))


def merge_forest_stack(stacked: jax.Array) -> jax.Array:
    """Merge K forests [K, N] into one — the cross-shard combine.

    Treats every (i, stacked[k, i]) as an edge and unions them all in a single
    fixpoint loop; used by the ICI merge where each device contributes its
    local forest (replaces the reference's pairwise reduce fan-in,
    ``M/SummaryBulkAggregation.java:81-83``).
    """
    k, n = stacked.shape
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (k, n)).reshape(-1)
    dsts = stacked.reshape(-1)
    return union_edges(fresh_forest(n), idx, dsts, jnp.ones((k * n,), bool))


def chase_depth(parent) -> int:
    """Maximum chain length in the forest — the number of ``x = p[x]``
    hops the deepest slot needs to reach its root. Host-side (numpy)
    diagnostic: 0 for the identity forest, 1 for a flat forest, and the
    quantity the pair-sized folds (:func:`union_pairs_rooted`,
    :func:`union_pairs_star`) and the dirty-delta merge let grow O(1)
    per dispatch/window. The cadenced flatten
    (``SummaryAggregation.flatten`` / ``ResilientRunner(flatten_state=)``
    → :func:`pointer_jump`) exists to keep this bounded on long streams;
    its regression test asserts post-flatten depth <= 2.
    """
    import numpy as np

    p = np.asarray(parent)
    x = np.arange(p.shape[0], dtype=p.dtype)
    # An acyclic forest fixes within n hops; more means a cycle — a
    # corrupt forest is exactly what a diagnostic gets pointed at, so
    # bound the walk instead of hanging.
    for depth in range(p.shape[0] + 1):
        nx = p[x]
        if np.array_equal(nx, x):
            return depth
        x = nx
    raise ValueError(
        f"parent array of {p.shape[0]} slots has no root fixpoint "
        "within n hops — the forest contains a cycle"
    )


def component_labels(parent: jax.Array, seen: jax.Array) -> jax.Array:
    """Labels for seen vertices (min slot in component); -1 for unseen slots."""
    p = pointer_jump(parent)
    return jnp.where(seen, p, -1)
