"""Union-find kernel unit tests — DisjointSetTest analog
(T/util/DisjointSetTest.java:32-78)."""

import jax.numpy as jnp
import numpy as np
import pytest

from gelly_tpu.ops.unionfind import (
    component_labels,
    fresh_forest,
    merge_forest_stack,
    merge_forests,
    pointer_jump,
    union_edges,
)


def labels_of(parent, n_used):
    return np.asarray(pointer_jump(parent))[:n_used].tolist()


def test_union_basic_chain():
    p = fresh_forest(8)
    src = jnp.array([0, 1, 2], jnp.int32)
    dst = jnp.array([1, 2, 3], jnp.int32)
    p = union_edges(p, src, dst, jnp.ones(3, bool))
    assert labels_of(p, 4) == [0, 0, 0, 0]


def test_union_respects_valid_mask():
    p = fresh_forest(8)
    src = jnp.array([0, 2], jnp.int32)
    dst = jnp.array([1, 3], jnp.int32)
    p = union_edges(p, src, dst, jnp.array([True, False]))
    assert labels_of(p, 4) == [0, 0, 2, 3]


def test_union_order_free_canonical():
    # Same component set regardless of edge order; root is the min slot.
    edges = [(4, 2), (2, 7), (7, 1), (5, 6)]
    for perm in ([0, 1, 2, 3], [3, 2, 1, 0], [1, 3, 0, 2]):
        p = fresh_forest(8)
        src = jnp.array([edges[i][0] for i in perm], jnp.int32)
        dst = jnp.array([edges[i][1] for i in perm], jnp.int32)
        p = union_edges(p, src, dst, jnp.ones(4, bool))
        lab = labels_of(p, 8)
        assert lab[1] == lab[2] == lab[4] == lab[7] == 1
        assert lab[5] == lab[6] == 5


def test_merge_even_odd_forests():
    # DisjointSetTest's merge scenario: an "evens" forest and an "odds"
    # forest over 18 elements merge into 2 roots (:60-78).
    n = 18
    evens = fresh_forest(32)
    odds = fresh_forest(32)
    e = jnp.array(range(0, n - 2, 2), jnp.int32)
    evens = union_edges(evens, e, e + 2, jnp.ones_like(e, dtype=bool))
    o = jnp.array(range(1, n - 2, 2), jnp.int32)
    odds = union_edges(odds, o, o + 2, jnp.ones_like(o, dtype=bool))
    merged = merge_forests(evens, odds)
    lab = labels_of(merged, n)
    assert set(lab[0::2]) == {0}
    assert set(lab[1::2]) == {1}
    assert len(set(lab)) == 2


def test_merge_stack_equals_pairwise():
    n = 16
    f1 = union_edges(fresh_forest(n), jnp.array([0]), jnp.array([1]),
                     jnp.ones(1, bool))
    f2 = union_edges(fresh_forest(n), jnp.array([1]), jnp.array([2]),
                     jnp.ones(1, bool))
    f3 = union_edges(fresh_forest(n), jnp.array([5]), jnp.array([6]),
                     jnp.ones(1, bool))
    stacked = jnp.stack([f1, f2, f3])
    m = merge_forest_stack(stacked)
    pairwise = merge_forests(merge_forests(f1, f2), f3)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(pairwise))
    lab = labels_of(m, 8)
    assert lab[0] == lab[1] == lab[2] == 0
    assert lab[5] == lab[6] == 5


def test_component_labels_unseen_is_minus_one():
    p = fresh_forest(8)
    seen = jnp.zeros(8, bool).at[jnp.array([0, 1])].set(True)
    p = union_edges(p, jnp.array([0]), jnp.array([1]), jnp.ones(1, bool))
    lab = np.asarray(component_labels(p, seen))
    assert lab.tolist() == [0, 0, -1, -1, -1, -1, -1, -1]


def test_union_pairs_compact_matches_union_edges():
    import jax.numpy as jnp

    from gelly_tpu.ops.unionfind import (
        fresh_forest,
        union_edges,
        union_pairs_compact,
    )

    rng = np.random.default_rng(43)
    n = 512
    for trial in range(5):
        src = jnp.asarray(rng.integers(0, n, 200), jnp.int32)
        dst = jnp.asarray(rng.integers(0, n, 200), jnp.int32)
        ok = jnp.asarray(rng.random(200) < 0.8)
        a = union_edges(fresh_forest(n), src, dst, ok)
        b = union_pairs_compact(fresh_forest(n), src, dst, ok)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # Chained folds (flat-input invariant maintained across calls).
        src2 = jnp.asarray(rng.integers(0, n, 150), jnp.int32)
        dst2 = jnp.asarray(rng.integers(0, n, 150), jnp.int32)
        ok2 = jnp.asarray(rng.random(150) < 0.8)
        a2 = union_edges(a, src2, dst2, ok2)
        b2 = union_pairs_compact(b, src2, dst2, ok2)
        np.testing.assert_array_equal(np.asarray(a2), np.asarray(b2))
        # Result is flat (the invariant consumers rely on).
        np.testing.assert_array_equal(np.asarray(b2), np.asarray(b2)[np.asarray(b2)])


def test_union_pairs_parity_compact_matches_union_edges_parity():
    import jax.numpy as jnp

    from gelly_tpu.ops.parity_unionfind import (
        fresh_parity_forest,
        union_edges_parity,
        union_pairs_parity_compact,
    )

    rng = np.random.default_rng(47)
    n = 512
    for trial in range(5):
        f_a = f_b = fresh_parity_forest(n)
        # Chained folds; later rounds likely create odd cycles, so both
        # the structure AND the sticky failed bit must track.
        for round_ in range(3):
            m = 150
            u = jnp.asarray(rng.integers(0, n, m), jnp.int32)
            v = jnp.asarray(rng.integers(0, n, m), jnp.int32)
            q = jnp.asarray(rng.integers(0, 2, m), jnp.int32)
            ok = jnp.asarray(rng.random(m) < 0.8)
            f_a = union_edges_parity(f_a, u, v, q, ok)
            f_b = union_pairs_parity_compact(f_b, u, v, q, ok)
            np.testing.assert_array_equal(
                np.asarray(f_a.parent), np.asarray(f_b.parent),
            )
            assert bool(f_a.failed) == bool(f_b.failed), (trial, round_)
            if not bool(f_a.failed):
                # The 2-coloring is unique per component only while the
                # constraints are consistent; after an odd cycle the
                # coloring is undefined (the reference collapses to
                # (false, {})) and the implementations may settle
                # different rel values.
                np.testing.assert_array_equal(
                    np.asarray(f_a.rel), np.asarray(f_b.rel),
                )
        # Flat-forest invariant holds for the compact result.
        p = np.asarray(f_b.parent)
        np.testing.assert_array_equal(p, p[p])
        r = np.asarray(f_b.rel)
        assert (r[p == np.arange(n)] == 0).all()


# ---------------- pair-sized kernels (compact-space folds) -------------- #


def _pair_oracle(m, all_pairs):
    parent = list(range(m))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in all_pairs:
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
    return [find(x) for x in range(m)]


def test_union_pairs_rooted_matches_union_edges():
    from gelly_tpu.ops.unionfind import union_pairs_rooted

    rng = np.random.default_rng(3)
    m = 64
    p = fresh_forest(m)
    all_pairs = []
    for _ in range(5):  # sequential calls over one never-flattened forest
        src = rng.integers(0, m, 20).astype(np.int32)
        dst = rng.integers(0, m, 20).astype(np.int32)
        ok = rng.random(20) < 0.8
        all_pairs += [(int(a), int(b))
                      for a, b, o in zip(src, dst, ok) if o]
        p = union_pairs_rooted(p, jnp.asarray(src), jnp.asarray(dst),
                               jnp.asarray(ok))
    assert labels_of(p, m) == _pair_oracle(m, all_pairs)


def test_union_pairs_star_deep_chain_no_severed_edges():
    # Deterministic regression for the severed-edge bug (code-review r4):
    # build croot chain 20->19->18->17->16 over five calls, then union
    # (20, 3). The depth-2 fast chase stops at INTERIOR node 18; an
    # unmasked hook would overwrite p[18]=17 with 3, disconnecting
    # {17, 16} — and the depth-3 convergence check then reads (20, 3) as
    # satisfied, so the exact fallback never repairs the split. The root
    # mask must reject that hook and route the pair to the exact loop.
    from gelly_tpu.ops.unionfind import union_pairs_star

    p = fresh_forest(24)
    rows = [(20, 19), (19, 18), (18, 17), (17, 16), (20, 3)]
    for a, root in rows:
        v = jnp.array([root, a], jnp.int32)
        ri = jnp.array([0, 0], jnp.int32)
        p = union_pairs_star(p, v, ri, jnp.ones(2, bool))
    lab = labels_of(p, 24)
    assert len({lab[x] for x in (3, 16, 17, 18, 19, 20)}) == 1, lab


@pytest.mark.slow  # tier-1 budget: deep-chain twin stays in tier
def test_union_pairs_star_sequential_calls_fuzz():
    # Regression for the severed-edge bug (code-review r4): unrolled fast
    # rounds hooking at a depth-limited NON-root overwrote its real parent
    # edge, disconnecting ancestors and silently splitting components
    # built by earlier dispatches. Adversarial star payloads over many
    # sequential calls on one never-flattened forest, vs a pair oracle.
    from gelly_tpu.ops.unionfind import union_pairs_star

    for seed in range(8):
        rng = np.random.default_rng(seed)
        m = 24
        p = fresh_forest(m)
        all_pairs = []
        for _ in range(6):
            # One star-forest row: unique v, row-local root indices ri.
            n_row = int(rng.integers(2, m))
            v = rng.permutation(m)[:n_row].astype(np.int32)
            # Random forest over the row: each entry points at a random
            # earlier entry (or itself) -> ri is a valid root index map.
            parent_idx = np.arange(n_row)
            for j in range(1, n_row):
                if rng.random() < 0.7:
                    parent_idx[j] = int(rng.integers(0, j))
            # Path-compress to row roots.
            for j in range(n_row):
                r = j
                while parent_idx[r] != r:
                    r = parent_idx[r]
                parent_idx[j] = r
            ri = parent_idx.astype(np.int32)
            all_pairs += [(int(v[j]), int(v[ri[j]]))
                          for j in range(n_row)]
            p = union_pairs_star(
                p, jnp.asarray(v), jnp.asarray(ri),
                jnp.ones(n_row, bool),
            )
        got = labels_of(p, m)
        want = _pair_oracle(m, all_pairs)
        assert got == want, (seed, got, want)


def _full_width_star(parent, v, ri, valid):
    """The star fold whose exact fixpoint chased every lane of the
    payload each round: the reference the live-lane tail must match bit
    for bit while its live lanes fit one batch."""
    import jax

    from gelly_tpu.ops.segments import masked_scatter_min

    v = jnp.where(valid, v, 0)

    def chase_fixed(p, x, depth):
        g = p[x]
        for _ in range(depth - 1):
            g = p[g]
        return g

    p = parent
    for depth in (2, 3):
        ru = chase_fixed(p, v, depth)
        rv = ru[ri]
        lo, hi = jnp.minimum(ru, rv), jnp.maximum(ru, rv)
        p = masked_scatter_min(p, hi, lo,
                               valid & (lo != hi) & (p[hi] == hi))
    ru = chase_fixed(p, v, 3)
    live0 = jnp.any(valid & (ru != ru[ri]))

    def roots(p, x):
        return jax.lax.while_loop(lambda s: jnp.any(s[1] != s[0]),
                                  lambda s: (s[1], p[s[1]]), (x, p[x]))[0]

    def body(state):
        p, _ = state
        ru = roots(p, v)
        rv = ru[ri]
        lo, hi = jnp.minimum(ru, rv), jnp.maximum(ru, rv)
        live = valid & (lo != hi)
        return masked_scatter_min(p, hi, lo, live), jnp.any(live)

    return jax.lax.while_loop(lambda s: s[1], body, (p, live0))[0]


def _star_payloads(seed, m=64, lanes=48, calls=16):
    """Sequential star payloads over ``m`` slots, ``lanes`` lanes each:
    a few rows of distinct vertices, each lane's ``ri`` its row's root
    lane, the rest invalid."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(calls):
        v = np.zeros(lanes, np.int32)
        ri = np.zeros(lanes, np.int32)
        valid = np.zeros(lanes, bool)
        at = 0
        while at < lanes - 1:
            n_row = int(rng.integers(2, min(12, lanes - at) + 1))
            v[at:at + n_row] = rng.permutation(m)[:n_row]
            ri[at:at + n_row] = at + int(rng.integers(0, n_row))
            valid[at:at + n_row] = rng.random() < 0.9
            at += n_row
        out.append((jnp.asarray(v), jnp.asarray(ri), jnp.asarray(valid)))
    return out


def _chain_payloads(lanes=48):
    """Eight chains of depth 5 in slots ``8c + 1 .. 8c + 6``, built top
    down one link a call, then one call joining each chain's deep end
    ``8c + 6`` to slot ``8c``: the depth-3 chases stop inside the chains,
    so that call leaves eight lanes live whose unions are independent."""
    calls = [[(8 * c + 6 - k, 8 * c + 5 - k) for c in range(8)]
             for k in range(5)]
    calls.append([(8 * c + 6, 8 * c) for c in range(8)])
    out = []
    for rows in calls:
        v = np.zeros(lanes, np.int32)
        ri = np.zeros(lanes, np.int32)
        valid = np.zeros(lanes, bool)
        for r, (a, root) in enumerate(rows):
            v[2 * r:2 * r + 2] = (root, a)
            ri[2 * r:2 * r + 2] = 2 * r
            valid[2 * r:2 * r + 2] = True
        out.append((jnp.asarray(v), jnp.asarray(ri), jnp.asarray(valid)))
    return out


def _live_count(p, v, ri, valid):
    from gelly_tpu.ops.unionfind import _star_check

    return int(_star_check(p, jnp.where(valid, v, 0), ri, valid)[3].sum())


@pytest.mark.parametrize("seed", [0, 1, 2, 3, "chains"])
def test_union_pairs_star_bit_identical_to_full_width_fixpoint(seed):
    # Every live lane fits one batch (the default width of 48 lanes is
    # all of them), so each call's forest equals the full-width
    # fixpoint's exactly, over sequential calls on a never-flattened
    # forest.
    import jax

    from gelly_tpu.ops.unionfind import union_pairs_star

    star, ref = jax.jit(union_pairs_star), jax.jit(_full_width_star)
    p = q = fresh_forest(64)
    live = 0
    payloads = (_chain_payloads() if seed == "chains"
                else _star_payloads(seed))
    for v, ri, valid in payloads:
        live += _live_count(p, v, ri, valid)
        p, q = star(p, v, ri, valid), ref(q, v, ri, valid)
        np.testing.assert_array_equal(np.asarray(p), np.asarray(q))
    assert live > 0  # the tail ran


@pytest.mark.parametrize("width", [1, 2, 5])
def test_union_pairs_star_tail_runs_several_batches(width, monkeypatch):
    import jax

    from gelly_tpu.ops import unionfind

    monkeypatch.setattr(unionfind, "star_tail_width", lambda lanes: width)
    # A function of its own, so jit traces it with the patched width
    # rather than reuse another test's trace of union_pairs_star.
    star = jax.jit(lambda *a: unionfind.union_pairs_star(*a))
    ref = jax.jit(_full_width_star)
    p = q = fresh_forest(64)
    pairs, live = [], []
    for v, ri, valid in _chain_payloads() + _star_payloads(1):
        live.append(_live_count(p, v, ri, valid))
        p, q = star(p, v, ri, valid), ref(q, v, ri, valid)
        vv, rr, ok = map(np.asarray, (v, ri, valid))
        pairs += [(int(vv[j]), int(vv[rr[j]])) for j in np.flatnonzero(ok)]
        assert labels_of(p, 64) == labels_of(q, 64)
    assert live[5] == 8 > width  # the joining call took several batches
    assert labels_of(p, 64) == _pair_oracle(64, pairs)
    pp = np.asarray(p)
    assert (pp <= np.arange(64)).all()  # still a min-rooted forest


@pytest.mark.parametrize("case", ["repeated_rows", "no_valid_lane"])
def test_union_pairs_star_payload_with_no_live_lane(case):
    import jax

    from gelly_tpu.ops.unionfind import union_pairs_star

    star = jax.jit(union_pairs_star)
    v, ri, valid = _star_payloads(3, calls=1)[0]
    # Flat, so every pair the first call joined reads one label at the
    # check's depth.
    p = pointer_jump(star(fresh_forest(64), v, ri, valid))
    if case == "no_valid_lane":
        valid = jnp.zeros_like(valid)
    assert _live_count(p, v, ri, valid) == 0
    out = star(p, v, ri, valid)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(p))
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(_full_width_star(p, v, ri, valid)))


@pytest.mark.parametrize("wire", ["segments", "pairs"])
def test_star_tail_takes_one_batch_a_dispatch_on_a_power_law_stream(wire):
    # Twitter-2010's degree law at 2^16 slots: 2^18 edges in 64 chunks
    # of 2^12, one dispatch each through the compact plan, as the
    # benchmark's CC cell folds them. Every dispatch's live lanes fit
    # the derived tail width (one batch), some dispatch runs the tail
    # and some runs none, and the labels are exact.
    import os
    import sys

    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark import synth
    from gelly_tpu.library.connected_components import (
        cc_labels_numpy,
        connected_components_compact,
        lane_segment_starts,
    )
    from gelly_tpu.ops.unionfind import _star_check, star_tail_width

    n = 1 << 16
    src, dst = synth.edges(
        {"vertices": n, "graph_vertices": n - n // 100, "edges": 1 << 18,
         "degree_exponent": 2.276, "graph_seed": 777}, 2147483013)
    agg = connected_components_compact(n, compact_capacity=n, wire=wire)
    fold = jax.jit(agg.fold_compressed)
    check = jax.jit(_star_check)

    class Chunk:
        def __init__(self, a, b):
            self.src, self.dst = a, b
            self.valid = np.ones(a.shape[0], bool)

    s, live = agg.init(), []
    for i in range(0, src.shape[0], 1 << 12):
        pl = agg.stack_payloads(
            [agg.host_compress(Chunk(src[i:i + 4096], dst[i:i + 4096]))])
        if wire == "segments":
            mm = jnp.atleast_2d(pl["m"])
            ri, ok = lane_segment_starts(jnp.atleast_2d(pl["len"]),
                                         mm.shape[1])
            v, ri, ok = mm.reshape(-1), ri.reshape(-1), ok.reshape(-1)
        else:
            vv, rr = jnp.atleast_2d(pl["v"]), jnp.atleast_2d(pl["ri"])
            ri = (rr + vv.shape[1] * jnp.arange(vv.shape[0])[:, None])
            v, ri = vv.reshape(-1), ri.reshape(-1)
            ok = v >= 0
        live.append((int(check(s.croot, jnp.where(ok, v, 0), ri,
                               ok)[3].sum()), v.shape[0]))
        s = fold(s, pl)
    assert len(live) == 64
    assert all(k <= star_tail_width(lanes) for k, lanes in live), live
    assert min(k for k, _ in live) == 0 < max(k for k, _ in live)
    np.testing.assert_array_equal(np.asarray(agg.transform(s)),
                                  cc_labels_numpy(src, dst, None, n))


# ------------------- sort-dedup raw fold (round 5) -------------------- #


def test_union_edges_dedup_matches_union_edges():
    from gelly_tpu.ops.unionfind import union_edges_dedup

    rng = np.random.default_rng(12)
    n = 256
    for seed in range(4):
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n, 500).astype(np.int32)
        dst = rng.integers(0, n, 500).astype(np.int32)
        valid = rng.random(500) < 0.85
        p1 = union_edges(
            fresh_forest(n), jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(valid),
        )
        p2 = union_edges_dedup(
            fresh_forest(n), jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(valid), unique_cap=256, tail_cap=64,
        )
        assert labels_of(p1, n) == labels_of(p2, n), seed


def test_union_edges_dedup_cap_overflow_exact():
    # ALL pairs distinct and unique_cap tiny: the full-width exact
    # fallback must fire and still produce correct labels.
    from gelly_tpu.ops.unionfind import union_edges_dedup

    n = 128
    src = np.arange(0, 126, 2, dtype=np.int32)
    dst = (np.arange(0, 126, 2, dtype=np.int32) + 1)
    p = union_edges_dedup(
        fresh_forest(n), jnp.asarray(src), jnp.asarray(dst),
        jnp.ones(src.shape[0], bool), unique_cap=8, tail_cap=4,
    )
    lab = labels_of(p, 126)
    assert lab == [2 * (i // 2) for i in range(126)]


def test_union_edges_dedup_tail_overflow_exact():
    # Long chain: the depth-3 rounds leave most pairs unresolved, the
    # tail cap overflows, and the exact distinct-pair fallback finishes.
    from gelly_tpu.ops.unionfind import union_edges_dedup

    n = 128
    src = np.arange(0, 100, dtype=np.int32)
    dst = np.arange(1, 101, dtype=np.int32)
    p = union_edges_dedup(
        fresh_forest(n), jnp.asarray(src), jnp.asarray(dst),
        jnp.ones(100, bool), unique_cap=128, tail_cap=4,
    )
    assert labels_of(p, 101) == [0] * 101


def test_union_edges_dedup_sequential_folds():
    # Streaming shape: repeated folds into the same forest, components
    # lowered across folds, parity vs the generic kernel every step.
    from gelly_tpu.ops.unionfind import union_edges_dedup

    n = 512
    rng = np.random.default_rng(33)
    p1 = fresh_forest(n)
    p2 = fresh_forest(n)
    for step in range(5):
        src = (rng.zipf(1.5, 300) % n).astype(np.int32)
        dst = (rng.zipf(1.5, 300) % n).astype(np.int32)
        ok = jnp.ones(300, bool)
        p1 = union_edges(p1, jnp.asarray(src), jnp.asarray(dst), ok)
        p2 = union_edges_dedup(
            p2, jnp.asarray(src), jnp.asarray(dst), ok,
            unique_cap=256, tail_cap=64,
        )
        assert labels_of(p1, n) == labels_of(p2, n), step
