"""Triangle algorithms: ITCase parity, operator-level exactness, estimator
convergence (T/example/test/TriangleCountTest.java,
WindowTrianglesITCase.java + ExamplesTestData.java)."""

import numpy as np
import pytest

from gelly_tpu import TimeCharacteristic, edge_stream_from_edges
from gelly_tpu.library.triangles import (
    exact_triangle_count,
    sampled_triangle_count,
    window_triangles,
)

# ExamplesTestData.TRIANGLES_DATA: (src, dst, event-time ms)
TRIANGLES_DATA = [
    (1, 2, 100), (1, 3, 150), (3, 2, 200), (2, 4, 250), (3, 4, 300),
    (3, 5, 350), (4, 5, 400), (4, 6, 450), (6, 5, 500), (5, 7, 550),
    (6, 7, 600), (8, 6, 650), (7, 8, 700), (7, 9, 750), (8, 9, 800),
    (10, 8, 850), (9, 10, 900), (9, 11, 950), (10, 11, 1000),
]


def triangles_stream(chunk_size=4):
    return edge_stream_from_edges(
        [(s, d, float(t)) for s, d, t in TRIANGLES_DATA],
        vertex_capacity=32, chunk_size=chunk_size,
        time=TimeCharacteristic.EVENT,
        ts_fn=lambda s, d, v: v.astype(np.int64),
    )


def test_window_triangles_itcase_golden():
    # WindowTrianglesITCase: window 400ms -> counts {0: 2, 1: 3, 2: 2}
    # (golden "(2,399) (3,799) (2,1199)" as (count, window max ts)).
    s = triangles_stream()
    got = dict(window_triangles(s, 400))
    assert got == {0: 2, 1: 3, 2: 2}


def test_window_triangles_chunk_size_invariant():
    for cs in (1, 3, 19):
        got = dict(window_triangles(triangles_stream(cs), 400))
        assert got == {0: 2, 1: 3, 2: 2}, cs


def test_window_triangles_duplicate_edges_counted_once():
    edges = [(1, 2, 1.0), (2, 3, 2.0), (1, 3, 3.0), (1, 2, 4.0), (2, 1, 5.0)]
    s = edge_stream_from_edges(
        edges, vertex_capacity=8, chunk_size=2,
        time=TimeCharacteristic.EVENT, timestamps=np.array([0, 1, 2, 3, 4]),
    )
    assert dict(window_triangles(s, 1000)) == {0: 1}


def test_window_triangles_batched_groups_match_per_window():
    # The grouped-dispatch path (lax.map over stacked packed windows,
    # padded final group) must equal the per-window path for every batch
    # size, including batch > #windows and a partial final group.
    import jax.numpy as jnp

    from gelly_tpu.library.triangles import window_triangle_counts_batched

    want = {0: 2, 1: 3, 2: 2}
    for batch in (1, 2, 4, 8):
        wins, counts = zip(*window_triangle_counts_batched(
            triangles_stream(), 400, batch=batch
        ))
        got = dict(zip(wins, np.asarray(jnp.stack(counts)).tolist()))
        assert got == want, batch


def test_exact_triangle_count_full_graph():
    # All 19 edges, no windows: 9 triangles total
    # {1,2,3},{2,3,4},{3,4,5},{4,5,6},{5,6,7},{6,7,8},{7,8,9},{8,9,10},{9,10,11}
    s = triangles_stream()
    final = exact_triangle_count(s).final_counts()
    # ground truth via brute force
    import itertools

    adj = set()
    for a, b, _ in TRIANGLES_DATA:
        adj.add((a, b)); adj.add((b, a))
    verts = sorted({v for e in TRIANGLES_DATA for v in e[:2]})
    expected_total = sum(
        1 for a, b, c in itertools.combinations(verts, 3)
        if (a, b) in adj and (b, c) in adj and (a, c) in adj
    )
    assert final[-1] == expected_total
    # per-vertex counters: vertex participates in k triangles
    per_vertex = {
        v: sum(
            1 for a, b, c in itertools.combinations(verts, 3)
            if v in (a, b, c)
            and (a, b) in adj and (b, c) in adj and (a, c) in adj
        )
        for v in verts
    }
    per_vertex = {v: k for v, k in per_vertex.items() if k}
    assert {k: v for k, v in final.items() if k != -1} == per_vertex


def test_exact_triangle_order_and_chunking_invariant():
    rng = np.random.default_rng(11)
    for cs in (1, 5, 32):
        edges = [(s, d, float(t)) for s, d, t in TRIANGLES_DATA]
        perm = rng.permutation(len(edges))
        s = edge_stream_from_edges(
            [edges[i] for i in perm], vertex_capacity=32, chunk_size=cs
        )
        assert exact_triangle_count(s).final_counts()[-1] == 9


def test_exact_triangle_duplicates_are_noops():
    edges = [(1, 2), (2, 3), (1, 3), (1, 2), (3, 2), (1, 3)]
    s = edge_stream_from_edges(edges, vertex_capacity=8, chunk_size=2)
    assert exact_triangle_count(s).final_counts()[-1] == 1


def test_sampled_estimator_unbiased_on_dense_graph():
    # Complete graph K12: T = C(12,3) = 220 triangles.
    import itertools

    verts = list(range(12))
    edges = [(a, b) for a, b in itertools.combinations(verts, 2)]
    rng = np.random.default_rng(5)
    rng.shuffle(edges)
    estimates = []
    for seed in range(8):
        s = edge_stream_from_edges(edges, vertex_capacity=16, chunk_size=16)
        last = None
        for last in sampled_triangle_count(
            s, num_samples=512, num_vertices=12, seed=seed
        ):
            pass
        estimates.append(last)
    mean = float(np.mean(estimates))
    # Estimator is unbiased with variance ~T*V*E/S; allow a wide band.
    assert 220 * 0.4 < mean < 220 * 1.9, estimates


def test_sampled_estimator_zero_when_no_triangles():
    edges = [(i, i + 1) for i in range(30)]  # path: no triangles
    s = edge_stream_from_edges(edges, vertex_capacity=64, chunk_size=8)
    last = None
    for last in sampled_triangle_count(s, 256, num_vertices=31, seed=1):
        pass
    assert last == 0.0


def test_sampled_estimator_mesh_matches_single_device():
    # Instance axis sharded over the 8-device mesh (broadcast deployment,
    # BroadcastTriangleCount.java:41-45): per-instance key streams make the
    # estimate identical to the single-device layout, beta psum included.
    import itertools

    from gelly_tpu.parallel import mesh as mesh_lib

    verts = list(range(12))
    edges = [(a, b) for a, b in itertools.combinations(verts, 2)]

    def run(mesh):
        s = edge_stream_from_edges(edges, vertex_capacity=16, chunk_size=16)
        return list(sampled_triangle_count(
            s, num_samples=256, num_vertices=12, seed=3, mesh=mesh
        ))

    single = run(None)
    sharded = run(mesh_lib.make_mesh(8))
    assert single == sharded
    assert run(mesh_lib.make_mesh(2)) == single


def test_sampled_estimator_skips_self_loops():
    # Self-loops close no wedge and must not enter the reservoir or the
    # edge count (they would skew the third-vertex draw past u == v).
    from gelly_tpu.core.chunk import make_chunk
    from gelly_tpu.library.triangles import _fresh_sampler, _sampler_step

    import jax.numpy as jnp

    src = np.array([1] * 40 + [1, 2, 1] + [2] * 10, np.int32)
    dst = np.array([1] * 40 + [2, 3, 3] + [2] * 10, np.int32)
    chunk = make_chunk(src, dst)
    state = _sampler_step(_fresh_sampler(128, seed=2), chunk, jnp.int32(4))
    # Only the 3 real edges count; no sampled pair is a self-loop.
    assert int(state.edge_count) == 3
    sampled = np.asarray(state.src) >= 0
    assert not (np.asarray(state.src)[sampled]
                == np.asarray(state.trg)[sampled]).any()


def test_exact_vectorized_matches_scan_reference():
    # The arrival-index slab step must agree with the literal per-edge scan
    # (IntersectNeighborhoods semantics) on totals AND per-vertex counts,
    # including duplicates, self-loops, and cross-chunk closing edges.
    from gelly_tpu.core.chunk import make_chunk
    from gelly_tpu.library.triangles import (
        _exact_step,
        _exact_step_scan,
        fresh_triangle_counts,
    )

    rng = np.random.default_rng(8)
    src = rng.integers(0, 24, 300).astype(np.int32)
    dst = rng.integers(0, 24, 300).astype(np.int32)
    a = fresh_triangle_counts(24)
    b = fresh_triangle_counts(24)
    for lo in range(0, 300, 64):
        chunk = make_chunk(src[lo:lo + 64], dst[lo:lo + 64], capacity=64)
        a = _exact_step(a, chunk)
        b = _exact_step_scan(b, chunk)
        assert int(a.total) == int(b.total)
    np.testing.assert_array_equal(np.asarray(a.counts), np.asarray(b.counts))
    np.testing.assert_array_equal(np.asarray(a.adj), np.asarray(b.adj))


def test_sampled_estimator_uses_live_vertex_count():
    # Default num_vertices follows the live table count, not the (much
    # larger) slot capacity — phantom third-vertex draws would make beta
    # nearly always 0 and the scale factor huge.
    import itertools

    verts = list(range(10))
    edges = [(a, b) for a, b in itertools.combinations(verts, 2)]

    s1 = edge_stream_from_edges(edges, vertex_capacity=1024, chunk_size=64)
    auto = list(sampled_triangle_count(s1, 256, seed=7))
    s2 = edge_stream_from_edges(edges, vertex_capacity=1024, chunk_size=64)
    explicit = list(sampled_triangle_count(s2, 256, num_vertices=10, seed=7))
    assert auto == explicit


def test_sparse_exact_matches_dense():
    # Capped-degree sparse path == dense arrival-index path, including
    # duplicates/self-loops across chunk boundaries.
    rng = np.random.default_rng(9)
    n_v, n_e = 64, 600
    edges = list(zip(rng.integers(0, n_v, n_e).tolist(),
                     rng.integers(0, n_v, n_e).tolist()))
    dense = exact_triangle_count(
        edge_stream_from_edges(edges, vertex_capacity=n_v, chunk_size=64)
    ).final_counts()
    sparse = exact_triangle_count(
        edge_stream_from_edges(edges, vertex_capacity=n_v, chunk_size=64),
        max_degree=n_v,
    ).final_counts()
    assert dense == sparse


def test_sparse_exact_million_vertex_capacity():
    # The VERDICT r1 gap: dense bool[N, N] capped N at ~10^4; the sparse
    # table runs at N = 1M with O(N * D) memory (~64MB at D = 8 vs 4TB
    # dense). Planted triangles spread across the id space.
    n_v = 1 << 20
    rng = np.random.default_rng(10)
    base = rng.choice(n_v, size=300, replace=False).astype(np.int64)
    edges = []
    for i in range(0, 300, 3):
        a, b, c = base[i], base[i + 1], base[i + 2]
        edges += [(a, b), (b, c), (a, c)]
    extra_u = rng.choice(n_v, 500).astype(np.int64)
    extra_v = rng.choice(n_v, 500).astype(np.int64)
    edges += list(zip(extra_u.tolist(), extra_v.tolist()))

    got = exact_triangle_count(
        edge_stream_from_edges(edges, vertex_capacity=n_v, chunk_size=256),
        max_degree=8,
    ).final_counts()

    # Host oracle.
    adj: dict[int, set] = {}
    total = 0
    per: dict[int, int] = {}
    seen = set()
    for u, v in edges:
        u, v = int(u), int(v)
        if u == v or (min(u, v), max(u, v)) in seen:
            continue
        seen.add((min(u, v), max(u, v)))
        common = adj.get(u, set()) & adj.get(v, set())
        total += len(common)
        for w in common:
            per[w] = per.get(w, 0) + 1
        if common:
            per[u] = per.get(u, 0) + len(common)
            per[v] = per.get(v, 0) + len(common)
        adj.setdefault(u, set()).add(v)
        adj.setdefault(v, set()).add(u)
    want = {-1: total, **{k: v for k, v in per.items() if v}}
    assert got == want
    assert total >= 100  # the planted triangles actually exercised the path


def test_sparse_exact_degree_skew_raises():
    # A hot vertex past max_degree must raise (no silent wrong counts) —
    # the Twitter-skew discipline.
    edges = [(0, i) for i in range(1, 40)]
    s = edge_stream_from_edges(edges, vertex_capacity=64, chunk_size=8)
    with pytest.raises(ValueError, match="max_degree"):
        exact_triangle_count(s, max_degree=8).final_counts()


@pytest.mark.pallas
def test_window_triangles_mxu_kernel_matches_gather():
    # Pallas MXU wedge-matrix path (interpret mode on CPU) == VPU gather path.
    s = edge_stream_from_edges(
        [(s_, d, float(t)) for s_, d, t in TRIANGLES_DATA],
        vertex_capacity=128, chunk_size=4, time=TimeCharacteristic.EVENT,
        ts_fn=lambda a, b, v: v.astype(np.int64),
    )
    got = dict(window_triangles(s, 400, method="mxu_interpret"))
    assert got == {0: 2, 1: 3, 2: 2}


@pytest.mark.pallas
def test_wedge_count_matrix_random():
    import jax.numpy as jnp

    from gelly_tpu.ops.pallas_kernels import wedge_count_matrix

    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.random((256, 256)) < 0.1)
    w = wedge_count_matrix(m, interpret=True)
    expected = np.asarray(m, np.float32).T @ np.asarray(m, np.float32)
    np.testing.assert_allclose(np.asarray(w), expected)


def test_window_triangles_sparse_matches_dense():
    # The capped-degree sparse window kernel (the large-N path) must agree
    # with the dense kernel on any stream, including duplicate edges,
    # reversed duplicates, and self-loops; across batch groupings.
    from gelly_tpu.library.triangles import window_triangle_counts_batched

    import jax.numpy as jnp

    rng = np.random.default_rng(27)
    n_v = 128
    n_e = 3000
    src = rng.integers(0, n_v, n_e)
    dst = rng.integers(0, n_v, n_e)
    ts = np.arange(n_e, dtype=np.int64)

    def stream():
        return edge_stream_from_edges(
            [(int(a), int(b), 1.0) for a, b in zip(src, dst)],
            vertex_capacity=n_v, chunk_size=512,
            time=TimeCharacteristic.EVENT, timestamps=ts,
        )

    def run(**kw):
        wins, counts = zip(*window_triangle_counts_batched(
            stream(), n_e // 5, **kw
        ))
        return dict(zip(wins, np.asarray(jnp.stack(counts)).tolist()))

    dense = run()
    for batch in (1, 4):
        assert run(max_degree=n_v, batch=batch) == dense, batch


def test_window_triangles_sparse_overflow_raises():
    from gelly_tpu.library.triangles import window_triangle_counts_batched

    # A star vertex with degree > max_degree must raise, not undercount.
    edges = [(0, i, 1.0) for i in range(1, 20)]
    s = edge_stream_from_edges(
        edges, vertex_capacity=64, chunk_size=32,
        time=TimeCharacteristic.EVENT,
        timestamps=np.arange(len(edges), dtype=np.int64),
    )
    with pytest.raises(ValueError, match="max_degree"):
        list(window_triangle_counts_batched(s, 1000, max_degree=4))


def test_window_triangles_bucketed_matches_dense():
    # The degree-bucketed sparse path (large-N workhorse) must agree with
    # the dense kernel on duplicate edges, reversed duplicates, and
    # self-loops, across batch groupings and skew (Zipf hot vertices now
    # work without a toy degree cap).
    import jax.numpy as jnp

    from gelly_tpu.library.triangles import (
        window_triangle_counts_batched,
        window_triangles_bucketed,
    )

    rng = np.random.default_rng(41)
    n_v = 128
    n_e = 3000
    src = rng.zipf(1.5, n_e) % n_v
    dst = rng.zipf(1.5, n_e) % n_v
    ts = np.arange(n_e, dtype=np.int64)

    def stream():
        return edge_stream_from_edges(
            [(int(a), int(b), 1.0) for a, b in zip(src, dst)],
            vertex_capacity=n_v, chunk_size=512,
            time=TimeCharacteristic.EVENT, timestamps=ts,
        )

    wins, counts = zip(*window_triangle_counts_batched(stream(), n_e // 5))
    dense = dict(zip(wins, np.asarray(jnp.stack(counts)).tolist()))
    for batch in (1, 3, 8):
        wins_b, counts_b = zip(*window_triangles_bucketed(
            stream(), n_e // 5, batch=batch
        ))
        got = dict(zip(wins_b, np.asarray(jnp.stack(counts_b)).tolist()))
        assert got == dense, batch


def test_window_triangles_bucketed_cap_raises_before_yield():
    from gelly_tpu.library.triangles import window_triangles_bucketed

    star = [(0, i, 1.0) for i in range(1, 20)]
    s = edge_stream_from_edges(
        star, vertex_capacity=64, chunk_size=32,
        time=TimeCharacteristic.EVENT,
        timestamps=np.zeros(len(star), dtype=np.int64),
    )
    it = window_triangles_bucketed(s, 1000, max_degree=4)
    with pytest.raises(ValueError, match="max_degree"):
        next(it)  # raises BEFORE any (possibly corrupt) count is yielded


def test_window_triangles_bucketed_million_vertex():
    from gelly_tpu.library.triangles import window_triangles_bucketed

    n_v = 1 << 20
    # Two triangles far apart in a million-slot space + noise edges.
    edges = [(10, 999_000, 1.0), (999_000, 500_000, 1.0),
             (500_000, 10, 1.0),
             (7, 8, 1.0), (8, 9, 1.0), (9, 7, 1.0),
             (1, 2, 1.0), (3, 4, 1.0)]
    s = edge_stream_from_edges(
        edges, vertex_capacity=n_v, chunk_size=8,
        time=TimeCharacteristic.EVENT,
        timestamps=np.zeros(len(edges), dtype=np.int64),
    )
    out = list(window_triangles_bucketed(s, 1000))
    assert len(out) == 1 and int(out[0][1]) == 2


def test_window_triangles_sparse_yield_overflow():
    from gelly_tpu.library.triangles import window_triangle_counts_batched

    # yield_overflow=True surfaces the per-window overflow scalar so
    # per-yield consumers can gate programmatically (ADVICE r3): clean
    # windows report 0, an overflowing window reports its dropped-entry
    # count in the SAME yielded tuple (before the deferred raise fires).
    edges = [(0, 1, 1.0), (1, 2, 1.0), (0, 2, 1.0)]  # one clean triangle
    s = edge_stream_from_edges(
        edges, vertex_capacity=64, chunk_size=32,
        time=TimeCharacteristic.EVENT,
        timestamps=np.zeros(len(edges), dtype=np.int64),
    )
    out = list(window_triangle_counts_batched(
        s, 1000, max_degree=4, yield_overflow=True
    ))
    assert len(out) == 1
    w, count, over = out[0]
    assert int(count) == 1 and int(over) == 0

    star = [(0, i, 1.0) for i in range(1, 20)]  # degree 19 > max_degree 4
    s = edge_stream_from_edges(
        star, vertex_capacity=64, chunk_size=32,
        time=TimeCharacteristic.EVENT,
        timestamps=np.zeros(len(star), dtype=np.int64),
    )
    it = window_triangle_counts_batched(
        s, 1000, max_degree=4, yield_overflow=True
    )
    w, count, over = next(it)
    assert int(over) > 0  # corrupt window flagged in-band
    with pytest.raises(ValueError, match="max_degree"):
        list(it)  # the deferred guard still fires


def test_window_triangles_sparse_million_vertex_capacity():
    # The whole point of the sparse kernel: vertex capacity where the
    # dense bool[N, N] adjacency (and the packed i32 format) cannot exist.
    from gelly_tpu.library.triangles import window_triangles

    n_v = 1 << 20
    rng = np.random.default_rng(35)
    ids = rng.choice(n_v, 9, replace=False).tolist()
    a, b, c, d, e, f, g, h, i = ids
    edges = [
        # window 0: one triangle + a chord pair
        (a, b, 1.0), (b, c, 1.0), (c, a, 1.0), (d, e, 1.0),
        # window 1: two triangles sharing edge (f, g)
        (f, g, 1.0), (g, h, 1.0), (h, f, 1.0), (g, i, 1.0), (i, f, 1.0),
    ]
    ts = np.array([0, 1, 2, 3, 10, 11, 12, 13, 14], dtype=np.int64)
    s = edge_stream_from_edges(
        edges, vertex_capacity=n_v, chunk_size=4,
        time=TimeCharacteristic.EVENT, timestamps=ts,
    )
    got = dict(window_triangles(s, 10, max_degree=8))
    assert got == {0: 1, 1: 2}


def test_arrival_rebase_lossless():
    # VERDICT r2 item 8: streaming past the i32 arrival budget must rebase
    # the summary losslessly instead of raising. A tiny budget mocks the
    # 2^31 counter; counts must match the unbounded run exactly, with
    # cross-chunk duplicates in the stream (the dedup interacts with
    # rebased indices).
    from gelly_tpu.library.triangles import exact_triangle_count

    rng = np.random.default_rng(21)
    n_e = 600
    src = rng.integers(0, 64, n_e).astype(np.int64)
    dst = rng.integers(0, 64, n_e).astype(np.int64)
    src[200:300] = src[:100]  # duplicates spanning future rebases
    dst[200:300] = dst[:100]

    def stream():
        return edge_stream_from_edges(
            list(zip(src.tolist(), dst.tolist())),
            vertex_capacity=64, chunk_size=64,
        )

    base = exact_triangle_count(stream()).final_counts()
    assert base[-1] > 0
    for budget in (130, 200, 400):
        t = exact_triangle_count(stream(), arrival_budget=budget)
        assert t.final_counts() == base, budget
        assert t.stats["rebases"] > 0, budget
    # Sparse path: same contract.
    t = exact_triangle_count(stream(), max_degree=64, arrival_budget=200)
    assert t.final_counts() == base
    assert t.stats["rebases"] > 0
