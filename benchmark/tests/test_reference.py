"""The reference equals a per-edge union-find; the generator keeps the
degree law, the sizes and the seed's part that the cell relies on."""

import numpy as np
import pytest

from benchmark import synth
from benchmark.reference import cc


def per_edge_union_find(src, dst, n_v: int) -> np.ndarray:
    """The slowest plain form, one edge at a time: the test oracle."""
    parent = list(range(n_v))
    seen = [False] * n_v

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for u, v in zip(np.asarray(src).tolist(), np.asarray(dst).tolist()):
        seen[u] = seen[v] = True
        ru, rv = find(u), find(v)
        if ru != rv:
            parent[max(ru, rv)] = min(ru, rv)
    return np.asarray([find(x) if seen[x] else -1 for x in range(n_v)],
                      np.int32)


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 7])
@pytest.mark.parametrize("n_v", [1, 97, 4096])
def test_reference_equals_per_edge_union_find(seed, n_v):
    rng = np.random.default_rng(seed)
    n_e = int(rng.integers(0, 3000))
    src = rng.integers(0, n_v, n_e).astype(np.int32)
    dst = rng.integers(0, n_v, n_e).astype(np.int32)
    np.testing.assert_array_equal(cc.labels(src, dst, n_v),
                                  per_edge_union_find(src, dst, n_v))


GRAPH = {"vertices": 5003, "graph_vertices": 4953, "edges": 20000,
         "degree_exponent": 2.276, "graph_seed": 3}


def test_reference_on_the_generated_graph():
    n_v = GRAPH["vertices"]
    src, dst = synth.edges(GRAPH, 9)
    want = per_edge_union_find(src, dst, n_v)
    np.testing.assert_array_equal(cc.labels(src, dst, n_v), want)
    # canonical form: every label is the smallest slot of its component
    seen = want >= 0
    assert seen.sum() == GRAPH["graph_vertices"]
    assert np.all(want[seen] <= np.nonzero(seen)[0])
    assert np.all(want[want[seen]] == want[seen])


@pytest.mark.parametrize("n_v,n_e", [(5003, 20000), (100_003, 1 << 17),
                                     (41_652_230, 1 << 26)])
def test_degrees_follow_the_rank_law(n_v, n_e):
    d = synth.degrees(n_v, n_e, 2.276)
    assert d.size == n_v and d.sum() == 2 * n_e and d.min() >= 1
    assert np.all(np.diff(d) <= 0)
    beta = 1 / 1.276
    # the head follows r^-beta: rank 10's degree over rank 100's is 10^beta
    assert d[9] / d[99] == pytest.approx(10 ** beta, rel=0.02)


def test_full_size_degrees_are_the_ones_the_config_states():
    d = synth.degrees(41_235_708, 1 << 26, 2.276)
    assert d[0] == 720_025 and int((d == 1).sum()) == 28_936_931
    assert d.min() == 1


def test_seeds_place_one_graph_on_slots_keeping_order():
    """Same seed, same edges; another seed, other slots but the same
    edges in the same order up to a map that keeps the slots' order, so
    every min-root and first-seen decision comes out the same."""
    one = synth.edges(GRAPH, 2**31 + 3)
    two = synth.edges(GRAPH, 2**31 + 3)
    three = synth.edges(GRAPH, 2**40 + 4)
    assert all(np.array_equal(x, y) for x, y in zip(one, two))
    assert not np.array_equal(one[0], three[0])

    def ranks(e):
        ids = np.unique(np.concatenate(e))
        assert ids.size == GRAPH["graph_vertices"]  # every vertex has an edge
        return [np.searchsorted(ids, x) for x in e]

    for x, y in zip(ranks(one), ranks(three)):
        np.testing.assert_array_equal(x, y)
    want = np.sort(synth.degrees(GRAPH["graph_vertices"], GRAPH["edges"],
                                 GRAPH["degree_exponent"]))
    s, d = one
    assert s.dtype == np.int32 and s.size == d.size == GRAPH["edges"]
    deg = np.bincount(np.concatenate([s, d]), minlength=GRAPH["vertices"])
    np.testing.assert_array_equal(np.sort(deg[deg > 0]), want)


def test_shuffle_is_a_uniform_permutation():
    x = np.arange(16, dtype=np.int32)
    first = np.zeros(16, int)
    for seed in range(800):
        y = synth.shuffle(x, seed)
        np.testing.assert_array_equal(np.sort(y), x)
        first[y[0]] += 1
    # each element leads about 800/16 = 50 times
    assert first.min() > 20 and first.max() < 85
    big = np.arange(1 << 20, dtype=np.int32)
    y = synth.shuffle(big, 5)
    np.testing.assert_array_equal(np.sort(y), big)
    # no trace of the input order: lag-1 correlation near 0
    assert abs(np.corrcoef(y[:-1], y[1:])[0, 1]) < 0.01


def test_slots_are_a_bijection_and_kept_slots_ascend():
    for seed in (0, 2**33 + 9):
        s = synth.slots(seed, 41_652_230 // 64)
        assert np.unique(s).size == s.size
        k = synth.kept(seed, 41_652_230 // 64, 41_235_708 // 64)
        assert k.size == 41_235_708 // 64 and np.all(np.diff(k) > 0)
