"""Twitter-2010 follower degrees, the deployment of the benchmark's
``degrees-twitter2010-file`` cell, at a small size on the CPU:

- ``stream.aggregate(degree_aggregate(n, codec="sparse"))`` over a
  binary edge file of the benchmark's generator equals the plain
  reference (``benchmark/reference/degrees.py``) exactly, in every
  window (each emits the degrees of the file so far) and at the end;
- the reference equals the degrees the generator designed, so it is
  checked against the graph and not against the program;
- the ``deg.*`` bus counters equal what the chunks hold: distinct
  endpoints per chunk, the power-of-two buckets they ship in, edges;
- the compiled folds carry the ``deg.fold`` scope.
"""

import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402

from benchmark import synth  # noqa: E402
from benchmark.reference import degrees as reference  # noqa: E402
from gelly_tpu import obs  # noqa: E402
from gelly_tpu.ingest import edge_stream_from_sharded_file  # noqa: E402
from gelly_tpu.library.degrees import degree_aggregate  # noqa: E402
from gelly_tpu.parallel.mesh import make_mesh  # noqa: E402

N_V = 20011  # the benchmark's tiny cut of the cell
GRAPH = {"vertices": N_V, "graph_vertices": N_V - N_V // 100,
         "edges": (1 << 15) + 77, "degree_exponent": 2.276,
         "graph_seed": 777}
CHUNK = 1 << 12
MERGE_EVERY = 4  # chunks a window: windows of 4, 4 and 1 chunks


def _edge_file(tmp_path, seed):
    src, dst = synth.edges(GRAPH, seed)
    rec = np.empty((src.shape[0], 2), "<i8")
    rec[:, 0], rec[:, 1] = src, dst
    path = str(tmp_path / "edges.bin")
    rec.tofile(path)
    return path, src, dst


def _run(path, **executor):
    stream = edge_stream_from_sharded_file(path, N_V, shards=1,
                                           chunk_size=CHUNK)
    out = stream.aggregate(degree_aggregate(N_V, codec="sparse"),
                           mesh=make_mesh(1), merge_every=MERGE_EVERY,
                           **executor)
    return [np.asarray(w) for w in out]


@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_program_equals_reference_over_a_synth_file(tmp_path, seed):
    path, src, dst = _edge_file(tmp_path, seed)
    windows = _run(path)
    n_chunks = -(-GRAPH["edges"] // CHUNK)
    assert len(windows) == -(-n_chunks // MERGE_EVERY)
    for w, got in enumerate(windows):
        end = min((w + 1) * MERGE_EVERY * CHUNK, GRAPH["edges"])
        want = reference.labels(src[:end], dst[:end], N_V)
        assert got.dtype == np.int64 and got.shape == (N_V,)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_reference_equals_the_designed_degrees(seed):
    k = GRAPH["graph_vertices"]
    src, dst = synth.edges(GRAPH, seed)
    want = np.zeros(N_V, np.int64)
    rank_slot = synth.kept(seed, N_V, k)[synth.slots(GRAPH["graph_seed"], k)]
    want[rank_slot] = synth.degrees(k, GRAPH["edges"],
                                    GRAPH["degree_exponent"])
    got = reference.labels(src, dst, N_V)
    np.testing.assert_array_equal(got, want)
    assert got.sum() == 2 * GRAPH["edges"]
    assert np.count_nonzero(got) == k


def test_reference_counts_a_self_loop_twice():
    got = reference.labels(np.array([2, 0]), np.array([2, 1]), 4)
    np.testing.assert_array_equal(got, [1, 1, 2, 0])
    assert got.dtype == np.int64


def test_degree_counters_equal_the_chunks(tmp_path):
    path, src, dst = _edge_file(tmp_path, 23)
    pairs = lanes = 0
    for lo in range(0, src.shape[0], CHUNK):
        n = np.unique(np.concatenate([src[lo:lo + CHUNK],
                                      dst[lo:lo + CHUNK]])).size
        pairs += n
        lanes += max(1024, 1 << (n - 1).bit_length())
    with obs.scope() as bus:
        _run(path)
        c = dict(bus.counters)
    assert c["deg.codec_edges"] == GRAPH["edges"]
    assert c["deg.fold_pairs"] == pairs
    assert c["deg.fold_lanes"] == lanes
    assert pairs < lanes < 2 * pairs + 1024


@pytest.mark.parametrize("fold_batch,i32_share", [(1, 1), (MERGE_EVERY, 0)])
def test_i32_fold_counter_follows_the_payload_dtype(tmp_path, fold_batch,
                                                    i32_share):
    # Executor defaults ship each chunk's i32 deltas, one row a fold, and
    # every pair takes the i32 scatter; fold_batch > 1 combines a group's
    # chunks into one i64 row, which keeps the int64 scatter-add.
    path, src, dst = _edge_file(tmp_path, 29)
    with obs.scope() as bus:
        windows = _run(path, fold_batch=fold_batch)
        c = dict(bus.counters)
    np.testing.assert_array_equal(windows[-1],
                                  reference.labels(src, dst, N_V))
    assert c["deg.fold_pairs"] > 0
    assert c["deg.fold_i32_pairs"] == i32_share * c["deg.fold_pairs"]


def _pairs(rng, n, k, pad):
    """One codec row: ``k`` distinct vertices of ``[0, n)`` with i32
    deltas, then ``pad`` lanes of -1 padding."""
    v = np.full(k + pad, -1, np.int32)
    v[:k] = rng.choice(n, k, replace=False)
    d = np.zeros(k + pad, np.int32)
    d[:k] = rng.integers(-40, 40, k)
    return v, d


def _fold_case(name):
    """(state, payload) for one case of the sparse degree fold."""
    rng = np.random.default_rng(sum(map(ord, name)))
    n, big = 4096, 2**31 - 1
    state = rng.integers(0, 1000, n).astype(np.int64)
    if name == "i32_one_row":
        # deletions, and garbage behind padding lanes that must not land
        v, d = _pairs(rng, n, 700, 324)
        d[:50] = -rng.integers(1, 5, 50)
        d[700:] = 7
        return state, {"v": v[None], "d": d[None]}
    if name == "i32_three_rows_share_vertices":
        rows = [_pairs(rng, 64, 40, 24) for _ in range(3)]
        v = np.stack([r[0] for r in rows])
        d = np.stack([r[1] for r in rows])
        v[:, 0], d[:, 0] = 5, big  # 3 x (2^31 - 1) on one vertex
        return state, {"v": v, "d": d}
    if name == "i32_state_near_2_40":
        v, d = _pairs(rng, n, 900, 124)
        d[:450:2], d[1:450:2] = big, -big
        state += 2**40
        return state, {"v": v[None], "d": d[None]}
    assert name == "i64_group_combined"
    agg = degree_aggregate(n, codec="sparse")
    chunks = []
    for _ in range(3):
        v, d = _pairs(rng, n, 600, 0)
        v[0], d[0] = 11, big
        chunks.append({"v": v, "d": d})
    payload = agg.stack_payloads(chunks, 1)
    assert payload["d"].dtype == np.int64 and payload["d"].shape[0] == 1
    return state, payload


@pytest.mark.parametrize("name", ["i32_one_row",
                                  "i32_three_rows_share_vertices",
                                  "i32_state_near_2_40",
                                  "i64_group_combined"])
def test_sparse_fold_equals_an_int64_reference(name):
    state, payload = _fold_case(name)
    v, d = payload["v"].reshape(-1), payload["d"].reshape(-1)
    want = state.copy()
    np.add.at(want, v[v >= 0], d[v >= 0].astype(np.int64))
    agg = degree_aggregate(state.shape[0], codec="sparse")
    got = np.asarray(jax.jit(agg.fold_compressed)(
        jax.numpy.asarray(state),
        {k: jax.numpy.asarray(x) for k, x in payload.items()}))
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, want)
    # every case but the first leaves a degree past what i32 holds
    assert want.max() > 2**31 or name == "i32_one_row"


def _op_names(fn, *args) -> set:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("program", ["fold", "fold_compressed_sparse"])
def test_deg_fold_scope_reaches_the_compiled_program(program):
    from gelly_tpu.core.chunk import make_chunk

    agg = degree_aggregate(64, codec="sparse")
    if program == "fold":
        ids = np.arange(8, dtype=np.int32)
        arg = make_chunk(ids, ids[::-1].copy(), capacity=16)
        fn = agg.fold
    else:
        arg = {"v": np.full((1, 1024), -1, np.int32),
               "d": np.zeros((1, 1024), np.int32)}
        fn = agg.fold_compressed
    assert fn.__name__ == program
    names = _op_names(fn, agg.init(), arg)
    assert any("/deg.fold/" in n for n in names), sorted(names)
