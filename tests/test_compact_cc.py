"""Compact-root-space CC plan (``codec="compact"``) — the large-N device
fold with zero per-dispatch O(capacity) work (VERDICT r3 item 1).

Asserts: exact label parity vs the sparse-codec plan and the numpy oracle
(single shard and 8-virtual-device mesh), session id-assignment invariants,
rerun isolation (``on_run_start``), checkpoint/resume session rebuild
(``on_resume``), and the overflow guard.
"""

import numpy as np
import pytest

from gelly_tpu.core.io import EdgeChunkSource
from gelly_tpu.core.stream import edge_stream_from_source
from gelly_tpu.core.vertices import IdentityVertexTable
from gelly_tpu.library.connected_components import (
    cc_labels_numpy,
    connected_components,
)
from gelly_tpu.ops.compact_space import CompactIdSession, CompactSpaceOverflow
from gelly_tpu.parallel import mesh as mesh_lib

N_V = 512


def _rand_edges(n_e=4000, seed=0, n_v=N_V):
    rng = np.random.default_rng(seed)
    # Zipf-ish skew: exercise repeated hot vertices across chunks.
    src = rng.zipf(1.4, n_e) % n_v
    dst = rng.zipf(1.4, n_e) % n_v
    return src.astype(np.int64), dst.astype(np.int64)


def _stream(src, dst, chunk_size=256, n_v=N_V):
    return edge_stream_from_source(
        EdgeChunkSource(src, dst, chunk_size=chunk_size,
                        table=IdentityVertexTable(n_v)),
        n_v,
    )


# --------------------------- session invariants ------------------------ #


def test_session_assign_lookup_roundtrip():
    s = CompactIdSession(64)
    ids = np.array([9, 3, 40, 7], np.int32)
    cids, new_ids, base = s.assign(ids)
    assert base == 0 and sorted(new_ids) == [3, 7, 9, 40]
    assert sorted(cids.tolist()) == [0, 1, 2, 3]
    # Re-assign with overlap: stable cids, only fresh ids get new cids.
    cids2, new2, base2 = s.assign(np.array([3, 11, 9], np.int32))
    assert base2 == 4 and new2.tolist() == [11]
    assert cids2[0] == cids[1] and cids2[2] == cids[0] and cids2[1] == 4
    assert np.array_equal(s.lookup(np.array([40, 11])), [cids[2], 4])
    with pytest.raises(KeyError):
        s.lookup(np.array([999]))


def test_session_lookup_empty_raises_keyerror():
    s = CompactIdSession(8)
    with pytest.raises(KeyError):
        s.lookup(np.array([5], np.int32))
    assert s.lookup(np.empty(0, np.int32)).shape == (0,)


def test_session_turn_ordering():
    # Concurrent stagers must take the stateful assign step in stream
    # order: a unit staged out of order blocks in await_turn until every
    # earlier unit completed (code-review r4: out-of-order assignment put
    # first-seen records in later-folded payloads, corrupting intermediate
    # emissions and checkpoint resume).
    import threading

    s = CompactIdSession(64)
    order: list[int] = []

    def worker(seq, ids):
        s.await_turn(seq)
        try:
            s.assign(np.asarray(ids, np.int32))
            order.append(seq)
        finally:
            s.complete_turn(seq)

    # Start unit 1 first; it must wait for unit 0.
    t1 = threading.Thread(target=worker, args=(1, [7, 8]))
    t1.start()
    import time

    time.sleep(0.05)
    assert order == []  # unit 1 parked
    t0 = threading.Thread(target=worker, args=(0, [7, 9]))
    t0.start()
    t0.join(5)
    t1.join(5)
    assert order == [0, 1]
    # Unit 0 assigned 7 -> cid 0: first-seen order follows stream order.
    assert np.array_equal(s.lookup(np.array([7, 9, 8])), [0, 1, 2])


def test_session_turn_wait_accounting():
    # Blocked time in await_turn accumulates into session.wait_s (the
    # engine reclassifies it out of ingest_compress busy at teardown: a
    # serial run never waits here, so booking it as compress work would
    # inflate the overlap accounting's serial-cost comparison). In-turn
    # awaits must add nothing, and reset() zeroes the accumulator.
    import threading
    import time

    s = CompactIdSession(64)
    s.await_turn(0)  # own turn: no wait booked
    s.complete_turn(0)
    assert s.wait_s == 0.0

    t2 = threading.Thread(target=lambda: (s.await_turn(2),
                                          s.complete_turn(2)))
    t2.start()
    time.sleep(0.05)  # unit 2 parks behind unit 1
    s.await_turn(1)
    s.complete_turn(1)
    t2.join(5)
    assert not t2.is_alive()
    assert s.wait_s >= 0.04  # the park was measured
    s.reset()
    assert s.wait_s == 0.0


def test_session_turn_release_before_turn_unparks_later_units():
    # A unit that fails BEFORE its turn releases out of order; the release
    # must be remembered (not discarded) so the turn counter skips the
    # dead unit once earlier units finish — otherwise later units park
    # forever (code-review r4 follow-up).
    import threading

    s = CompactIdSession(64)
    s.complete_turn(2)  # unit 2 died early, _turn still 0
    done = []

    def unit3():
        s.await_turn(3)
        done.append(3)
        s.complete_turn(3)

    t3 = threading.Thread(target=unit3)
    t3.start()
    for seq in (0, 1):
        s.await_turn(seq)
        s.complete_turn(seq)
    t3.join(5)
    assert done == [3]  # unit 3 unparked through the dead unit's slot
    assert not t3.is_alive()


def test_compact_parity_with_two_ingest_workers():
    src, dst = _rand_edges(n_e=5000, seed=29)
    oracle = cc_labels_numpy(src.astype(np.int32), dst.astype(np.int32),
                             None, N_V)
    agg = connected_components(N_V, codec="compact", compact_capacity=N_V)
    res = _stream(src, dst, chunk_size=128).aggregate(
        agg, mesh=mesh_lib.make_mesh(1), merge_every=4, fold_batch=4,
        ingest_workers=2, prefetch_depth=4,
    )
    # Drain every window emission: each must equal its prefix oracle —
    # an out-of-order assignment would leave a window's new vertices
    # undecodable (-1) mid-stream (the ordered-staging guarantee).
    emitted = [np.asarray(e) for e in res]
    assert np.array_equal(emitted[-1], oracle)
    for i, lab in enumerate(emitted):
        n_pref = min((i + 1) * 4 * 128, src.shape[0])
        pref = cc_labels_numpy(
            src[:n_pref].astype(np.int32), dst[:n_pref].astype(np.int32),
            None, N_V,
        )
        assert np.array_equal(lab, pref), i


def test_session_overflow_raises():
    s = CompactIdSession(4)
    s.assign(np.array([1, 2, 3], np.int32))
    with pytest.raises(CompactSpaceOverflow):
        s.assign(np.array([10, 11], np.int32))


def test_session_rebuild_from_vertex_of():
    s = CompactIdSession(16)
    s.assign(np.array([30, 10, 20], np.int32))
    vertex_of = np.full(16, -1, np.int32)
    vertex_of[[0, 1, 2]] = [10, 20, 30]  # first-seen sorted order
    s2 = CompactIdSession(16)
    s2.rebuild_from_vertex_of(vertex_of)
    assert np.array_equal(s2.lookup(np.array([10, 20, 30])), [0, 1, 2])
    assert s2.assigned == 3
    # Holes (staged-but-unfolded cids) stay dead: next alloc skips past.
    vertex_of[5] = 50
    s2.rebuild_from_vertex_of(vertex_of)
    _, _, base = s2.assign(np.array([60], np.int32))
    assert base == 6


# ------------------------------- parity -------------------------------- #


def test_compact_label_parity_single_shard():
    src, dst = _rand_edges(seed=3)
    oracle = cc_labels_numpy(src.astype(np.int32), dst.astype(np.int32),
                             None, N_V)
    agg = connected_components(N_V, codec="compact", compact_capacity=N_V)
    res = _stream(src, dst).aggregate(
        agg, mesh=mesh_lib.make_mesh(1), merge_every=4, fold_batch=2
    )
    labels = np.asarray(res.result())
    assert np.array_equal(labels, oracle)


def test_compact_matches_sparse_plan():
    src, dst = _rand_edges(seed=11)
    agg_c = connected_components(N_V, codec="compact", compact_capacity=N_V)
    agg_s = connected_components(N_V, codec="sparse")
    m1 = mesh_lib.make_mesh(1)
    lab_c = np.asarray(
        _stream(src, dst).aggregate(agg_c, mesh=m1, merge_every=2).result()
    )
    lab_s = np.asarray(
        _stream(src, dst).aggregate(agg_s, mesh=m1, merge_every=2).result()
    )
    assert np.array_equal(lab_c, lab_s)


def test_compact_wire_formats_agree():
    """The segment wire (fused native unit codec, round 5) and the pairs
    wire (per-chunk combine + (v, ri) rows) must emit identical labels —
    and both must match the numpy oracle — across batched windows."""
    from gelly_tpu.library.connected_components import (
        connected_components_compact,
    )

    src, dst = _rand_edges(seed=23)
    oracle = cc_labels_numpy(src.astype(np.int32), dst.astype(np.int32),
                             None, N_V)
    m1 = mesh_lib.make_mesh(1)
    labs = {}
    for wire in ("segments", "pairs"):
        agg = connected_components_compact(
            N_V, compact_capacity=N_V, wire=wire
        )
        labs[wire] = np.asarray(
            _stream(src, dst).aggregate(
                agg, mesh=m1, merge_every=4, fold_batch=2
            ).result()
        )
    assert np.array_equal(labs["segments"], oracle)
    assert np.array_equal(labs["pairs"], oracle)


def test_unit_segments_root_first_invariant():
    """Wire invariant the device fold relies on: each segment's FIRST
    member is the component root (canonical min vertex), and lengths sum
    to the member count."""
    from gelly_tpu.utils import native

    if not native.unit_segments_available():
        import pytest

        pytest.skip("native unit segment codec unavailable")
    rng = np.random.default_rng(7)
    src = (rng.zipf(1.3, 20000) % 3000).astype(np.int32)
    dst = (rng.zipf(1.3, 20000) % 3000).astype(np.int32)
    m, ln = native.cc_unit_forest_segments(src, dst, None, 3000, block=997)
    assert int(ln.sum()) == m.shape[0]
    starts = np.concatenate([[0], np.cumsum(ln)[:-1]])
    seg_of = np.repeat(np.arange(ln.shape[0]), ln)
    roots = m[starts]
    # Root-first + canonical min: the root is the minimum of its segment.
    mins = np.full(ln.shape[0], np.iinfo(np.int32).max)
    np.minimum.at(mins, seg_of, m)
    assert np.array_equal(roots, mins)


@pytest.mark.parametrize("lengths, capm", [
    ([[3, 1, 4, 2]], 12),                      # one row, padding lanes
    ([[2, 2], [1, 0], [4, 0]], 6),             # several rows, trailing 0s
    ([[2, 0, 0, 3, 0, 1, 0, 0]], 8),           # zeros mid-row and trailing
    ([[0, 0, 0], [3, 2, 0]], 7),               # an all-zero row
    ([[4, 4], [5, 3]], 8),                     # full rows: total == capm
    ([[1, 1, 1, 1, 1, 0]], 9),                 # single-member segments
    ([[1, 3, 1, 0, 2, 1, 1, 0]], 9),           # full, with a zero inside
])
def test_lane_segment_starts_matches_search(lengths, capm):
    """Every valid lane's segment start equals a search over the
    lengths' cumsum (numpy ``searchsorted``, side right); lanes at and
    past each row's total are invalid."""
    import jax.numpy as jnp

    from gelly_tpu.library.connected_components import lane_segment_starts

    ln = np.asarray(lengths, np.int32)
    ri, valid = lane_segment_starts(jnp.asarray(ln), capm)
    ri, valid = np.asarray(ri), np.asarray(valid)
    lane = np.arange(capm)
    for k in range(ln.shape[0]):
        cum = np.cumsum(ln[k])
        seg = np.searchsorted(cum, lane, side="right")
        want = (cum - ln[k])[np.minimum(seg, ln.shape[1] - 1)]
        ok = lane < cum[-1]
        assert np.array_equal(valid[k], ok)
        assert np.array_equal(ri[k][ok], want[ok])


def test_lane_segment_starts_random_rows():
    """Random length rows with zero-length segments anywhere, padded to
    one lane count like the wire's buckets: the same starts as the
    segments laid out on the host, on every valid lane."""
    import jax.numpy as jnp

    from gelly_tpu.library.connected_components import lane_segment_starts

    rng = np.random.default_rng(11)
    capm = 160
    for _ in range(20):
        ln = rng.integers(0, 5, size=(3, 40)).astype(np.int32)
        ln[rng.random((3, 40)) < 0.3] = 0
        ri, valid = lane_segment_starts(jnp.asarray(ln), capm)
        ri, valid = np.asarray(ri), np.asarray(valid)
        for k in range(3):
            starts = np.repeat(np.cumsum(ln[k]) - ln[k], ln[k])
            t = starts.shape[0]
            assert np.array_equal(valid[k], np.arange(capm) < t)
            assert np.array_equal(ri[k][:t], starts)


def test_segment_wire_many_small_components():
    """Many small components put many segments in every row, so a lane
    given another segment's start would join two components: the segment
    wire's labels must still equal the oracle's."""
    from gelly_tpu.library.connected_components import (
        connected_components_compact,
    )

    rng = np.random.default_rng(29)
    block = rng.integers(0, N_V // 4, 3000)
    src = (4 * block + rng.integers(0, 4, 3000)).astype(np.int64)
    dst = (4 * block + rng.integers(0, 4, 3000)).astype(np.int64)
    oracle = cc_labels_numpy(src.astype(np.int32), dst.astype(np.int32),
                             None, N_V)
    agg = connected_components_compact(N_V, compact_capacity=N_V,
                                       wire="segments")
    lab = np.asarray(
        _stream(src, dst).aggregate(
            agg, mesh=mesh_lib.make_mesh(1), merge_every=4, fold_batch=2
        ).result()
    )
    assert np.array_equal(lab, oracle)


def test_compact_rerun_same_agg_instance():
    # on_run_start must reset the session: a second run with the same agg
    # re-assigns ids from scratch (fresh device state needs fresh newv).
    src, dst = _rand_edges(seed=5)
    oracle = cc_labels_numpy(src.astype(np.int32), dst.astype(np.int32),
                             None, N_V)
    agg = connected_components(N_V, codec="compact", compact_capacity=N_V)
    for _ in range(2):
        labels = np.asarray(
            _stream(src, dst).aggregate(
                agg, mesh=mesh_lib.make_mesh(1), merge_every=4
            ).result()
        )
        assert np.array_equal(labels, oracle)


def test_compact_mesh_parity():
    src, dst = _rand_edges(n_e=6000, seed=7)
    oracle = cc_labels_numpy(src.astype(np.int32), dst.astype(np.int32),
                             None, N_V)
    m = mesh_lib.make_mesh()  # all 8 virtual CPU devices
    agg = connected_components(N_V, codec="compact", compact_capacity=N_V)
    res = _stream(src, dst).aggregate(
        agg, mesh=m, merge_every=8, fold_batch=8
    )
    labels = np.asarray(res.result())
    assert np.array_equal(labels, oracle)


def test_compact_per_window_emissions_improve():
    # Every window emission is a valid prefix CC labeling; the final one is
    # the full-stream oracle (continuously-improving summary semantics).
    src, dst = _rand_edges(n_e=2000, seed=13)
    agg = connected_components(N_V, codec="compact", compact_capacity=N_V)
    emitted = [
        np.asarray(e)
        for e in _stream(src, dst, chunk_size=500).aggregate(
            agg, mesh=mesh_lib.make_mesh(1), merge_every=1
        )
    ]
    assert len(emitted) == 4
    for i, lab in enumerate(emitted):
        n_pref = min((i + 1) * 500, src.shape[0])
        pref = cc_labels_numpy(
            src[:n_pref].astype(np.int32), dst[:n_pref].astype(np.int32),
            None, N_V,
        )
        assert np.array_equal(lab, pref)


def test_compact_checkpoint_resume(tmp_path):
    src, dst = _rand_edges(n_e=3000, seed=17)
    oracle = cc_labels_numpy(src.astype(np.int32), dst.astype(np.int32),
                             None, N_V)
    ckpt = str(tmp_path / "cc_compact.npz")
    agg = connected_components(N_V, codec="compact", compact_capacity=N_V)
    # First run: stop after a few windows by draining only part of the
    # stream (checkpoint fires per closed window).
    m1 = mesh_lib.make_mesh(1)
    it = iter(_stream(src, dst, chunk_size=250).aggregate(
        agg, mesh=m1, merge_every=2, checkpoint_path=ckpt
    ))
    next(it)
    next(it)
    del it
    # Resume with a FRESH agg instance (fresh session): on_resume must
    # rebuild the id table from the checkpointed vertex_of.
    agg2 = connected_components(N_V, codec="compact", compact_capacity=N_V)
    res = _stream(src, dst, chunk_size=250).aggregate(
        agg2, mesh=m1, merge_every=2, checkpoint_path=ckpt, resume=True
    )
    labels = np.asarray(res.result())
    assert np.array_equal(labels, oracle)


def test_windowed_codec_cc_parity():
    # VERDICT r3 item 8: the ingest codec engages in window_ms mode —
    # chunks are masked to one window before compression, so payloads are
    # window-scoped without carrying timestamps. Per-window emissions must
    # match the raw windowed fold exactly, for the sparse AND compact
    # codecs (the compact plan previously could not run windowed at all).
    from gelly_tpu.core.io import EdgeChunkSource, TimeCharacteristic

    rng = np.random.default_rng(19)
    n = 1000
    src = (rng.zipf(1.4, n) % N_V).astype(np.int64)
    dst = (rng.zipf(1.4, n) % N_V).astype(np.int64)
    ts = np.sort(rng.integers(0, 400, n)).astype(np.int64)

    def stream():
        return edge_stream_from_source(
            EdgeChunkSource(src, dst, timestamps=ts, chunk_size=128,
                            table=IdentityVertexTable(N_V),
                            time=TimeCharacteristic.EVENT),
            N_V,
        )

    m1 = mesh_lib.make_mesh(1)

    def run(agg):
        return [
            np.asarray(e)
            for e in stream().aggregate(agg, mesh=m1, window_ms=100)
        ]

    raw = run(connected_components(N_V, ingest_combine=False))
    assert len(raw) >= 3
    for codec in ("sparse", "compact"):
        got = run(connected_components(
            N_V, codec=codec, compact_capacity=N_V
        ))
        assert len(got) == len(raw), codec
        for i, (g, r) in enumerate(zip(got, raw)):
            assert np.array_equal(g, r), (codec, i)


def test_windowed_codec_degrees_parity():
    # Windowed degree aggregation with the codec engaged (incl. deletion
    # events: the delta codec carries ±1, so window-scoped payloads must
    # reproduce the raw windowed fold exactly).
    from gelly_tpu.core.chunk import EDGE_ADDITION, EDGE_DELETION
    from gelly_tpu.core.io import EdgeChunkSource, TimeCharacteristic
    from gelly_tpu.library.degrees import degree_aggregate

    rng = np.random.default_rng(23)
    n = 600
    src = rng.integers(0, N_V, n).astype(np.int64)
    dst = rng.integers(0, N_V, n).astype(np.int64)
    ev = np.where(rng.random(n) < 0.2, EDGE_DELETION, EDGE_ADDITION)
    ts = np.sort(rng.integers(0, 300, n)).astype(np.int64)

    def stream():
        return edge_stream_from_source(
            EdgeChunkSource(src, dst, events=ev, timestamps=ts,
                            chunk_size=100,
                            table=IdentityVertexTable(N_V),
                            time=TimeCharacteristic.EVENT),
            N_V,
        )

    m1 = mesh_lib.make_mesh(1)

    def run(agg):
        return [
            np.asarray(e)
            for e in stream().aggregate(agg, mesh=m1, window_ms=100)
        ]

    raw = run(degree_aggregate(N_V, ingest_combine=False))
    for codec in ("dense", "sparse"):
        got = run(degree_aggregate(N_V, codec=codec))
        assert len(got) == len(raw) >= 2, codec
        for i, (g, r) in enumerate(zip(got, raw)):
            assert np.array_equal(g, r), (codec, i)


def test_mesh_windowed_codec_parity():
    """VERDICT r4 item 5: window_ms + codec + S>1 — the masked chunk
    splits into S host slices whose payloads ride the sharded batch axis.
    Per-window emissions on the 8-device mesh must equal the single-shard
    windowed run for the sparse AND compact codecs, and for the degree
    codec."""
    from gelly_tpu.core.io import EdgeChunkSource, TimeCharacteristic
    from gelly_tpu.library.degrees import degree_aggregate

    rng = np.random.default_rng(29)
    n = 1200
    src = (rng.zipf(1.4, n) % N_V).astype(np.int64)
    dst = (rng.zipf(1.4, n) % N_V).astype(np.int64)
    ts = np.sort(rng.integers(0, 400, n)).astype(np.int64)

    def stream():
        return edge_stream_from_source(
            EdgeChunkSource(src, dst, timestamps=ts, chunk_size=128,
                            table=IdentityVertexTable(N_V),
                            time=TimeCharacteristic.EVENT),
            N_V,
        )

    m1 = mesh_lib.make_mesh(1)
    m8 = mesh_lib.make_mesh()

    def run(agg, mesh):
        return [
            np.asarray(e)
            for e in stream().aggregate(agg, mesh=mesh, window_ms=100)
        ]

    for make in (
        lambda: connected_components(N_V, codec="sparse", merge="gather"),
        lambda: connected_components(
            N_V, codec="compact", compact_capacity=N_V
        ),
        lambda: degree_aggregate(N_V, codec="sparse"),
    ):
        single = run(make(), m1)
        mesh = run(make(), m8)
        assert len(single) >= 3
        assert len(single) == len(mesh)
        for i, (a, b) in enumerate(zip(single, mesh)):
            assert np.array_equal(a, b), (make, i)


def test_compact_requires_codec_path():
    agg = connected_components(N_V, codec="compact", compact_capacity=N_V)
    with pytest.raises(NotImplementedError):
        agg.fold(agg.init(), None)
    with pytest.raises(ValueError):
        connected_components(N_V, codec="compact", ingest_combine=False)
