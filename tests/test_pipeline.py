"""Pipelined executor (engine/aggregation.py): parity, faults, resume.

The executor overlaps host compress (K workers), H2D transfer (dedicated
double-buffer thread) and the donated device folds — none of which may
change a single bit of any emission. This suite pins that down on
adversarial streams (hot vertex, deletions, cap overflow), drives the new
codec/H2D fault boundaries, and proves the last-retired-chunk checkpoint
rule with chunks in flight (generator abandon + subprocess kill -9).
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from gelly_tpu import edge_stream_from_edges
from gelly_tpu.engine import faults
from gelly_tpu.library.connected_components import connected_components

N_V = 256


def _zipf_edges(n=800, seed=3, n_v=N_V):
    rng = np.random.default_rng(seed)
    return [
        (int(a), int(b))
        for a, b in zip(rng.zipf(1.4, n) % n_v, rng.zipf(1.4, n) % n_v)
    ]


def _stream(edges, chunk_size=64, n_v=N_V):
    return edge_stream_from_edges(
        [(a, b, 1.0) for a, b in edges],
        vertex_capacity=n_v, chunk_size=chunk_size,
    )


def _run_cc(edges, codec, *, serial, merge_mode="auto", **kw):
    s = _stream(edges)
    agg = connected_components(N_V, merge="gather", codec=codec,
                               merge_mode=merge_mode)
    if serial:
        kw.update(ingest_workers=0, prefetch_depth=0, h2d_depth=0)
    else:
        kw.setdefault("codec_workers", 3)
        kw.setdefault("h2d_depth", 2)
    res = s.aggregate(agg, merge_every=8, fold_batch=8, **kw)
    return np.asarray(res.result()), res


# ---------------------------------------------------------------------- #
# parity: pipelined == serial, bit for bit


@pytest.mark.parametrize("codec", ["sparse", "compact"])
def test_pipelined_parity_hot_vertex(codec):
    # Zipf streams put a mega-degree vertex in every chunk — maximum
    # contention for the donated fold state and the ordered codec
    # session, folded through all three overlapped stages.
    edges = _zipf_edges()
    base, _ = _run_cc(edges, codec, serial=True)
    pipe, _ = _run_cc(edges, codec, serial=False)
    assert np.array_equal(base, pipe)


@pytest.mark.parametrize("codec", ["sparse", "compact"])
def test_pipelined_parity_delta_merge(codec):
    # merge_mode="delta" (dirty-row gather) must emit the same labels as
    # the replicated merge, through the pipelined executor — for BOTH
    # plan families: the sparse plan's vertex-space delta and the compact
    # plan's cid-space delta (croot union + vertex_of max-scatter).
    edges = _zipf_edges(seed=11)
    rep, _ = _run_cc(edges, codec, serial=True, merge_mode="replicated")
    delta, res = _run_cc(edges, codec, serial=False, merge_mode="delta")
    assert np.array_equal(rep, delta)
    assert res.stats["merge_modes"]["delta"] > 0


def test_codec_wait_reattributed_from_compress_stage():
    # The ordered compact session's await_turn blocks INSIDE the
    # ingest_compress timer context; at teardown the engine reclassifies
    # that wait into a codec_wait stage so the bench's serial-cost
    # comparison (pipeline_serial_sum_s) counts work, not lock-wait.
    edges = _zipf_edges()
    _, res = _run_cc(edges, "compact", serial=False)
    busy = res.timer.busy()
    # Booked even at 0.0 wait: artifacts distinguish "no contention"
    # from "accounting not active".
    assert busy["codec_wait"] >= 0.0
    assert busy["ingest_compress"] >= 0.0
    # The sparse codec has no ordered session: no reclassification row.
    _, res_sparse = _run_cc(edges, "sparse", serial=False)
    assert "codec_wait" not in res_sparse.timer.busy()


def test_pipelined_parity_deletions():
    # EDGE_DELETION events ride the raw-chunk path (batch folds +
    # donation, no codec): a deletion-honoring count fold must retire
    # every event exactly once regardless of pipelining.
    import jax.numpy as jnp

    from gelly_tpu.core.io import EdgeChunkSource
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable
    from gelly_tpu.engine.aggregation import SummaryAggregation

    rng = np.random.default_rng(7)
    n = 640
    src = rng.integers(0, N_V, n).astype(np.int64)
    dst = rng.integers(0, N_V, n).astype(np.int64)
    events = (rng.random(n) < 0.25).astype(np.int8)  # 1 = deletion

    def agg():
        return SummaryAggregation(
            init=lambda: jnp.zeros((), jnp.int64),
            fold=lambda s, c: s + jnp.sum(
                jnp.where(c.valid, jnp.where(c.event == 1, -1, 1), 0)
            ),
            combine=lambda a, b: a + b,
        )

    def run(**kw):
        s = edge_stream_from_source(
            EdgeChunkSource(src, dst, events=events, chunk_size=64,
                            table=IdentityVertexTable(N_V)),
            N_V,
        )
        return int(s.aggregate(agg(), merge_every=4, fold_batch=4,
                               **kw).result())

    want = int((events == 0).sum()) - int((events == 1).sum())
    assert run(ingest_workers=0, prefetch_depth=0, h2d_depth=0) == want
    assert run(codec_workers=2, h2d_depth=2) == want


def test_pipelined_cap_overflow_fails_loudly():
    # Compact-space overflow raised inside a codec WORKER must surface at
    # the consumer promptly (no hang, ordered-session turns released) on
    # both the serial and pipelined paths.
    from gelly_tpu.ops.compact_space import CompactSpaceOverflow

    edges = _zipf_edges(seed=5)

    def run(**kw):
        s = _stream(edges)
        agg = connected_components(N_V, merge="gather", codec="compact",
                                   compact_capacity=8)  # << touched
        return s.aggregate(agg, merge_every=8, fold_batch=8, **kw).result()

    with pytest.raises(CompactSpaceOverflow):
        run(ingest_workers=0, prefetch_depth=0, h2d_depth=0)
    with pytest.raises(CompactSpaceOverflow):
        run(codec_workers=3, h2d_depth=2)


def test_accum_plan_emissions_survive_donation():
    # Accumulate plans WITHOUT a transform yield the live fold state —
    # donation must stay off for them, or the next fold deletes the
    # consumer's held emission (review finding: degree_aggregate on one
    # shard raised 'Array has been deleted' on any retained emission).
    from gelly_tpu.library.degrees import degree_aggregate
    from gelly_tpu.parallel import mesh as mesh_lib

    edges = _zipf_edges(n=96, seed=2)
    s = _stream(edges, chunk_size=16)
    agg = degree_aggregate(N_V, ingest_combine=False)
    m1 = mesh_lib.make_mesh(1)  # S=1: the accumulate-plan shape
    emissions = list(s.aggregate(agg, mesh=m1, merge_every=2))
    assert len(emissions) >= 2
    # Every retained emission stays readable and monotone in total degree.
    totals = [int(np.asarray(e).sum()) for e in emissions]
    assert totals == sorted(totals)
    assert totals[-1] == 2 * len(edges)


# ---------------------------------------------------------------------- #
# knobs


def test_codec_workers_alias_rejects_both():
    s = _stream(_zipf_edges(n=64))
    agg = connected_components(N_V)
    with pytest.raises(ValueError, match="codec_workers or ingest_workers"):
        s.aggregate(agg, codec_workers=2, ingest_workers=2).result()


def test_h2d_depth_validation():
    s = _stream(_zipf_edges(n=64))
    agg = connected_components(N_V)
    with pytest.raises(ValueError, match="h2d_depth"):
        s.aggregate(agg, h2d_depth=-1).result()


def test_merge_mode_validation_and_plan_cache_key():
    with pytest.raises(ValueError, match="merge_mode"):
        connected_components(N_V, merge_mode="nope")
    # Rebinding merge_mode on the same instance must re-jit (the cache
    # keys on it), not silently reuse stale executables.
    edges = _zipf_edges(n=128)
    agg = connected_components(N_V, merge="gather", codec="sparse",
                               merge_mode="replicated")
    a = np.asarray(_stream(edges).aggregate(agg, merge_every=4).result())
    agg.merge_mode = "delta"
    b = np.asarray(_stream(edges).aggregate(agg, merge_every=4).result())
    assert np.array_equal(a, b)
    assert len(agg._plan_cache) == 2
    # Misconfigured plans fail LOUDLY at plan time, not with a TypeError
    # from inside a jit trace at the first window close: merge_mode=delta
    # needs the plan's merge_delta, and merge_delta needs its bucket-sizing
    # merge_dirty_count.
    bad = connected_components(N_V, merge="gather", codec="sparse",
                               merge_mode="delta")
    bad.merge_delta = None
    with pytest.raises(ValueError, match="no merge_delta"):
        _stream(edges).aggregate(bad, merge_every=4).result()
    bad2 = connected_components(N_V, merge="gather", codec="sparse",
                                merge_mode="delta")
    bad2.merge_dirty_count = None
    with pytest.raises(ValueError, match="merge_dirty_count"):
        _stream(edges).aggregate(bad2, merge_every=4).result()
    # The ENGINE validates the mode too (hand-built SummaryAggregation
    # plans bypass the library's resolve_merge_mode): a typo'd mode must
    # not silently run the capacity-proportional replicated merge.
    bad3 = connected_components(N_V, merge="gather", codec="sparse")
    bad3.merge_mode = "Delta"  # case typo, set after construction
    with pytest.raises(ValueError, match="merge_mode must be"):
        _stream(edges).aggregate(bad3, merge_every=4).result()


# ---------------------------------------------------------------------- #
# fault injection at the new executor boundaries

pytest_faults = pytest.mark.faults


@pytest_faults
def test_codec_worker_fault_propagates():
    # A fault in a codec WORKER (ordered compact session in play) must
    # propagate to the consumer as the injected error — not wedge the
    # pool behind an unreleased assignment turn.
    edges = _zipf_edges(seed=9)
    s = _stream(edges)
    agg = connected_components(N_V, merge="gather", codec="compact",
                               compact_capacity=N_V)
    plan = faults.FaultPlan([faults.Fault(boundary="codec", at=1)])
    with faults.install(plan):
        with pytest.raises(faults.FaultInjected):
            s.aggregate(agg, merge_every=8, fold_batch=8,
                        codec_workers=2, h2d_depth=2).result()
    assert plan.fired and plan.fired[0][0] == "codec"
    # The pool unwound: a fresh run on the same aggregation completes.
    got = np.asarray(
        _stream(edges).aggregate(agg, merge_every=8, fold_batch=8,
                                 codec_workers=2, h2d_depth=2).result()
    )
    base = np.asarray(
        _stream(edges).aggregate(agg, merge_every=8, fold_batch=8,
                                 ingest_workers=0, prefetch_depth=0,
                                 h2d_depth=0).result()
    )
    assert np.array_equal(got, base)


@pytest_faults
def test_h2d_fault_propagates():
    edges = _zipf_edges(seed=10)
    s = _stream(edges)
    agg = connected_components(N_V, merge="gather", codec="sparse")
    plan = faults.FaultPlan([faults.Fault(boundary="h2d", at=2)])
    with faults.install(plan):
        with pytest.raises(faults.FaultInjected):
            s.aggregate(agg, merge_every=4, fold_batch=2,
                        codec_workers=2, h2d_depth=2).result()
    assert ("h2d", 2, "raise") in plan.fired


# ---------------------------------------------------------------------- #
# exactly-once resume with chunks in flight


def test_resume_with_inflight_double_buffers(tmp_path):
    # Abandon the pipelined run mid-stream with units sitting in the
    # compress/H2D buffers; resume must refold exactly the un-retired
    # suffix (last-retired-chunk rule) — final labels identical to an
    # uninterrupted run.
    p = str(tmp_path / "ck.npz")
    edges = _zipf_edges(seed=21)

    def make(resume):
        s = _stream(edges, chunk_size=32)
        agg = connected_components(N_V, merge="gather", codec="compact",
                                   compact_capacity=N_V)
        return s.aggregate(agg, merge_every=8, fold_batch=8,
                           checkpoint_path=p, checkpoint_every=1,
                           resume=resume, codec_workers=2, h2d_depth=2)

    it = iter(make(False))
    next(it)
    next(it)
    it.close()  # chunks in flight in the compress/H2D stages are dropped
    assert os.path.exists(p)
    got = np.asarray(make(True).result())
    s = _stream(edges, chunk_size=32)
    agg = connected_components(N_V, merge="gather", codec="compact",
                               compact_capacity=N_V)
    want = np.asarray(s.aggregate(agg, merge_every=8, fold_batch=8,
                                  ingest_workers=0, prefetch_depth=0,
                                  h2d_depth=0).result())
    assert np.array_equal(got, want)


CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "_pipeline_crash_child.py")


def _spawn(ckpt, out, sleep_s):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # single default CPU device is enough
    return subprocess.Popen(
        [sys.executable, CHILD, str(ckpt), str(out), str(sleep_s)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


@pytest_faults
def test_pipelined_kill9_resume_bit_identical(tmp_path):
    from gelly_tpu.engine.checkpoint import load_checkpoint

    ckpt = tmp_path / "pipe-ck.npz"
    out_clean = tmp_path / "clean.npz"
    out_resumed = tmp_path / "resumed.npz"

    p = _spawn(tmp_path / "clean-ck.npz", out_clean, 0.0)
    assert p.wait(timeout=300) == 0

    # Throttled run: SIGKILL once a checkpoint is durably on disk — the
    # pipeline guarantees staged units are in flight past the recorded
    # position at that moment.
    p = _spawn(ckpt, out_resumed, 0.05)
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        if p.poll() is not None:
            pytest.fail(f"child exited early (rc={p.returncode})")
        if ckpt.exists():
            break
        time.sleep(0.02)
    else:
        pytest.fail("no checkpoint appeared before the deadline")
    os.kill(p.pid, signal.SIGKILL)
    assert p.wait(timeout=60) == -signal.SIGKILL
    assert not out_resumed.exists()

    _, pos, _ = load_checkpoint(str(ckpt))
    import _pipeline_crash_child as child

    total = -(-child.N_EDGES // child.CHUNK)
    assert 0 < pos < total  # mid-stream position

    p = _spawn(ckpt, out_resumed, 0.0)
    assert p.wait(timeout=300) == 0
    resumed, _, _ = load_checkpoint(str(out_resumed))
    clean, _, _ = load_checkpoint(str(out_clean))
    assert len(resumed) == len(clean) == 1
    assert resumed[0].tobytes() == clean[0].tobytes()
