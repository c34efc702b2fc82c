"""Multi-tenant batched fold engine (``gelly_tpu/engine/tenants.py``).

The acceptance contract of the tenant batch: for EVERY tenant of a
mixed-workload N >= 64 batch, labels are bit-identical to that
tenant's single-stream ``run_aggregation`` run; one vmapped dispatch
advances the whole tier per scheduling round; live ``labels(tenant,
v)`` queries are answered from the last merge-window snapshot and
never block (or are blocked by) a window close; per-tenant
checkpoints ride the existing position-header/CRC format and resume
exactly-once under kill -9 (crash child).
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from gelly_tpu import edge_stream_from_edges
from gelly_tpu.core.vertices import IdentityVertexTable
from gelly_tpu.engine.checkpoint import load_checkpoint
from gelly_tpu.engine.resilience import CheckpointManager
from gelly_tpu.engine.tenants import MultiTenantEngine, TenantBatch
from gelly_tpu.library.connected_components import (
    cc_tenant_tier,
    connected_components,
)
from gelly_tpu.library.degrees import degree_aggregate
from gelly_tpu.obs import bus as obs_bus

pytestmark = pytest.mark.tenants

N_V = 128
CHUNK = 32


def _stream(seed: int, n_edges: int = 96, n_v: int = N_V,
            chunk: int = CHUNK, identity: bool = False):
    rng = np.random.default_rng(seed)
    pairs = rng.integers(0, n_v, (n_edges, 2))
    kw = {"table": IdentityVertexTable(n_v)} if identity else {}
    return edge_stream_from_edges(
        [(int(a), int(b)) for a, b in pairs],
        vertex_capacity=n_v, chunk_size=chunk, **kw,
    )


def _cc_plan(n_v: int = N_V):
    return connected_components(n_v, merge="gather", ingest_combine=False)


# --------------------------------------------------------------------- #
# batched fold correctness


def test_mixed_workload_64_tenants_bit_identical():
    """The acceptance batch: 64 tenants across two tiers (CC +
    degrees), every tenant's final snapshot bit-identical to its
    single-stream run_aggregation run."""
    cc = _cc_plan()
    dg = degree_aggregate(N_V, ingest_combine=False)
    eng = MultiTenantEngine(merge_every=2)
    eng.add_tier("cc", cc, CHUNK)
    eng.add_tier("deg", dg, CHUNK)
    n_cc, n_dg = 48, 16
    for i in range(n_cc):
        eng.admit(("cc", i), "cc", chunks=_stream(i))
    for i in range(n_dg):
        eng.admit(("dg", i), "deg", chunks=_stream(1000 + i))
    out = eng.drain()
    assert len(out) == 64
    for i in range(n_cc):
        want = np.asarray(_stream(i).aggregate(cc, merge_every=2).result())
        got = out[("cc", i)]
        assert got.dtype == want.dtype
        assert got.tobytes() == want.tobytes()
    for i in range(n_dg):
        want = np.asarray(
            _stream(1000 + i).aggregate(dg, merge_every=2).result()
        )
        got = out[("dg", i)]
        assert got.dtype == want.dtype
        assert got.tobytes() == want.tobytes()


def test_one_dispatch_advances_the_whole_tier():
    """The amortization claim: D tenants × K chunks fold in K
    dispatches (one per scheduling round), not D × K."""
    cc = _cc_plan()
    eng = MultiTenantEngine(merge_every=2)
    eng.add_tier("cc", cc, CHUNK)
    n, chunks_each = 8, 3  # 96 edges / CHUNK 32
    for i in range(n):
        eng.admit(i, "cc", chunks=_stream(i))
    eng.drain()
    assert eng.stats["chunks"] == n * chunks_each
    assert eng.stats["dispatches"] == chunks_each


def test_uneven_streams_and_starvation_accounting():
    """Stragglers never stall the batch: tenants with shorter streams
    finish early (masked no-op lanes), longer ones keep advancing;
    results stay bit-identical per tenant."""
    cc = _cc_plan()
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("cc", cc, CHUNK)
    lengths = {0: 32, 1: 96, 2: 160, 3: 64}
    for tid, n in lengths.items():
        eng.admit(tid, "cc", chunks=_stream(tid, n_edges=n))
    out = eng.drain()
    for tid, n in lengths.items():
        want = np.asarray(
            _stream(tid, n_edges=n).aggregate(cc, merge_every=1).result()
        )
        assert out[tid].tobytes() == want.tobytes()
        assert eng.position(tid) == -(-n // CHUNK)
    # The longest tenant drove 5 rounds; everyone else went masked for
    # the tail rounds but was already `finished`, so nobody starved.
    assert eng.stats["dispatches"] == 5
    assert eng.stats["starved_lanes"] == 0


def test_starved_windows_counts_live_but_empty_lanes():
    """A live push-mode tenant with nothing queued contributes a
    masked lane — counted as a starved window on the bus."""
    cc = _cc_plan()
    with obs_bus.scope() as bus:
        eng = MultiTenantEngine(merge_every=1)
        eng.add_tier("cc", cc, CHUNK)
        eng.admit("busy", "cc")
        eng.admit("idle", "cc")
        for c in _stream(3):
            eng.submit("busy", c)
        eng.finish("busy")
        with pytest.raises(RuntimeError, match="never finish"):
            eng.drain()  # idle is live push-mode: loud, not a hang
        assert eng.starved_windows("idle") > 0
        assert bus.counters["tenants.starved_windows"] > 0
        eng.finish("idle")
        out = eng.drain()
        want = np.asarray(_stream(3).aggregate(cc, merge_every=1).result())
        assert out["busy"].tobytes() == want.tobytes()


def test_lane_width_growth_preserves_admitted_state():
    """Admissions double the lane width (1 → 2 → 4 …); existing
    tenants' summaries survive the widening copy bit-identically."""
    cc = _cc_plan()
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("cc", cc, CHUNK)
    eng.admit(0, "cc")
    for c in _stream(0):
        eng.submit(0, c)
    eng.finish(0)
    # Drive tenant 0 to completion at width 1, then admit more.
    for tid in range(1, 5):
        eng.admit(tid, "cc", chunks=_stream(tid))
    assert eng._tiers["cc"].batch.lanes >= 5  # widened past 1
    out = eng.drain()
    for tid in range(5):
        want = np.asarray(
            _stream(tid).aggregate(cc, merge_every=1).result()
        )
        assert out[tid].tobytes() == want.tobytes()


def test_mesh_shards_the_tenant_axis():
    from gelly_tpu.parallel import mesh as mesh_lib

    cc = _cc_plan()
    m = mesh_lib.make_mesh()
    eng = MultiTenantEngine(merge_every=2, mesh=m)
    eng.add_tier("cc", cc, CHUNK)
    n = 10  # lanes pad to 16 (multiple of the 8-device mesh)
    for i in range(n):
        eng.admit(i, "cc", chunks=_stream(i))
    out = eng.drain()
    assert eng._tiers["cc"].batch.lanes % mesh_lib.num_shards(m) == 0
    for i in range(n):
        want = np.asarray(_stream(i).aggregate(cc, merge_every=2).result())
        assert out[i].tobytes() == want.tobytes()


# --------------------------------------------------------------------- #
# plan validation


def test_tier_refuses_stateful_codec_plans():
    """Only the genuinely un-batchable case stays refused now that
    codec plans compile a vmapped fold_codec — and the message names
    the reason: the stack_ordered session assigns compact ids in
    global stream order, which concurrent lanes cannot provide."""
    from gelly_tpu.engine.aggregation import _compiled_tenant_plan

    compact = connected_components(N_V, codec="compact",
                                   compact_capacity=N_V)
    with pytest.raises(ValueError, match="GLOBAL STREAM order"):
        _compiled_tenant_plan(compact, 2)
    # A codec-ONLY plan on a raw tier is refused up front too (its raw
    # fold does not exist), pointing at the compressed-tier knob.
    with pytest.raises(ValueError, match="compressed=True"):
        TenantBatch(compact, CHUNK)
    # Plain codec plans now compile fold_codec next to the raw fold.
    sparse = connected_components(N_V, codec="sparse")
    plan = _compiled_tenant_plan(sparse, 2)
    assert plan.fold_codec is not None


def test_tier_refuses_host_transforms():
    from gelly_tpu.engine.aggregation import _compiled_tenant_plan

    agg = _cc_plan()
    agg.jit_transform = False
    with pytest.raises(ValueError, match="host-side transform"):
        _compiled_tenant_plan(agg, 2)


def test_admission_validation():
    cc = _cc_plan()
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("cc", cc, CHUNK)
    with pytest.raises(ValueError, match="already registered"):
        eng.add_tier("cc", cc, CHUNK)
    with pytest.raises(ValueError, match="unknown tier"):
        eng.admit(0, "nope")
    eng.admit(0, "cc")
    with pytest.raises(ValueError, match="already admitted"):
        eng.admit(0, "cc")
    with pytest.raises(ValueError, match="chunk capacity"):
        eng.submit(0, next(iter(_stream(0, chunk=CHUNK * 2))))
    eng.finish(0)
    with pytest.raises(ValueError, match="finished"):
        eng.submit(0, next(iter(_stream(0))))


def test_cc_tenant_tier_builder():
    agg, cap = cc_tenant_tier(N_V, chunk_capacity=CHUNK)
    assert agg.host_compress is None  # raw fold, vmappable
    assert cap == CHUNK
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("t", agg, cap)
    eng.admit(0, "t", chunks=_stream(0))
    out = eng.drain()
    want = np.asarray(_stream(0).aggregate(agg, merge_every=1).result())
    assert out[0].tobytes() == want.tobytes()


def test_delta_auto_rows_knob():
    agg = connected_components(N_V, delta_auto_rows=777)
    assert agg.merge_delta_auto_rows == 777
    agg = connected_components(N_V)
    assert agg.merge_delta_auto_rows == N_V // 4
    agg = connected_components(N_V, codec="compact", compact_capacity=64,
                               delta_auto_rows=11)
    assert agg.merge_delta_auto_rows == 11


# --------------------------------------------------------------------- #
# live queries


def test_query_staleness_is_one_merge_window():
    """Mid-stream queries answer from the LAST closed window — stale by
    at most one window — and carry the window number."""
    cc = _cc_plan()
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("cc", cc, CHUNK)
    eng.admit(0, "cc")
    assert eng.labels(0) is None  # no window closed yet
    assert eng.snapshot_window(0) == 0
    chunks = list(_stream(0, n_edges=160))
    seen_windows = []
    eng.start()
    try:
        for k, c in enumerate(chunks):
            eng.submit(0, c)
            # merge_every=1: chunk k+1's fold closes window k+1 in the
            # same scheduling round — wait for it (first round pays the
            # vmapped-plan compile), then the snapshot must be exactly
            # one-window fresh.
            deadline = time.time() + 60
            while (time.time() < deadline
                   and eng.snapshot_window(0) < k + 1):
                time.sleep(0.01)
            seen_windows.append(eng.snapshot_window(0))
            # A scalar labels() read mid-stream.
            v = eng.labels(0, 0)
            assert v is not None and v.shape == ()
        eng.finish(0)
        deadline = time.time() + 20
        while time.time() < deadline and eng.position(0) < len(chunks):
            time.sleep(0.02)
    finally:
        eng.stop()
    assert seen_windows == list(range(1, len(chunks) + 1))
    want = np.asarray(
        _stream(0, n_edges=160).aggregate(cc, merge_every=1).result()
    )
    assert eng.labels(0).tobytes() == want.tobytes()


def test_queries_never_block_window_close():
    """Hammer queries from two threads through a whole drain: every
    window still closes (drain terminates) and every observed snapshot
    is internally consistent (labels row matches a prefix run)."""
    cc = _cc_plan()
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("cc", cc, CHUNK)
    for i in range(4):
        eng.admit(i, "cc", chunks=_stream(i, n_edges=256))
    stop = threading.Event()
    errors: list = []

    def hammer():
        try:
            while not stop.is_set():
                for i in range(4):
                    eng.labels(i)
                    eng.snapshot_window(i)
                    eng.queue_depth(i)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        out = eng.drain()
    finally:
        stop.set()
        for t in threads:
            t.join(5)
    assert not errors
    for i in range(4):
        want = np.asarray(
            _stream(i, n_edges=256).aggregate(cc, merge_every=1).result()
        )
        assert out[i].tobytes() == want.tobytes()


def test_query_for_tenant_admitted_after_snapshot_returns_none():
    """A tenant admitted AFTER the last window close has no lane in
    the stored snapshot — query must return None, not CLAMP to the
    highest stacked lane (JAX out-of-bounds indexing clamps instead of
    raising, which silently leaked another tenant's row)."""
    cc = _cc_plan()
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("cc", cc, CHUNK)
    eng.admit("a", "cc", chunks=_stream(0))
    a_row = eng.drain()["a"]
    assert a_row is not None
    # "b" lands on lane 1; the snapshot is still the width-1 stack
    # from the drain above.
    eng.admit("b", "cc")
    assert eng.labels("b") is None
    assert eng.labels("b", 0) is None
    assert eng.snapshot_window("b") == 0
    assert eng.labels("a").tobytes() == a_row.tobytes()  # "a" unharmed
    # Once "b" folds its own stream, queries resolve to b's own data.
    for c in _stream(7):
        eng.submit("b", c)
    eng.finish("b")
    out = eng.drain()
    want = np.asarray(_stream(7).aggregate(cc, merge_every=1).result())
    assert out["b"].tobytes() == want.tobytes()


# --------------------------------------------------------------------- #
# malformed-chunk containment


def test_submit_rejects_template_mismatch_to_submitter():
    """A chunk whose ``val`` dtype diverges from the tier template is
    rejected AT submit() — were it first caught at stack time it would
    kill the scheduler thread for every tenant, after the round had
    already popped (and so dropped) other tenants' chunks."""
    cc = _cc_plan()
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("cc", cc, CHUNK)
    eng.admit("good", "cc")
    eng.admit("bad", "cc")
    chunks = list(_stream(3))
    for c in chunks:
        eng.submit("good", c)
    rogue = chunks[0]._replace(
        val=np.asarray(chunks[0].val, np.float64)
    )
    with pytest.raises(ValueError, match="tier template"):
        eng.submit("bad", rogue)
    eng.finish("good")
    eng.finish("bad")
    out = eng.drain()
    want = np.asarray(_stream(3).aggregate(cc, merge_every=1).result())
    assert out["good"].tobytes() == want.tobytes()


def test_pull_mode_malformed_chunk_quarantines_one_tenant():
    """A pull-source tenant shipping a template-mismatched chunk is
    quarantined (its stream truncated at the bad chunk) — the
    scheduler survives and every other tenant folds to completion."""
    cc = _cc_plan()
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("cc", cc, CHUNK)
    rogue = [c._replace(val=np.asarray(c.val, np.float64))
             for c in _stream(5, n_edges=64)]
    eng.admit("good", "cc", chunks=_stream(0))
    eng.admit("rogue", "cc", chunks=rogue)
    out = eng.drain()  # terminates: the bad tenant must not hang it
    want = np.asarray(_stream(0).aggregate(cc, merge_every=1).result())
    assert out["good"].tobytes() == want.tobytes()
    assert eng.position("rogue") == 0  # nothing folded past the reject


def test_starved_windows_counts_only_dispatch_rounds():
    """The counter's unit is 'masked no-op lane IN a dispatch': rounds
    where nothing dispatched must not bump it (an idle serving engine
    polling empty queues would otherwise inflate it at the poll
    rate, diverging from the bus counter)."""
    cc = _cc_plan()
    with obs_bus.scope() as bus:
        eng = MultiTenantEngine(merge_every=1)
        eng.add_tier("cc", cc, CHUNK)
        eng.admit("busy", "cc")
        eng.admit("idle", "cc")
        for c in _stream(3):  # 96 edges -> 3 chunks -> 3 rounds
            eng.submit("busy", c)
        eng.finish("busy")
        with pytest.raises(RuntimeError, match="never finish"):
            eng.drain()
        # Exactly one starved window per DISPATCH round; the empty
        # round that ended drain() contributes none.
        assert eng.starved_windows("idle") == 3
        assert bus.counters["tenants.starved_windows"] == 3
        assert eng.stats["starved_lanes"] == 3


# --------------------------------------------------------------------- #
# per-tenant checkpoints + resume


def test_checkpoint_manager_prefix_isolates_rotations(tmp_path):
    a = CheckpointManager(str(tmp_path), prefix="t1", async_write=False,
                          keep=2)
    b = CheckpointManager(str(tmp_path), prefix="t11", async_write=False,
                          keep=2)
    for pos in (1, 2, 3):
        a.save({"x": np.full((4,), pos)}, pos)
    b.save({"x": np.full((4,), 99)}, 7)
    assert [os.path.basename(p) for p in a.list()] == [
        "t1-000000000002.npz", "t1-000000000003.npz",
    ]
    assert [os.path.basename(p) for p in b.list()] == [
        "t11-000000000007.npz",
    ]
    got = a.load_latest(like={"x": np.zeros((4,), np.int64)})
    assert got is not None and got[1] == 3
    with pytest.raises(ValueError, match="prefix"):
        CheckpointManager(str(tmp_path), prefix=f"a{os.sep}b")
    # "-" is the rotation separator: a prefix containing it would glob
    # into sibling rotations ("t7-*" matches a "t7-0" tenant's files).
    with pytest.raises(ValueError, match="prefix"):
        CheckpointManager(str(tmp_path), prefix="t7-0")


def test_tenant_prefixes_escape_arbitrary_ids(tmp_path):
    from gelly_tpu.engine.tenants import tenant_prefix

    # Injective + separator-free: ids "7" and "7-0" must never share a
    # rotation namespace (the raw f"t{id}" form made t7's glob match,
    # prune and even load t7-0's checkpoints).
    assert tenant_prefix(7) == "t7"
    assert tenant_prefix("7-0") == "t7%2d0"
    assert "-" not in tenant_prefix("user-42/7%x")
    assert tenant_prefix("a-b") != tenant_prefix("a_b")
    cc = _cc_plan()
    eng = MultiTenantEngine(merge_every=1, checkpoint_dir=str(tmp_path))
    eng.add_tier("cc", cc, CHUNK)
    eng.admit("7", "cc", chunks=_stream(0, n_edges=32))
    eng.admit("7-0", "cc", chunks=_stream(1, n_edges=64))
    eng.drain()
    t7 = eng._tenants["7"].manager.list()
    t70 = eng._tenants["7-0"].manager.list()
    assert t7 and t70 and not set(t7) & set(t70)
    # Each rotation resolves to ITS tenant's position.
    assert load_checkpoint(t7[-1])[1] == 1
    assert load_checkpoint(t70[-1])[1] == 2


def test_per_tenant_checkpoints_ride_the_crc_format(tmp_path):
    cc = _cc_plan()
    eng = MultiTenantEngine(merge_every=2, checkpoint_dir=str(tmp_path),
                            checkpoint_every=1)
    eng.add_tier("cc", cc, CHUNK)
    for i in range(3):
        eng.admit(i, "cc", chunks=_stream(i))
    eng.drain()
    for i in range(3):
        files = sorted(tmp_path.glob(f"t{i}-*.npz"))
        assert files, i
        state, pos, meta = load_checkpoint(
            str(files[-1]), like=cc.init()
        )
        assert pos == eng.position(i) == 3
        assert meta["tenant"] == str(i)
        assert meta["tier"] == "cc"


def test_resume_skips_folded_prefix_bit_identical(tmp_path):
    """Kill-free resume: a first engine folds a prefix (checkpoints
    on), a second engine with resume=True folds only the remainder and
    ends bit-identical to an uninterrupted run."""
    cc = _cc_plan()
    chunks = {i: list(_stream(i, n_edges=256)) for i in range(3)}
    eng = MultiTenantEngine(merge_every=1, checkpoint_dir=str(tmp_path),
                            checkpoint_every=1)
    eng.add_tier("cc", cc, CHUNK)
    for i in range(3):
        eng.admit(i, "cc", chunks=chunks[i][:5])  # prefix only
    eng.drain()
    eng2 = MultiTenantEngine(merge_every=1, checkpoint_dir=str(tmp_path),
                             checkpoint_every=1, resume=True)
    eng2.add_tier("cc", cc, CHUNK)
    for i in range(3):
        eng2.admit(i, "cc", chunks=chunks[i])  # full source, seekable
    out = eng2.drain()
    assert eng2.stats["chunks"] == 3 * 3  # only the 3-chunk suffixes
    for i in range(3):
        want = np.asarray(
            _stream(i, n_edges=256).aggregate(cc, merge_every=1).result()
        )
        assert out[i].tobytes() == want.tobytes()


CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "_tenants_crash_child.py")


def _spawn(ckpt_dir, out, sleep_s, compressed=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # single default CPU device is enough
    if compressed:
        env["GELLY_TEN_COMPRESSED"] = "1"
    return subprocess.Popen(
        [sys.executable, CHILD, str(ckpt_dir), str(out), str(sleep_s)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


@pytest.mark.faults
@pytest.mark.parametrize("compressed", [False, True],
                         ids=["raw", "codec"])
def test_multi_tenant_kill9_resume_bit_identical(tmp_path, compressed):
    """SIGKILL a multi-tenant run mid-window; the resumed incarnation's
    final forest must be bit-identical, per tenant, to the unkilled
    run AND to each tenant's single-stream run_aggregation oracle. The
    ``codec`` variant runs a COMPRESSED tier (producer-side compress +
    fold_codec lanes): the per-tenant payload-position resume must be
    exactly-once too."""
    import _tenants_crash_child as child

    out_clean = tmp_path / "clean.npz"
    out_resumed = tmp_path / "resumed.npz"
    ckpt_clean = tmp_path / "ck-clean"
    ckpt = tmp_path / "ck"

    p = _spawn(ckpt_clean, out_clean, 0.0, compressed=compressed)
    assert p.wait(timeout=300) == 0

    p = _spawn(ckpt, out_resumed, 0.03, compressed=compressed)
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        if p.poll() is not None:
            pytest.fail(f"child exited early (rc={p.returncode})")
        # Kill only after EVERY tenant has a durable checkpoint, so
        # resume exercises all three rotations.
        if all(
            list(ckpt.glob(f"t{t}-*.npz"))
            for t in range(child.TENANTS)
        ) if ckpt.exists() else False:
            break
        time.sleep(0.02)
    else:
        pytest.fail("no per-tenant checkpoints appeared before deadline")
    os.kill(p.pid, signal.SIGKILL)
    assert p.wait(timeout=60) == -signal.SIGKILL
    assert not out_resumed.exists()

    total = -(-child.N_EDGES // child.CHUNK)
    for t in range(child.TENANTS):
        newest = sorted(ckpt.glob(f"t{t}-*.npz"))[-1]
        _, pos, _ = load_checkpoint(str(newest))
        assert 0 < pos < total  # killed mid-stream for every tenant

    p = _spawn(ckpt, out_resumed, 0.0, compressed=compressed)
    assert p.wait(timeout=300) == 0
    resumed, _, _ = load_checkpoint(str(out_resumed))
    clean, _, _ = load_checkpoint(str(out_clean))
    assert len(resumed) == len(clean) == child.TENANTS
    for t in range(child.TENANTS):
        assert resumed[t].tobytes() == clean[t].tobytes()
        # The unkilled single-stream oracle (always the RAW plan: the
        # compressed tier's labels must match it bit-for-bit anyway).
        agg, _cap = cc_tenant_tier(child.N_V, chunk_capacity=child.CHUNK)
        want = np.asarray(
            child.build_stream(t).aggregate(agg, merge_every=2).result()
        )
        assert resumed[t].tobytes() == want.tobytes()


# --------------------------------------------------------------------- #
# observability


def test_bus_gauges_and_counters():
    cc = _cc_plan()
    with obs_bus.scope() as bus:
        eng = MultiTenantEngine(merge_every=2)
        eng.add_tier("cc", cc, CHUNK)
        for i in range(4):
            eng.admit(i, "cc", chunks=_stream(i))
        eng.drain()
        snap = bus.snapshot()
        assert snap["counters"]["tenants.dispatches"] == 3
        assert snap["counters"]["tenants.chunks_folded"] == 12
        assert snap["counters"]["tenants.windows_closed"] >= 1
        assert "tenants.active" in snap["gauges"]
        assert "tenants.queue_depth" in snap["gauges"]


def test_heartbeat_carries_tenant_fields():
    from gelly_tpu.obs import SpanTracer, install

    cc = _cc_plan()
    tracer = SpanTracer(heartbeat_every_s=0.0)  # beat on every tick
    with obs_bus.scope():
        with install(tracer):
            eng = MultiTenantEngine(merge_every=1)
            eng.add_tier("cc", cc, CHUNK)
            for i in range(2):
                eng.admit(i, "cc", chunks=_stream(i))
            eng.drain()
    beats = [i for i in tracer.instants() if i["name"] == "heartbeat"]
    assert beats
    line = beats[-1]["args"]
    assert "tenants_active" in line
    assert "tenants_queue_depth" in line
    assert "starved" in line
    assert "backlog_age_max_s" in line
    assert line["slo_breaching"] == 0  # no plane attached, gauge absent
    folds = [s for s in tracer.spans() if s["name"] == "fold"]
    assert folds and all(
        s["args"]["lanes"] >= s["args"]["advanced"] for s in folds
    )


def test_attached_slo_plane_ticks_from_scheduler():
    """An attached SLO plane is evaluated inside the dispatch loop's
    rate-limited gauge block: per-tenant burn-rate gauges and the
    ``slo.breaching`` headline gauge exist after a drain, and the
    heartbeat mirrors the count without a second evaluation thread."""
    from gelly_tpu.obs import SpanTracer, install
    from gelly_tpu.obs.slo import SloPlane, tenant_backlog_age_s

    cc = _cc_plan()
    tracer = SpanTracer(heartbeat_every_s=0.0)
    with obs_bus.scope() as bus:
        with install(tracer):
            eng = MultiTenantEngine(merge_every=1)
            # Impossible-to-breach threshold: the assertion is about
            # plumbing (gauges published from the scheduler), not about
            # forcing a breach (test_slo.py covers breaches).
            eng.attach_slo_plane(
                SloPlane([tenant_backlog_age_s(1e9)], bus=bus))
            eng.add_tier("cc", cc, CHUNK)
            for i in range(2):
                eng.admit(i, "cc", chunks=_stream(i))
            eng.drain()
        snap = bus.snapshot()["gauges"]
        assert snap.get("slo.breaching") == 0
        burn = [k for k in snap if k.endswith(".burn_rate")]
        assert any(".t0" in k for k in burn) and any(
            ".t1" in k for k in burn)
    beats = [i for i in tracer.instants() if i["name"] == "heartbeat"]
    assert beats and beats[-1]["args"]["slo_breaching"] == 0


# --------------------------------------------------------------------- #
# compressed tiers (the shared compression plane's tenant leg)


def _compressed_tier():
    return cc_tenant_tier(N_V, chunk_capacity=CHUNK, compressed=True,
                          codec="sparse")


def test_compressed_tier_bit_identical_to_raw_tier():
    """Tenants shipping producer-compressed payloads fold through the
    vmapped fold_codec and every final snapshot is bit-identical to
    the raw tier's (and to the single-stream oracle); dispatches land
    on ``tenants.compressed_dispatches``."""
    def chunk_lists(t):
        return list(_stream(300 + t))

    agg_r, cap = cc_tenant_tier(N_V, chunk_capacity=CHUNK)
    eng_r = MultiTenantEngine(merge_every=2)
    eng_r.add_tier("cc", agg_r, cap)
    for t in range(4):
        eng_r.admit(t, "cc", chunks=chunk_lists(t))
    raw = eng_r.drain()

    agg_c, cap = _compressed_tier()
    eng_c = MultiTenantEngine(merge_every=2)
    eng_c.add_tier("cc", agg_c, cap, compressed=True)
    with obs_bus.scope() as bus:
        for t in range(4):
            eng_c.admit(t, "cc", chunks=[
                agg_c.host_compress(c) for c in chunk_lists(t)
            ])
        comp = eng_c.drain()
    counters = bus.snapshot()["counters"]
    assert counters["tenants.compressed_dispatches"] >= 1
    assert counters["tenants.compressed_dispatches"] == \
        counters["tenants.dispatches"]
    for t in range(4):
        assert comp[t].dtype == raw[t].dtype
        assert comp[t].tobytes() == raw[t].tobytes()


def test_compressed_tier_push_mode_and_uneven_streams():
    """submit_payload from the producer thread; uneven backlogs ride
    masked identity-payload lanes without disturbing neighbors."""
    agg, cap = _compressed_tier()
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("cc", agg, cap, compressed=True)
    chunks_a = list(_stream(31, n_edges=4 * CHUNK))
    chunks_b = list(_stream(32, n_edges=CHUNK))  # 4x shorter
    eng.admit("a", "cc")
    eng.admit("b", "cc")
    for c in chunks_a:
        eng.submit_payload("a", agg.host_compress(c))
    for c in chunks_b:
        eng.submit_payload("b", agg.host_compress(c))
    eng.finish("a")
    eng.finish("b")
    out = eng.drain()
    for tid, chunks in (("a", chunks_a), ("b", chunks_b)):
        want = np.asarray(run_aggregation_oracle(chunks))
        assert out[tid].tobytes() == want.tobytes()


def run_aggregation_oracle(chunks):
    from gelly_tpu.engine.aggregation import run_aggregation

    agg = _cc_plan()
    return run_aggregation(
        agg, chunks, merge_every=1, ingest_workers=0,
        prefetch_depth=0, h2d_depth=0,
    ).result()


def test_compressed_tier_guards():
    agg_c, cap = _compressed_tier()
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("cc", agg_c, cap, compressed=True)
    eng.add_tier("raw", _cc_plan(), cap)
    eng.admit("c", "cc")
    eng.admit("r", "raw")
    chunk = next(iter(_stream(5)))
    # raw chunk into a compressed tier / payload into a raw tier
    with pytest.raises(ValueError, match="compressed tier"):
        eng.submit("c", chunk)
    with pytest.raises(ValueError, match="raw tier"):
        eng.submit_payload("r", agg_c.host_compress(chunk))
    # an EdgeChunk smuggled through submit_payload is named loudly
    with pytest.raises(ValueError, match="EdgeChunk"):
        eng.submit_payload("c", chunk)
    # payload template mismatch raises to the SUBMITTER, not the
    # scheduler: first payload pins the codec shape
    eng.submit_payload("c", agg_c.host_compress(chunk))
    with pytest.raises(ValueError, match="tier template"):
        eng.submit_payload("c", {"v": np.zeros(3, np.int64),
                                 "r": np.zeros(3, np.int32)})
    # a NESTED payload (e.g. a fused multi-query codec dict) must fail
    # at the submitter, not poison the template as a 0-d object array
    with pytest.raises(ValueError, match="FLAT dict"):
        eng.submit_payload("c", {"cc": {"v": np.zeros(2, np.int32)}})
    # out-of-range ids raise at the submitter (payload_to_chunk
    # parity) — on device they would silently drop/clamp
    with pytest.raises(ValueError, match="out of range"):
        eng.submit_payload("c", {"v": np.asarray([N_V + 3], np.int32),
                                 "r": np.asarray([0], np.int32)})
    # one tenant's oversized payload must not inflate every lane's
    # padded bucket: variable keys are bounded by 2 x chunk_capacity
    big = np.arange(2 * CHUNK + 1, dtype=np.int32) % N_V
    with pytest.raises(ValueError, match="tier bound"):
        eng.submit_payload("c", {"v": big, "r": np.zeros_like(big)})
    # a compressed tier needs a plan with fold_compressed
    with pytest.raises(ValueError, match="fold_compressed"):
        eng.add_tier("bad", _cc_plan(), cap, compressed=True)
    # ... and host_compress (masked lanes pad with the codec identity
    # payload — a missing one must fail at REGISTRATION, not at the
    # first dispatch with a drained lane)
    import dataclasses

    no_hc = dataclasses.replace(
        agg_c, host_compress=None, name="no-host-compress",
    )
    with pytest.raises(ValueError, match="host_compress"):
        eng.add_tier("bad2", no_hc, cap, compressed=True)


@pytest.mark.ingest
def test_tenant_router_routes_compressed_streams(await_tenant_windows):
    """Wire leg end to end: clients compress BEFORE send
    (DATA_COMPRESSED + tenant tag), the router submits payloads
    straight into the compressed tier, and the folded labels match the
    single-stream oracle — with zero ingest-side compress work."""
    from gelly_tpu.ingest import IngestClient, IngestServer, TenantRouter

    agg, cap = cc_tenant_tier(N_V, chunk_capacity=16, compressed=True,
                              codec="sparse")
    eng = MultiTenantEngine(merge_every=1).start()
    router = TenantRouter(eng, "small", vertex_capacity=N_V)
    eng.add_tier("small", agg, cap, compressed=True)
    edges = {
        t: np.random.default_rng(200 + t).integers(0, N_V, (64, 2))
        for t in (3, 4)
    }
    from gelly_tpu.core.chunk import make_chunk

    def payloads_for(t):
        out = []
        for i in range(0, 64, 16):
            s = edges[t][i:i + 16, 0].astype(np.int64)
            d = edges[t][i:i + 16, 1].astype(np.int64)
            c = make_chunk(s.astype(np.int32), d.astype(np.int32),
                           raw_src=s, raw_dst=d, capacity=16,
                           device=False)
            p = dict(agg.host_compress(c))
            p["tenant"] = np.array([t], np.int64)
            out.append(p)
        return out

    servers, clients = [], []
    try:
        for t in (3, 4):
            s = IngestServer(port=0).start()
            router.attach(s)
            c = IngestClient("127.0.0.1", s.port).connect()
            servers.append(s)
            clients.append((t, c))
        for t, c in clients:
            for p in payloads_for(t):
                c.send_compressed(p)
            c.flush()
        await_tenant_windows(eng, (3, 4), 4)
        for t in (3, 4):
            eng.finish(t)
        got = {t: eng.labels(t) for t in (3, 4)}
    finally:
        eng.stop()
        for s in servers:
            s.stop()
        router.stop()
    raw_plan = _cc_plan()
    for t in (3, 4):
        st = edge_stream_from_edges(
            [(int(a), int(b)) for a, b in edges[t]],
            vertex_capacity=N_V, chunk_size=16,
            table=IdentityVertexTable(N_V),
        )
        want = np.asarray(st.aggregate(raw_plan, merge_every=1).result())
        assert got[t].tobytes() == want.tobytes()


# --------------------------------------------------------------------- #
# wire routing (ingest front end)


@pytest.mark.ingest
def test_tenant_router_routes_n_client_streams(await_tenant_windows):
    from gelly_tpu.ingest import IngestClient, IngestServer, TenantRouter
    from gelly_tpu.ingest.client import edge_payload

    agg, cap = cc_tenant_tier(N_V, chunk_capacity=16)
    eng = MultiTenantEngine(merge_every=1).start()
    router = TenantRouter(eng, "small", vertex_capacity=N_V)
    eng.add_tier("small", agg, cap)
    edges = {
        t: np.random.default_rng(t).integers(0, N_V, (64, 2))
        for t in (7, 9)
    }
    servers, clients = [], []
    try:
        for t in (7, 9):
            s = IngestServer(port=0).start()
            router.attach(s)
            c = IngestClient("127.0.0.1", s.port).connect()
            servers.append(s)
            clients.append((t, c))
        for t, c in clients:
            for i in range(0, 64, 16):
                p = edge_payload(edges[t][i:i + 16, 0],
                                 edges[t][i:i + 16, 1])
                p["tenant"] = np.array([t], np.int64)
                c.send(p)
            c.flush()
        await_tenant_windows(eng, (7, 9), 4)
        for t in (7, 9):
            eng.finish(t)
        got = {t: eng.labels(t) for t in (7, 9)}
    finally:
        eng.stop()
        for s in servers:
            s.stop()
        router.stop()
    for t in (7, 9):
        st = edge_stream_from_edges(
            [(int(a), int(b)) for a, b in edges[t]],
            vertex_capacity=N_V, chunk_size=16,
            table=IdentityVertexTable(N_V),
        )
        want = np.asarray(st.aggregate(agg, merge_every=1).result())
        assert got[t].tobytes() == want.tobytes()


@pytest.mark.ingest
def test_tenant_router_unroutable_payloads_counted():
    from gelly_tpu.ingest import IngestClient, IngestServer, TenantRouter
    from gelly_tpu.ingest.client import edge_payload

    agg, cap = cc_tenant_tier(N_V, chunk_capacity=16)
    with obs_bus.scope() as bus:
        eng = MultiTenantEngine(merge_every=1).start()
        eng.add_tier("small", agg, cap)
        router = TenantRouter(eng, "small", vertex_capacity=N_V,
                              auto_admit=False)
        s = IngestServer(port=0).start()
        router.attach(s)
        try:
            c = IngestClient("127.0.0.1", s.port).connect()
            p = edge_payload(np.array([1, 2]), np.array([3, 4]))
            p["tenant"] = np.array([42], np.int64)  # never admitted
            c.send(p)
            c.flush()
            deadline = time.time() + 10
            while (time.time() < deadline and
                   bus.counters.get("ingest.chunks_unroutable", 0) < 1):
                time.sleep(0.02)
            assert bus.counters["ingest.chunks_unroutable"] >= 1
        finally:
            eng.stop()
            s.stop()
            router.stop()


# --------------------------------------------------------------------- #
# idle-lane reclamation (lane widths previously only grew)


def _feed_blocking(eng, tid, it, n):
    """Submit up to n chunks, blocking on a small queue bound so chunks
    are never dropped on the floor."""
    fed = 0
    deadline = time.time() + 60
    while fed < n and time.time() < deadline:
        c = next(it, None)
        if c is None:
            break
        while eng.queue_depth(tid) >= 2 and time.time() < deadline:
            time.sleep(0.002)
        eng.submit(tid, c)
        fed += 1
    return fed


def test_idle_lane_reclamation_halves_width(tmp_path):
    """High-water live count below width/2 for K consecutive windows
    halves the tier stack: evicted (done) tenants' rows are snapshotted
    (queries keep answering, final checkpoint durable) and live tenants
    compact into the low lanes; later admissions reuse them."""
    cc = _cc_plan()
    with obs_bus.scope() as bus:
        eng = MultiTenantEngine(merge_every=1, reclaim_after=2,
                                checkpoint_dir=str(tmp_path))
        eng.add_tier("cc", cc, CHUNK)
        for i in range(6):  # short streams: finish after 3 windows
            eng.admit(i, "cc", chunks=_stream(i))
        eng.admit("a", "cc")
        eng.admit("b", "cc")
        tier = eng._tiers["cc"]
        assert tier.batch.lanes == 8
        eng.start()
        try:
            chunks = {t: list(_stream(900 + ord(t), n_edges=960))
                      for t in ("a", "b")}
            feeds = {t: iter(cs) for t, cs in chunks.items()}
            fed = {"a": 0, "b": 0}
            # Phase 1: 2 live tenants of 8 lanes — high-water 2 < 8/2,
            # so the stack halves to 4 (and stops there: 2*2 < 4 is
            # false, the hysteresis bound).
            deadline = time.time() + 90
            while time.time() < deadline and tier.batch.lanes > 4:
                for t, it in feeds.items():
                    fed[t] += _feed_blocking(eng, t, it, 1)
            assert tier.batch.lanes == 4
            # Phase 2: finish one live tenant; high-water drops to 1 <
            # 4/2 and the stack halves again to the 2-lane pow-2 floor.
            eng.finish("b")
            while time.time() < deadline and tier.batch.lanes > 2:
                fed["a"] += _feed_blocking(eng, "a", feeds["a"], 1)
            assert tier.batch.lanes == 2
            assert eng.stats["reclaims"] >= 2
            assert eng.stats["lanes_reclaimed"] >= 6
            assert bus.counters["tenants.reclaims"] >= 2
            assert bus.counters["tenants.lanes_reclaimed"] >= 6

            # Evicted tenants: queries answer from the parked row,
            # bit-identical to the standalone oracle, with a durable
            # final checkpoint at the evicted position.
            for i in range(6):
                got = eng.labels(i)
                assert got is not None
                want = np.asarray(
                    _stream(i).aggregate(cc, merge_every=1).result()
                )
                assert got.tobytes() == want.tobytes()
                assert eng.snapshot_window(i) > 0
                pos = eng.position(i)
                assert os.path.exists(
                    eng._tenants[i].manager.path_for(pos)
                )

            # A post-reclaim admission reuses the freed lane space.
            lane_c = eng.admit("c", "cc", chunks=_stream(777))
            assert lane_c <= 2
            eng.finish("a")
            deadline = time.time() + 60
            while time.time() < deadline and any(
                not t.done for t in eng._tenants.values()
            ):
                time.sleep(0.01)
        finally:
            eng.stop()
        # Live tenants were remapped mid-serving and the new admission
        # rode the shrunken stack: all still bit-identical to oracles
        # over exactly the chunks they folded.
        from gelly_tpu.engine.aggregation import run_aggregation

        for tid in ("a", "b"):
            assert eng.position(tid) == fed[tid]
            want = np.asarray(run_aggregation(
                cc, chunks[tid][: fed[tid]], merge_every=1,
                ingest_workers=0, prefetch_depth=0, h2d_depth=0,
            ).result())
            got = eng.labels(tid)
            assert got is not None
            assert got.tobytes() == want.tobytes(), tid
        want_c = np.asarray(
            _stream(777).aggregate(cc, merge_every=1).result()
        )
        got_c = eng.labels("c")
        assert got_c is not None and got_c.tobytes() == want_c.tobytes()


def test_reclamation_respects_min_lanes_and_stays_off_by_default():
    """min_lanes floors the shrink target, and an engine without
    reclaim_after never reclaims no matter how idle the tier goes."""
    cc = _cc_plan()
    # Default: off. Drain a tier down to one live tenant; width stays.
    eng = MultiTenantEngine(merge_every=1)
    eng.add_tier("cc", cc, CHUNK)
    for i in range(4):
        eng.admit(i, "cc", chunks=_stream(i))
    eng.drain()
    assert eng._tiers["cc"].batch.lanes == 4
    assert eng.stats["reclaims"] == 0

    # min_lanes=4 floors the target: 1 live tenant of 4 lanes never
    # shrinks below the floor (and so never reclaims at all here).
    eng2 = MultiTenantEngine(merge_every=1, reclaim_after=1)
    eng2.add_tier("cc", _cc_plan(), CHUNK, min_lanes=4)
    for i in range(3):
        eng2.admit(i, "cc", chunks=_stream(i))
    eng2.admit("live", "cc")
    eng2.start()
    try:
        it = iter(list(_stream(321, n_edges=320)))
        _feed_blocking(eng2, "live", it, 10)
        time.sleep(0.3)  # several windows' worth of close cadence
        assert eng2._tiers["cc"].batch.lanes == 4
        assert eng2.stats["reclaims"] == 0
        eng2.finish("live")
        deadline = time.time() + 30
        while time.time() < deadline and any(
            not t.done for t in eng2._tenants.values()
        ):
            time.sleep(0.01)
    finally:
        eng2.stop()


def test_reclaim_after_validation():
    with pytest.raises(ValueError, match="reclaim_after"):
        MultiTenantEngine(reclaim_after=0)


def test_reclamation_defers_while_a_tenant_is_half_admitted():
    """admit() publishes (lane, resume state, readiness) in stages; a
    reclaim interleaving with it would remap or drop the lane the
    admission still holds. The reclaim body therefore DEFERS whenever
    any lane-holding tenant is not yet ready — and proceeds once the
    admission completes."""
    cc = _cc_plan()
    with obs_bus.scope():
        eng = MultiTenantEngine(merge_every=1, reclaim_after=1)
        eng.add_tier("cc", cc, CHUNK)
        for i in range(4):
            eng.admit(i, "cc", chunks=_stream(i))
        eng.admit("live", "cc")
        for c in _stream(55, n_edges=64):
            eng.submit("live", c)
        eng.finish("live")
        eng.drain()  # everyone done; width 8 (5 admits)
        tier = eng._tiers["cc"]
        width0 = tier.batch.lanes
        # Simulate an in-flight admission: insert a lane-holding tenant
        # that admit() has not yet marked ready, then force the reclaim
        # conditions — the body must refuse to shrink.
        from gelly_tpu.engine.tenants import _Tenant

        half = _Tenant("half", "cc", width0 - 1)
        with eng._lock:
            eng._tenants["half"] = half
        tier.low_windows = 10
        tier.hw_active = 0
        eng._maybe_reclaim(tier, obs_bus.get_bus(), None)
        assert tier.batch.lanes == width0
        assert eng.stats["reclaims"] == 0
        # Admission completes: the same conditions now reclaim.
        with eng._lock:
            half.ready = True
            half.done = True  # finished instantly; lane is evictable
        tier.low_windows = 10
        eng._maybe_reclaim(tier, obs_bus.get_bus(), None)
        assert tier.batch.lanes < width0
        assert eng.stats["reclaims"] == 1
