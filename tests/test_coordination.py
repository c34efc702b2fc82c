"""Coordinated multi-host recovery — in-process protocol tier.

Multiple "hosts" are plain :class:`Coordinator` instances with explicit
:class:`HostIdentity` sharing one store directory (threads where the
protocol needs concurrency). The real 2-process gloo-mesh tier —
jax.distributed + SIGKILL mid-stream — lives in
``tests/test_coordinated_recovery.py``; everything deterministic about
the protocol itself (barrier agreement, 2PC abort, leader rotation,
manifest/mixed-epoch validation, degraded adoption, fault injection,
the cadenced path flatten) is proven here, fast.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax

from gelly_tpu.engine import coordination as coord_mod
from gelly_tpu.engine import faults
from gelly_tpu.engine.coordination import (
    CheckpointStore,
    CoordinationConfig,
    Coordinator,
    CoordinationError,
    HostIdentity,
    LeaseBoard,
    ManifestCorruptError,
    MixedEpochError,
)
from gelly_tpu.engine.resilience import (
    CheckpointManager,
    ResilienceConfig,
    ResilientRunner,
    Watchdog,
    WatchdogTimeout,
)
from gelly_tpu.obs import bus as obs_bus


@pytest.fixture(autouse=True)
def _reset_active_coordinator():
    """Coordinators register themselves for heartbeat/trace leadership
    attribution; tests here construct many without closing — clear the
    registry so no leadership flag leaks across tests/files."""
    yield
    coord_mod._ACTIVE = None


def _cfg(**kw):
    kw.setdefault("lease_ttl", 2.0)
    kw.setdefault("poll_s", 0.005)
    kw.setdefault("barrier_timeout", 10.0)
    # In-process tests simulate silent host death by simply STOPPING a
    # coordinator's calls, so the background lease thread (which would
    # keep the "dead" host alive) is opted out here; its semantics get
    # a dedicated test below, and the gloo subprocess tier runs with it
    # on (SIGKILL kills the thread — the production shape).
    kw.setdefault("lease_thread", False)
    return CoordinationConfig(**kw)


def _fast(**kw):
    kw.setdefault("checkpoint_every_chunks", 4)
    kw.setdefault("watchdog_timeout", 30.0)
    return ResilienceConfig(**kw)


def _run_hosts(n, body):
    """Run ``body(k)`` for each host index on its own thread; re-raise
    the first failure (coordination is symmetric — one host erroring
    usually strands the others in a bounded wait)."""
    errs = []

    def wrapped(k):
        try:
            body(k)
        except BaseException as e:  # noqa: BLE001
            errs.append((k, e))

    ts = [threading.Thread(target=wrapped, args=(k,)) for k in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    if errs:
        raise errs[0][1]
    stuck = [k for k, t in enumerate(ts) if t.is_alive()]
    if stuck:
        raise AssertionError(f"hosts {stuck} still running after 60 s")


# ---------------------------------------------------------------------- #
# identities, leases, store plumbing


def test_host_identity_validation():
    with pytest.raises(ValueError):
        HostIdentity(2, 2)
    with pytest.raises(ValueError):
        HostIdentity(-1, 2)
    with pytest.raises(ValueError):
        HostIdentity(0, 0)
    ident = coord_mod.detect_host_identity()
    assert ident.process_index == 0 and ident.process_count == 1


def test_lease_board_liveness_and_expiry(tmp_path):
    store = CheckpointStore(str(tmp_path))
    now = [100.0]
    b0 = LeaseBoard(store, 0, ttl=1.0, clock=lambda: now[0])
    b1 = LeaseBoard(store, 1, ttl=1.0, clock=lambda: now[0])
    assert b0.beat() and b1.beat()
    assert b0.live() == {0, 1}
    assert not b0.expired(1)
    assert not b0.expired(7)  # absent lease = unknown, never "dead"
    now[0] += 0.2
    assert not b1.beat()  # rate-limited to ttl/3
    now[0] += 2.0
    b0.beat()
    assert b0.live() == {0}
    assert b0.expired(1)


def test_store_atomic_writes_leave_no_tmp(tmp_path):
    store = CheckpointStore(str(tmp_path))
    store.write_intent(1, 0, 5)
    store.write_prepared(1, 0, 5)
    store.commit(1, 5, 1)
    leftovers = [
        f for _, _, files in os.walk(tmp_path) for f in files
        if f.endswith(".tmp")
    ]
    assert leftovers == []
    man = store.read_manifest()
    assert man["epoch"] == 1 and man["position"] == 5
    assert man["hosts"] == [0]


def test_torn_manifest_rejected(tmp_path):
    store = CheckpointStore(str(tmp_path))
    store.commit(3, 12, 2)
    with open(store.manifest_path, "r+") as f:
        f.truncate(os.path.getsize(store.manifest_path) // 2)
    with pytest.raises(ManifestCorruptError, match="torn|unparsable"):
        store.read_manifest()
    # schema damage is rejected too, distinctly from a tear
    with open(store.manifest_path, "w") as f:
        json.dump({"version": 1, "epoch": 3}, f)
    with pytest.raises(ManifestCorruptError, match="position"):
        store.read_manifest()


def test_mixed_epoch_store_rejected(tmp_path):
    """Validation targets the SHARDS (the fsync-durable, deterministic
    truth — votes are a commit artifact a crashed re-attempt may have
    overwritten): a committed epoch missing any host's shard at the
    manifest position is rejected."""
    store = CheckpointStore(str(tmp_path))
    state = {"x": np.arange(4, dtype=np.int64)}
    # host 0's shard at position 8; host 1 "died mid-write": its shard
    # exists only at an OLDER position (epoch surgery / partial copy).
    store.write_shard(2, 0, state, 8)
    store.write_shard(2, 1, state, 4)
    store.commit(2, 8, 2)  # a manifest 2PC would never have written
    man = store.read_manifest()
    with pytest.raises(MixedEpochError, match="missing"):
        store.validate_epoch(man)
    store.write_shard(2, 1, state, 8)
    store.validate_epoch(man)  # consistent at last
    # a shard whose INTERNAL position header disagrees is caught at
    # load (the recover path), not by the existence scan
    state2, pos, _ = store.load_shard(2, 1, 8)
    assert pos == 8


# ---------------------------------------------------------------------- #
# barrier + 2PC


def test_barrier_agrees_on_max_and_commits(tmp_path):
    results = {}

    def body(k):
        co = Coordinator(str(tmp_path), HostIdentity(k, 2), _cfg())
        epoch, agreed = co.agree_position(3 + k)  # proposals 3 and 4
        man = co.publish(epoch, {"x": np.arange(4) + k}, agreed)
        results[k] = (epoch, agreed, man["epoch"], man["position"])

    with obs_bus.scope() as bus:
        _run_hosts(2, body)
    assert results[0] == results[1] == (1, 4, 1, 4)
    counters = bus.snapshot()["counters"]
    assert counters["coordination.barrier_agreed"] == 2
    assert counters["coordination.prepared"] == 2
    assert counters["coordination.committed"] == 1


def test_epoch_aborts_when_host_dies_before_preparing(tmp_path):
    """2PC phase-1 death: the missing host's lease expires, the leader
    aborts the epoch, and NO manifest exists — recovery sees the
    previous committed state, never half an epoch."""
    cfg = _cfg(lease_ttl=0.5, barrier_timeout=5.0)
    co0 = Coordinator(str(tmp_path), HostIdentity(0, 2), cfg)
    co1 = Coordinator(str(tmp_path), HostIdentity(1, 2), cfg)
    # Both agree on the barrier...
    out = {}

    def body(k):
        co = (co0, co1)[k]
        out[k] = co.agree_position(6)

    _run_hosts(2, body)
    assert out[0] == out[1]
    epoch, agreed = out[0]
    # ...but host 1 dies before writing its shard: its lease lapses.
    time.sleep(0.7)
    with pytest.raises(CoordinationError, match="died before preparing"):
        co0.publish(epoch, {"x": np.arange(2)}, agreed)
    assert CheckpointStore(str(tmp_path)).read_manifest() is None


def test_leader_rotation_commits_after_leader_death(tmp_path):
    """Leader dies BETWEEN phases (its shard is prepared, the manifest
    is not written): the next-lowest live host observes the lease
    expiry, becomes leader, and completes the commit — rotation, not
    abort. Leadership loss is published on the bus."""
    cfg = _cfg(lease_ttl=0.4, barrier_timeout=10.0)
    with obs_bus.scope() as bus:
        co0 = Coordinator(str(tmp_path), HostIdentity(0, 2), cfg)
        co1 = Coordinator(str(tmp_path), HostIdentity(1, 2), cfg)
        out = {}

        def body(k):
            out[k] = (co0, co1)[k].agree_position(9)

        _run_hosts(2, body)
        epoch, agreed = out[0]
        # Host 0 (the leader) prepares its shard, then dies silently.
        co0.store.write_shard(epoch, 0, {"x": np.arange(3)}, agreed)
        co0.store.write_prepared(epoch, 0, agreed)
        time.sleep(0.6)  # let the leader's lease lapse
        man = co1.publish(epoch, {"x": np.arange(3) + 1}, agreed)
    assert man["epoch"] == epoch and man["position"] == agreed
    assert man["meta"]["committed_by"] == 1
    assert co1.is_leader
    counters = bus.snapshot()["counters"]
    assert counters["coordination.leader_elected"] >= 3  # initial + takeover
    assert counters["coordination.committed"] == 1
    CheckpointStore(str(tmp_path)).validate_epoch(man)


def test_lease_thread_keeps_host_alive_through_stalls(tmp_path):
    """The background beat thread makes the lease mean PROCESS
    liveness: a host stalled past the ttl (shard write, jit compile)
    is never false-declared dead; close() stops the thread and the
    lease then expires like a real departure."""
    cfg = _cfg(lease_ttl=0.45, lease_thread=True)
    co = Coordinator(str(tmp_path), HostIdentity(0, 2), cfg)
    observer = LeaseBoard(CheckpointStore(str(tmp_path)), 1, ttl=0.45)
    time.sleep(0.7)  # stall with no protocol calls, longer than ttl
    assert not observer.expired(0)
    co.close()
    time.sleep(0.7)
    assert observer.expired(0)


def test_epoch_numbering_derives_from_committed_state(tmp_path):
    """Epochs are ``committed + 1`` — derived from the manifest every
    host reads, never from racy directory listings — and records left
    by a PREVIOUS incarnation in a re-attempted epoch dir are filtered
    by run_id instead of mis-agreeing the barrier."""
    co = Coordinator(str(tmp_path), HostIdentity(0, 1), _cfg())
    e1, _ = co.agree_position(2)
    assert e1 == 1
    co.publish(e1, {"x": np.arange(2)}, 2)
    # a crashed incarnation left an uncommitted higher epoch dir plus a
    # stale intent inside the epoch the new incarnation will re-attempt
    os.makedirs(co.store.epoch_dir(7), exist_ok=True)
    co.store.write_intent(2, 1, 999, run_id="e0-stale")
    co2 = Coordinator(str(tmp_path), HostIdentity(0, 1), _cfg())
    e2, p2 = co2.agree_position(4)
    # committed(1)+1, stale dir 7 ignored — and the agreed position is
    # 4, not the stale intent's 999 (run_id filter).
    assert (e2, p2) == (2, 4)
    # 2-host shape: the stale peer intent must NOT satisfy the
    # rendezvous (different incarnation) — the barrier times out on the
    # genuinely absent host instead of agreeing on position 999.
    co3 = Coordinator(str(tmp_path), HostIdentity(0, 2),
                      _cfg(barrier_timeout=0.8))
    with pytest.raises(CoordinationError, match="incomplete"):
        co3.agree_position(6)


def test_reattempted_epoch_converges_over_stale_records(tmp_path):
    """A crashed incarnation that shares the restart's run_id (same
    committed base) left intents/votes in the uncommitted next epoch —
    including some from a host that no longer exists. The restart must
    scrub its own leftovers, ignore the out-of-group host's, and drive
    the re-attempted epoch to a clean commit at the FRESH positions."""
    _committed_two_host_store(tmp_path, position=8)
    store = CheckpointStore(str(tmp_path))
    man = store.read_manifest()
    run_id = f"e{man['epoch']}-{man['wall_time']}"
    for h, pos in ((0, 99), (1, 98), (2, 97)):
        store.write_intent(2, h, pos, run_id=run_id)
        store.write_prepared(2, h, pos, run_id=run_id)
    out = {}

    def body(k):
        # A loaded run can stall a host thread for seconds: give the
        # barrier room inside _run_hosts' 60 s join, so a slow run still
        # converges and a stuck one fails with the barrier's message.
        co = Coordinator(str(tmp_path), HostIdentity(k, 2),
                         _cfg(barrier_timeout=45.0))
        _, pos, _ = co.recover(like={"x": np.zeros(4, dtype=np.int64)})
        epoch, agreed = co.agree_position(pos + 4)
        man2 = co.publish(
            epoch, {"x": np.arange(4, dtype=np.int64)}, agreed
        )
        out[k] = (epoch, agreed, man2["position"])

    _run_hosts(2, body)
    assert out[0] == out[1] == (2, 12, 12)


def test_prune_keeps_committed_window(tmp_path):
    store = CheckpointStore(str(tmp_path))
    for e in (1, 2, 3, 4, 5):
        os.makedirs(store.epoch_dir(e), exist_ok=True)
    store.prune(committed=5, keep=2)
    assert store.list_epochs() == [4, 5]


# ---------------------------------------------------------------------- #
# recover: re-join + the degradation rung


def _committed_two_host_store(tmp_path, position=8):
    def body(k):
        co = Coordinator(str(tmp_path), HostIdentity(k, 2), _cfg())
        epoch, agreed = co.agree_position(position)
        co.publish(
            epoch, {"x": np.arange(4, dtype=np.int64) * (k + 1)}, agreed
        )

    _run_hosts(2, body)


def test_rejoin_loads_own_shard_at_barrier_position(tmp_path):
    _committed_two_host_store(tmp_path)
    with obs_bus.scope() as bus:
        co = Coordinator(str(tmp_path), HostIdentity(1, 2), _cfg())
        state, pos, _meta = co.recover(
            like={"x": np.zeros(4, dtype=np.int64)}
        )
    assert pos == 8
    np.testing.assert_array_equal(state["x"], np.arange(4) * 2)
    assert bus.snapshot()["counters"]["coordination.rejoins"] == 1


def test_degraded_rejoin_adopts_orphan_shards(tmp_path):
    """The degradation rung: one survivor of a 2-host group re-joins
    with process_count=1, adopts the lost host's shard via the combine,
    and a ``coordination.degradations`` event is published — the stream
    continues at reduced capacity instead of aborting."""
    _committed_two_host_store(tmp_path)
    events = []
    with obs_bus.scope() as bus:
        bus.subscribe(lambda name, f: events.append((name, f)))
        co = Coordinator(str(tmp_path), HostIdentity(0, 1), _cfg())
        state, pos, _meta = co.recover(
            like={"x": np.zeros(4, dtype=np.int64)},
            adopt=lambda a, b: {"x": a["x"] + b["x"]},
        )
    assert pos == 8
    np.testing.assert_array_equal(state["x"], np.arange(4) * 3)
    degr = [f for name, f in events if name == "coordination.degradations"]
    assert len(degr) == 1
    assert degr[0]["previous_process_count"] == 2
    assert degr[0]["process_count"] == 1
    assert degr[0]["adopted"] == [1]
    assert degr[0]["capacity_frac"] == 0.5


def test_degraded_rejoin_without_adopt_refuses(tmp_path):
    _committed_two_host_store(tmp_path)
    co = Coordinator(str(tmp_path), HostIdentity(0, 1), _cfg())
    with pytest.raises(CoordinationError, match="adopt"):
        co.recover(like={"x": np.zeros(4, dtype=np.int64)})


def test_recover_rejects_mixed_epoch(tmp_path):
    _committed_two_host_store(tmp_path)
    store = CheckpointStore(str(tmp_path))
    man = store.read_manifest()
    os.unlink(store.shard_path(man["epoch"], 1, man["position"]))
    co = Coordinator(str(tmp_path), HostIdentity(0, 2), _cfg())
    with pytest.raises(MixedEpochError):
        co.recover(like={"x": np.zeros(4, dtype=np.int64)})


# ---------------------------------------------------------------------- #
# fault injection inside the protocol (the "barrier" boundary)


@pytest.mark.faults
def test_barrier_fault_raises_inside_agree(tmp_path):
    plan = faults.FaultPlan([faults.Fault("barrier", at=0)])
    co = Coordinator(str(tmp_path), HostIdentity(0, 1), _cfg())
    with faults.install(plan):
        with pytest.raises(faults.FaultInjected):
            co.agree_position(3)
    assert ("barrier", 0, "raise") in plan.fired


@pytest.mark.faults
def test_barrier_hang_is_caught_by_watchdog(tmp_path):
    plan = faults.FaultPlan([
        faults.Fault("barrier", at=0, kind="hang", hang_seconds=5.0),
    ])
    co = Coordinator(str(tmp_path), HostIdentity(0, 1), _cfg())
    wd = Watchdog(0.3)
    with faults.install(plan):
        with pytest.raises(WatchdogTimeout):
            wd.call(lambda: co.agree_position(3), "barrier")


@pytest.mark.faults
def test_barrier_corrupt_fault_tears_manifest(tmp_path):
    """The post-commit injection point carries the manifest path, so a
    seeded corrupt fault produces exactly the torn manifest recovery
    must reject."""
    # single host: barrier indices are 0=agree, 1=publish, 2=post-commit
    plan = faults.FaultPlan([
        faults.Fault("barrier", at=2, kind="corrupt"),
    ])
    co = Coordinator(str(tmp_path), HostIdentity(0, 1), _cfg())
    with faults.install(plan):
        epoch, agreed = co.agree_position(5)
        co.publish(epoch, {"x": np.arange(2)}, agreed)
    assert ("barrier", 2, "corrupt") in plan.fired
    with pytest.raises(ManifestCorruptError):
        CheckpointStore(str(tmp_path)).read_manifest()


@pytest.mark.faults
def test_collective_boundary_fires_at_window_merge():
    """The cross-shard window-close merge is a fault boundary: a seeded
    plan raises inside the engine's merge dispatch."""
    from gelly_tpu import edge_stream_from_edges
    from gelly_tpu.engine.aggregation import run_aggregation
    from gelly_tpu.library.degrees import degree_aggregate

    rng = np.random.default_rng(5)
    edges = [(int(a), int(b)) for a, b in rng.integers(0, 32, (64, 2))]
    stream = edge_stream_from_edges(edges, vertex_capacity=32,
                                    chunk_size=16)
    plan = faults.FaultPlan([faults.Fault("collective", at=0)])
    with faults.install(plan):
        with pytest.raises(faults.FaultInjected):
            run_aggregation(
                degree_aggregate(32), stream, merge_every=2,
            ).result()
    assert ("collective", 0, "raise") in plan.fired


# ---------------------------------------------------------------------- #
# coordinated ResilientRunner (threads = in-process hosts)


def _add_step(s, chunk):
    return s + np.int64(chunk), None


def test_coordinated_runner_end_to_end_and_resume(tmp_path):
    finals = {}

    def body(k):
        co = Coordinator(str(tmp_path), HostIdentity(k, 2), _cfg())
        r = ResilientRunner(
            _add_step, list(range(k * 100, k * 100 + 10)), np.int64(0),
            coordinator=co, config=_fast(),
        )
        finals[k] = (int(r.run()), r.stats["checkpoints"])

    _run_hosts(2, body)
    assert finals[0] == (sum(range(10)), 3)          # 4, 8, final 10
    assert finals[1] == (sum(range(100, 110)), 3)
    man = CheckpointStore(str(tmp_path)).read_manifest()
    assert man["position"] == 10 and man["process_count"] == 2

    # resume: both hosts restart, skip everything, recover their state
    def body2(k):
        co = Coordinator(str(tmp_path), HostIdentity(k, 2), _cfg())
        r = ResilientRunner(
            _add_step, list(range(k * 100, k * 100 + 10)), np.int64(0),
            coordinator=co, config=_fast(),
        )
        finals[k] = (int(r.run()), r.stats["chunks"],
                     r.stats["resumed_from"])

    _run_hosts(2, body2)
    for k in (0, 1):
        total, chunks, resumed_from = finals[k]
        assert total == sum(range(k * 100, k * 100 + 10))
        assert chunks == 0  # nothing re-folded
        assert resumed_from and resumed_from.endswith("MANIFEST.json")


def test_coordinated_runner_rejects_checkpoint_dir(tmp_path):
    co = Coordinator(str(tmp_path / "store"), HostIdentity(0, 1), _cfg())
    with pytest.raises(ValueError, match="not both"):
        ResilientRunner(
            _add_step, [1, 2], np.int64(0), coordinator=co,
            checkpoint_dir=str(tmp_path / "local"),
        )


def test_coordinated_runner_unequal_partitions_fail_loudly(tmp_path):
    """Hosts whose partitions disagree on the final chunk count must
    surface the skew as CoordinationError, not deadlock or silently
    commit a mixed position."""
    cfg = _cfg(barrier_timeout=2.0)
    errs = {}

    def body(k):
        co = Coordinator(str(tmp_path), HostIdentity(k, 2), cfg)
        r = ResilientRunner(
            _add_step, list(range(8 if k == 0 else 10)), np.int64(0),
            coordinator=co,
            config=_fast(checkpoint_every_chunks=100),
        )
        try:
            r.run()
        except CoordinationError as e:
            errs[k] = str(e)

    _run_hosts(2, body)
    assert errs, "at least one host must observe the skew"
    assert any("equal chunk counts" in v or "incomplete" in v
               for v in errs.values())


def test_degraded_runner_continues_at_reduced_capacity(tmp_path):
    """The acceptance shape: a 2-host committed store, one host
    permanently lost; the survivor re-joins with adopt, continues the
    stream (its own remainder plus the re-routed chunks), and a
    degradations event is published instead of an abort."""
    def body(k):
        co = Coordinator(str(tmp_path), HostIdentity(k, 2), _cfg())
        ResilientRunner(
            _add_step, [k * 10 + i for i in range(8)], np.int64(0),
            coordinator=co, config=_fast(),
        ).run()

    _run_hosts(2, body)
    man = CheckpointStore(str(tmp_path)).read_manifest()
    assert man["position"] == 8
    # host 1 is permanently gone; host 0 re-joins as a 1-host group.
    # Ingest-side re-routing is the caller's job: the survivor's source
    # holds the re-sharded tail (here: 4 fresh chunks past position 8).
    events = []
    with obs_bus.scope() as bus:
        bus.subscribe(lambda name, f: events.append((name, f)))
        co = Coordinator(str(tmp_path), HostIdentity(0, 1), _cfg())
        r = ResilientRunner(
            _add_step, lambda pos: iter(range(100, 100 + 12 - pos)),
            np.int64(0), coordinator=co, config=_fast(),
            adopt_state=lambda a, b: a + b,
        )
        final = int(r.run())
    both = sum(i for i in range(8)) + sum(10 + i for i in range(8))
    assert final == both + sum(range(100, 104))
    degr = [f for name, f in events
            if name == "coordination.degradations"]
    assert len(degr) == 1 and degr[0]["capacity_frac"] == 0.5
    man2 = CheckpointStore(str(tmp_path)).read_manifest()
    assert man2["position"] == 12 and man2["process_count"] == 1


# ---------------------------------------------------------------------- #
# atomic checkpoint publish: rotation can never strand zero valid files


@pytest.mark.faults
def test_rotation_never_prunes_fallback_of_torn_newest(tmp_path):
    """keep=1 + a torn final write: before the fix, rotation pruned the
    previous file and the store held ZERO valid checkpoints; now the
    newest file is validated before any pruning."""
    mgr = CheckpointManager(str(tmp_path), keep=1, async_write=False)
    mgr.save(np.int64(10), 4)
    plan = faults.FaultPlan([
        faults.Fault("checkpoint_corrupt", at=0, count=100,
                     kind="corrupt"),
    ])
    with obs_bus.scope() as bus:
        with faults.install(plan):
            mgr.save(np.int64(20), 8)
    files = [os.path.basename(p) for p in mgr.list()]
    assert "ckpt-000000000004.npz" in files  # fallback survived
    state, pos, _, path = mgr.load_latest(like=np.int64(0))
    assert pos == 4 and int(state) == 10
    assert bus.snapshot()["counters"]["resilience.rotation_skipped"] == 1


def test_save_checkpoint_fsyncs_before_rename(tmp_path, monkeypatch):
    from gelly_tpu.engine.checkpoint import save_checkpoint

    synced = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: synced.append(fd))
    path = str(tmp_path / "c.npz")
    save_checkpoint(path, np.arange(4), position=1)
    assert len(synced) >= 1  # file fsync (dir fsync is best-effort)
    monkeypatch.setattr(os, "fsync", real_fsync)
    synced.clear()
    save_checkpoint(path, np.arange(4), position=2, fsync=False)
    assert synced == []


# ---------------------------------------------------------------------- #
# the cadenced path flatten


def _chain_depth_stream(n_pairs):
    """Edge chunks that force union_pairs_rooted chase depth to grow:
    stars are built high and their roots repeatedly hooked under ever
    smaller slots, so each union hangs a deep tree one level deeper."""
    from gelly_tpu.core.chunk import make_chunk

    edges = []
    for level in range(n_pairs - 1, -1, -1):
        a, b = 2 ** (level + 1), 2 ** level
        edges.append((a, b))
    return [
        make_chunk(np.array([a], np.int64), np.array([b], np.int64),
                   capacity=1, device=False)
        for a, b in edges
    ]


def test_flatten_state_bounds_chase_depth_bit_identical(tmp_path):
    """The regression the satellite names: depth after flatten <= 2,
    labels bit-identical with and without the cadenced flatten."""
    from gelly_tpu.ops import unionfind

    cap = 64
    chunks = _chain_depth_stream(5)  # depth ~5 without flattening

    fold = jax.jit(
        lambda p, c: unionfind.union_pairs_rooted(p, c.src, c.dst, c.valid)
    )
    step = lambda p, c: (fold(p, c), None)  # noqa: E731

    def run(flatten, ckpt_dir):
        r = ResilientRunner(
            step, chunks, lambda: unionfind.fresh_forest(cap),
            checkpoint_dir=ckpt_dir, config=_fast(),
            flatten_state=flatten,
        )
        return r, r.run()

    _, plain = run(None, None)
    assert unionfind.chase_depth(plain) > 2  # the test actually bites

    depths = []
    flat_fn = jax.jit(unionfind.pointer_jump)

    def spy_flatten(p):
        out = flat_fn(p)
        depths.append(unionfind.chase_depth(out))
        return out

    r2, flat = run(spy_flatten, str(tmp_path))
    assert depths and max(depths) <= 2
    assert unionfind.chase_depth(flat) <= 2
    # labels identical: flatten only shortcuts chains
    labels_a = np.asarray(jax.jit(unionfind.pointer_jump)(plain))
    labels_b = np.asarray(jax.jit(unionfind.pointer_jump)(flat))
    assert labels_a.tobytes() == labels_b.tobytes()
    # and the checkpoint on disk holds the flattened forest
    from gelly_tpu.engine.checkpoint import load_checkpoint

    state, _, _ = load_checkpoint(r2.manager.list()[-1])
    assert unionfind.chase_depth(state[0]) <= 2


def test_engine_flatten_at_checkpoint_cadence(tmp_path):
    """SummaryAggregation.flatten rides run_aggregation's checkpoint
    cadence: the snapshot holds a flattened forest and emissions are
    identical to a flatten-free run."""
    from gelly_tpu.engine.aggregation import (
        SummaryAggregation,
        run_aggregation,
    )
    from gelly_tpu.engine.checkpoint import load_checkpoint
    from gelly_tpu.ops import unionfind
    from gelly_tpu.parallel import mesh as mesh_lib

    cap = 64
    chunks = _chain_depth_stream(5)

    def mk_agg(flatten):
        return SummaryAggregation(
            init=lambda: unionfind.fresh_forest(cap),
            fold=lambda p, c: unionfind.union_pairs_rooted(
                p, c.src, c.dst, c.valid
            ),
            combine=unionfind.merge_forests,
            transform=None,
            fold_accumulates=True,
            flatten=flatten,
            name="chain-uf",
        )

    mesh = mesh_lib.make_mesh(1)  # accumulate plan: the depth-growing one
    plain = run_aggregation(
        mk_agg(None), list(chunks), mesh=mesh, merge_every=1,
    ).result()
    assert unionfind.chase_depth(plain) > 2

    ckpt = str(tmp_path / "flat.npz")
    flat = run_aggregation(
        mk_agg(lambda p: unionfind.pointer_jump(p)), list(chunks),
        mesh=mesh, merge_every=1, checkpoint_path=ckpt,
        checkpoint_every=2,
    ).result()
    state, _, _ = load_checkpoint(ckpt)
    assert unionfind.chase_depth(state[0]) <= 2
    labels_a = np.asarray(jax.jit(unionfind.pointer_jump)(plain))
    labels_b = np.asarray(jax.jit(unionfind.pointer_jump)(flat))
    assert labels_a.tobytes() == labels_b.tobytes()


def test_cc_plans_supply_flatten():
    from gelly_tpu.library.connected_components import (
        CCSummary,
        connected_components,
    )
    from gelly_tpu.ops import unionfind

    agg = connected_components(64)
    assert agg.flatten is not None
    deep = unionfind.fresh_forest(64).at[np.array([3, 2, 1])].set(
        np.array([2, 1, 0], np.int32)
    )
    flat = agg.flatten(CCSummary(parent=deep,
                                 seen=np.zeros(64, bool)))
    assert unionfind.chase_depth(flat.parent) <= 1
    compact = connected_components(1 << 21, codec="compact")
    assert compact.flatten is not None


# ---------------------------------------------------------------------- #
# host identity on heartbeat lines + exported traces


def test_heartbeat_lines_carry_host_identity(tmp_path):
    from gelly_tpu.obs.heartbeat import Heartbeat

    co = Coordinator(str(tmp_path), HostIdentity(0, 1), _cfg())
    try:
        hb = Heartbeat(every_s=0)
        assert hb.tick(position=7)
        line = hb.lines[-1]
        assert line["process_index"] == 0
        assert line["process_count"] == 1
        assert "coordinator_address" in line
        assert line["leader"] is True  # active coordinator, sole host
        assert line["position"] == 7
    finally:
        co.close()
    hb2 = Heartbeat(every_s=0)
    assert hb2.tick(position=8)
    assert "leader" not in hb2.lines[-1]  # no coordinator active


def test_chrome_trace_otherdata_carries_host_identity(tmp_path):
    from gelly_tpu.obs.export import to_chrome_trace, validate_chrome_trace
    from gelly_tpu.obs.tracing import SpanTracer

    tr = SpanTracer(capacity=16)
    tr.span("fold", "fold", tr.now(), unit=0)
    co = Coordinator(str(tmp_path), HostIdentity(0, 1), _cfg())
    try:
        trace = to_chrome_trace(tr)
    finally:
        co.close()
    validate_chrome_trace(trace)
    host = trace["otherData"]["host"]
    assert host["process_index"] == 0
    assert host["process_count"] == 1
    assert "coordinator_address" in host
    assert host["leader"] is True


# ---------------------------------------------------------------------- #
# concurrency regression (racecheck RC001/RC002 fix): LeaseBoard.beat is
# called from the background lease thread AND the protocol paths


@pytest.mark.racecheck
def test_lease_beat_concurrent_force_beats_lose_no_updates(tmp_path):
    """beat() races the lease thread against maybe_beat/barrier force
    beats; pre-fix the unlocked ``beats += 1`` lost updates and the
    rate-limit check-then-set admitted overlapping writes. Post-fix the
    counter is exact under contention."""
    store = CheckpointStore(str(tmp_path))
    board = LeaseBoard(store, host=0, ttl=5.0)
    n_threads, per_thread = 8, 25

    def hammer():
        for _ in range(per_thread):
            assert board.beat(force=True)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert board.beats == n_threads * per_thread
    # the lease file survived the concurrent atomic writes and is fresh
    assert board.wall(0) is not None
    assert not board.expired(0)


@pytest.mark.racecheck
def test_lease_beat_rate_limit_still_rate_limits(tmp_path):
    """The lock must not break the ttl/3 rate limit: unforced beats
    within the window are rejected without a write."""
    now = [100.0]
    board = LeaseBoard(CheckpointStore(str(tmp_path)), host=0, ttl=3.0,
                       clock=lambda: now[0])
    assert board.beat()            # first write
    assert not board.beat()        # inside ttl/3
    now[0] += 1.01                 # past ttl/3 = 1.0
    assert board.beat()
    assert board.beats == 2
