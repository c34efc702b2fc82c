"""Multi-tenant exactly-once wire: pre-shared-key auth, per-tenant
sequence spaces, WELCOME park/pause/shed state, tenant-scoped
PAUSE/RESUME, typed NACK shed, tenant-mode CRC resync, checkpoint-gated
per-tenant acks, and the SIGKILL crash child proving no acked chunk is
ever double-folded across a server restart.
"""

import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from gelly_tpu import edge_stream_from_edges
from gelly_tpu.core.vertices import IdentityVertexTable
from gelly_tpu.engine.checkpoint import load_checkpoint
from gelly_tpu.engine.tenants import MultiTenantEngine
from gelly_tpu.ingest import IngestClient, IngestServer, TenantRouter
from gelly_tpu.ingest import wire
from gelly_tpu.ingest.client import IngestError, edge_payload
from gelly_tpu.ingest.server import payload_to_chunk
from gelly_tpu.library.connected_components import cc_tenant_tier
from gelly_tpu.obs import bus as obs_bus

pytestmark = pytest.mark.ingest

N_V = 128
CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "_qos_crash_child.py")


def _drain_frames(srv, out):
    """Background consumer keeping (seq, payload, compressed) triples."""
    def run():
        for item in srv.frames():
            out.append(item)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def _wait(pred, timeout=20.0, step=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(step)
    return pred()


def _read_frame(sock):
    return wire.read_frame(sock.recv)


# --------------------------------------------------------------------- #
# pre-shared-key HELLO auth


def test_auth_handshake_accepts_matching_token():
    with obs_bus.scope() as bus:
        with IngestServer(auth_token="sesame") as srv:
            out = []
            _drain_frames(srv, out)
            with IngestClient("127.0.0.1", srv.port,
                              auth_token="sesame") as cli:
                for i in range(3):
                    cli.send(edge_payload([i, i + 1], [i + 2, i + 3]))
                cli.flush()
            assert _wait(lambda: len(out) == 3)
        counters = bus.snapshot()["counters"]
        assert counters.get("ingest.auth_challenges") == 1
        assert "ingest.auth_failures" not in counters


def test_auth_missing_token_raises_before_streaming():
    with IngestServer(auth_token="sesame") as srv:
        cli = IngestClient("127.0.0.1", srv.port)  # no token
        with pytest.raises(IngestError, match="pre-shared auth token"):
            cli.connect()


def test_auth_wrong_token_gets_typed_auth_fail():
    with obs_bus.scope() as bus:
        with IngestServer(auth_token="sesame") as srv:
            cli = IngestClient("127.0.0.1", srv.port, auth_token="wrong")
            with pytest.raises(IngestError,
                               match="authentication failed"):
                cli.connect()
        counters = bus.snapshot()["counters"]
        assert counters.get("ingest.auth_failures") == 1
        assert counters.get("ingest.auth_challenges") == 1


def test_auth_refuses_data_before_handshake():
    """Nothing but the handshake crosses an unauthed connection: a raw
    DATA frame is answered with AUTH_FAIL and the connection closes."""
    with obs_bus.scope() as bus:
        with IngestServer(auth_token="sesame") as srv:
            raw = socket.create_connection(("127.0.0.1", srv.port))
            raw.settimeout(5)
            try:
                raw.sendall(wire.pack_frame(
                    wire.DATA, 0,
                    wire.pack_payload(edge_payload([1], [2]))))
                ftype, _seq, _payload = _read_frame(raw)
                assert ftype == wire.AUTH_FAIL
                # Terminal: the server closes after AUTH_FAIL.
                assert _read_frame(raw)[0] == wire.BYE  # clean EOF
            finally:
                raw.close()
        assert bus.snapshot()["counters"].get(
            "ingest.auth_failures") == 1


# --------------------------------------------------------------------- #
# per-tenant sequence spaces


def test_tenant_streams_have_distinct_seq_spaces():
    with IngestServer(tenant_streams=True) as srv:
        out = []
        _drain_frames(srv, out)
        cli = IngestClient("127.0.0.1", srv.port,
                           tenant_streams=True).connect()
        try:
            for i in range(4):
                cli.send(edge_payload([i], [i + 1]), tenant=7)
                if i < 2:
                    cli.send(edge_payload([i], [i + 2]), tenant=9)
            cli.flush()
            # Per-tenant acks: each space acknowledges its OWN count.
            assert cli.acked_for(7) == 4
            assert cli.acked_for(9) == 2
            assert _wait(lambda: len(out) == 6)
            seqs = {
                (int(np.asarray(p["tenant"]).reshape(-1)[0]), s)
                for s, p, _ in out
            }
            # Both spaces start at 0 — they are DISTINCT, not one
            # interleaved counter.
            assert seqs == {(7, 0), (7, 1), (7, 2), (7, 3),
                            (9, 0), (9, 1)}
        finally:
            cli.close(flush_timeout=None)


# --------------------------------------------------------------------- #
# WELCOME carries park/pause/shed state (reconnect regression)


def test_welcome_carries_tenant_hold_and_release():
    """Regression, both directions: a hold placed while NO client is
    connected lands via WELCOME (the reconnecting client holds
    immediately); a release while disconnected also lands (the client
    does not stay stuck on stale hold state)."""
    with IngestServer(tenant_streams=True) as srv:
        srv.pause_tenant(3)  # no connection yet: state only
        cli = IngestClient("127.0.0.1", srv.port, tenant_streams=True,
                           send_pause_timeout=10).connect()
        try:
            assert cli.tenant_paused(3)
            assert not cli.tenant_paused(4)
            # The un-held tenant flows.
            cli.send(edge_payload([1], [2]), tenant=4)
            cli.flush()
            # The held tenant's send blocks until the policy release.
            done = threading.Event()

            def held_send():
                cli.send(edge_payload([5], [6]), tenant=3)
                done.set()

            t = threading.Thread(target=held_send, daemon=True)
            t.start()
            time.sleep(0.2)
            assert not done.is_set()
            srv.resume_tenant(3)
            assert done.wait(5)
            cli.flush()
            # Direction two: release while DISCONNECTED.
            srv.pause_tenant(3)
            assert _wait(lambda: cli.tenant_paused(3))
            cli.close(flush_timeout=None)
            srv.resume_tenant(3)  # lands in no socket — state only
            cli.connect()
            assert not cli.tenant_paused(3)
            cli.send(edge_payload([7], [8]), tenant=3)
            cli.flush()
        finally:
            cli.close(flush_timeout=None)


def test_welcome_carries_legacy_pause_bit():
    """Legacy single-stream server: a policy hold (one tenant per
    server) pauses the WHOLE wire, and WELCOME carries the bit so a
    reconnecting client holds immediately."""
    with IngestServer() as srv:
        srv.pause_tenant(0)
        cli = IngestClient("127.0.0.1", srv.port).connect()
        try:
            assert cli.paused
            srv.resume_tenant(0)
            assert _wait(lambda: not cli.paused)
            cli.send(edge_payload([1], [2]))
            cli.flush()
        finally:
            cli.close(flush_timeout=None)


# --------------------------------------------------------------------- #
# typed NACK shed


def test_shed_tenant_nacks_and_closes_stream():
    with obs_bus.scope() as bus:
        with IngestServer(tenant_streams=True) as srv:
            out = []
            _drain_frames(srv, out)
            cli = IngestClient("127.0.0.1", srv.port,
                               tenant_streams=True).connect()
            cli.send(edge_payload([1], [2]), tenant=5)
            cli.send(edge_payload([1], [2]), tenant=6)
            cli.flush()
            srv.shed_tenant(5, reason="overload")
            assert _wait(lambda: 5 in cli.shed_tenants)
            assert cli.shed_tenants[5] == "overload"
            with pytest.raises(IngestError, match="shed"):
                cli.send(edge_payload([3], [4]), tenant=5)
            # The OTHER tenant's stream is untouched.
            cli.send(edge_payload([3], [4]), tenant=6)
            cli.flush()
            assert cli.acked_for(6) == 2
            cli.close(flush_timeout=None)
            # A late frame for the shed tenant (a client that never
            # heard the NACK) is refused with a typed NACK carrying the
            # durable position — raw socket, so the frame really
            # arrives.
            raw = socket.create_connection(("127.0.0.1", srv.port))
            raw.settimeout(5)
            try:
                raw.sendall(wire.pack_frame(wire.HELLO, 0))
                ftype, _seq, wbody = _read_frame(raw)
                assert ftype == wire.WELCOME
                info = wire.unpack_json(wbody)
                assert info["shed_tenants"] == [5]
                p = edge_payload([9], [10])
                p["tenant"] = np.asarray([5], dtype=np.int64)
                raw.sendall(wire.pack_frame(
                    wire.DATA, 1, wire.pack_payload(p)))
                ftype, seq, body = _read_frame(raw)
                assert ftype == wire.NACK
                # The NACK's seq is the DURABLE position — auto_ack
                # acks are not durability claims, so it stays 0 here.
                assert seq == 0
                env = wire.unpack_json(body)
                assert env == {"reason": "overload", "tenant": 5}
            finally:
                raw.close()
        counters = bus.snapshot()["counters"]
        assert counters.get("ingest.nacks_sent", 0) >= 2
        assert counters.get("ingest.nacks_received") == 1
        assert counters.get("ingest.frames_shed") == 1


# --------------------------------------------------------------------- #
# tenant-mode CRC resync


def test_tenant_mode_crc_corruption_resyncs_and_completes(
        await_tenant_windows):
    """A corrupt frame in tenant_streams mode cannot name its stream
    (the tenant id lives in the unverifiable payload): the server asks
    for a full resync and the client retransmits every unacked frame —
    duplicates drop, the stream completes, labels bit-identical."""
    agg, cap = cc_tenant_tier(N_V, chunk_capacity=16)
    edges = np.random.default_rng(41).integers(0, N_V, (64, 2))
    with obs_bus.scope() as bus:
        eng = MultiTenantEngine(merge_every=1).start()
        router = TenantRouter(eng, "small", vertex_capacity=N_V)
        eng.add_tier("small", agg, cap)
        srv = IngestServer(tenant_streams=True).start()
        router.attach(srv)
        cli = IngestClient("127.0.0.1", srv.port,
                           tenant_streams=True).connect()
        try:
            orig = cli._raw_send
            left = [1]

            def corrupting(frame):
                if left[0] and len(frame) > 200:  # only DATA is this big
                    left[0] -= 1
                    frame = frame[:-1] + bytes([frame[-1] ^ 0xFF])
                orig(frame)

            cli._raw_send = corrupting
            for i in range(0, 64, 16):
                cli.send(edge_payload(edges[i:i + 16, 0],
                                      edges[i:i + 16, 1]), tenant=3)
            cli.flush(timeout=30)
            assert left[0] == 0  # the corruption really happened
            await_tenant_windows(eng, (3,), 4)
            eng.finish(3)
            got = eng.labels(3)
        finally:
            cli.close(flush_timeout=None)
            eng.stop()
            srv.stop()
            router.stop()
        counters = bus.snapshot()["counters"]
        assert counters.get("ingest.frames_rejected", 0) >= 1
        assert counters.get("ingest.frames_resent", 0) >= 1
    st = edge_stream_from_edges(
        [(int(a), int(b)) for a, b in edges], vertex_capacity=N_V,
        chunk_size=16, table=IdentityVertexTable(N_V),
    )
    want = np.asarray(st.aggregate(agg, merge_every=1).result())
    assert got.tobytes() == want.tobytes()


# --------------------------------------------------------------------- #
# checkpoint-gated per-tenant acks


def test_checkpoint_gated_acks_flow_through_router(tmp_path):
    """auto_ack=False + checkpoint_acks=True: a tenant's wire ACK fires
    only from the engine's on_durable hook after its CheckpointManager
    rotation — flush() completing IS the durability proof."""
    agg, cap = cc_tenant_tier(N_V, chunk_capacity=16)
    edges = {
        t: np.random.default_rng(50 + t).integers(0, N_V, (64, 2))
        for t in (1, 2)
    }
    eng = MultiTenantEngine(
        merge_every=1, checkpoint_dir=str(tmp_path), checkpoint_every=1,
    ).start()
    router = TenantRouter(eng, "small", vertex_capacity=N_V,
                          checkpoint_acks=True)
    eng.add_tier("small", agg, cap)
    srv = IngestServer(tenant_streams=True, auto_ack=False).start()
    router.attach(srv)
    cli = IngestClient("127.0.0.1", srv.port,
                       tenant_streams=True).connect()
    try:
        for t in (1, 2):
            for i in range(0, 64, 16):
                cli.send(edge_payload(edges[t][i:i + 16, 0],
                                      edges[t][i:i + 16, 1]), tenant=t)
        cli.flush(timeout=60)  # completes only via checkpoint-gated acks
        for t in (1, 2):
            assert cli.acked_for(t) == 4
            assert eng.position(t) == 4
            assert list(tmp_path.glob(f"t{t}-*.npz"))
        assert cli.unacked_count == 0
    finally:
        cli.close(flush_timeout=None)
        eng.stop()
        srv.stop()
        router.stop()


# --------------------------------------------------------------------- #
# STACKED frames in tenant mode


def test_stacks_are_tenant_scoped_on_the_wire():
    """A coalescing client keys its stack buffers by tenant, so every
    STACKED frame that reaches the server is single-tenant-scoped and
    rides that tenant's OWN sequence space — interleaved sends to two
    tenants never share a frame."""
    with obs_bus.scope() as bus:
        with IngestServer(tenant_streams=True) as srv:
            out = []
            _drain_frames(srv, out)
            cli = IngestClient("127.0.0.1", srv.port,
                               tenant_streams=True, stack=3).connect()
            try:
                for i in range(4):
                    cli.send(edge_payload([i], [i + 1]), tenant=7)
                    if i < 2:
                        cli.send(edge_payload([i], [i + 2]), tenant=9)
                cli.flush()
                # Frame-granular acks still land per tenant space.
                assert cli.acked_for(7) == 4
                assert cli.acked_for(9) == 2
                assert _wait(lambda: len(out) == 6)
                seqs = {
                    (int(np.asarray(p["tenant"]).reshape(-1)[0]), s)
                    for s, p, _ in out
                }
                assert seqs == {(7, 0), (7, 1), (7, 2), (7, 3),
                                (9, 0), (9, 1)}
            finally:
                cli.close(flush_timeout=None)
        counters = bus.snapshot()["counters"]
        # t7: one full stack [0,3) + a K=1 tail (legacy DATA frame);
        # t9: one K=2 tail stack. Stacks never straddled tenants.
        assert counters.get("ingest.frames_stacked") == 2
        assert counters.get("ingest.chunks_unroutable", 0) == 0


def test_mixed_tenant_stack_refused_whole():
    """A hand-crafted stack that straddles tenant ids (or omits one)
    has no single sequence space to land in: the server refuses it
    WHOLE — no partial admission, no seq advance — and counts
    ``chunks_unroutable``. A clean stack then lands at the untouched
    position."""
    def tp(t, v):
        p = edge_payload([v], [v + 1])
        if t is not None:
            p["tenant"] = np.asarray([t], dtype=np.int64)
        return p

    def stack(*payloads):
        return wire.pack_stacked(
            [(wire.pack_payload(p), False) for p in payloads])

    with obs_bus.scope() as bus:
        with IngestServer(tenant_streams=True) as srv:
            out = []
            _drain_frames(srv, out)
            raw = socket.create_connection(("127.0.0.1", srv.port))
            raw.settimeout(5)
            try:
                raw.sendall(wire.pack_frame(wire.HELLO, 0))
                assert _read_frame(raw)[0] == wire.WELCOME
                # Straddling stack: tenants 3 and 4 in one frame.
                raw.sendall(wire.pack_frame(
                    wire.STACKED, 0, stack(tp(3, 1), tp(4, 2))))
                # Tenant-less stack: no sequence space at all.
                raw.sendall(wire.pack_frame(
                    wire.STACKED, 0, stack(tp(None, 3), tp(None, 4))))
                # Both were dropped whole — seq 0 is untouched, so a
                # clean single-tenant stack lands there and is acked at
                # frame granularity.
                raw.sendall(wire.pack_frame(
                    wire.STACKED, 0, stack(tp(3, 5), tp(3, 6))))
                ftype, seq, body = _read_frame(raw)
                assert ftype == wire.ACK
                assert seq == 2
                assert wire.unpack_json(body) == {"tenant": 3}
            finally:
                raw.close()
            assert _wait(lambda: len(out) == 2)
            assert all(
                int(np.asarray(p["tenant"]).reshape(-1)[0]) == 3
                for _s, p, _c in out
            )
        counters = bus.snapshot()["counters"]
        assert counters.get("ingest.chunks_unroutable") == 2
        assert counters.get("ingest.frames_stacked") == 1


def test_stacked_tenant_stream_folds_bit_identical_through_router(
        await_tenant_windows):
    """Whole stacks ride the TenantRouter as one drain unit each and
    the folded labels are bit-identical to the in-process engine run —
    stacking is invisible to the tenant fold."""
    agg, cap = cc_tenant_tier(N_V, chunk_capacity=16)
    edges = np.random.default_rng(77).integers(0, N_V, (96, 2))
    with obs_bus.scope() as bus:
        eng = MultiTenantEngine(merge_every=1).start()
        router = TenantRouter(eng, "small", vertex_capacity=N_V)
        eng.add_tier("small", agg, cap)
        srv = IngestServer(tenant_streams=True).start()
        router.attach(srv)
        cli = IngestClient("127.0.0.1", srv.port, tenant_streams=True,
                           stack=3).connect()
        try:
            for i in range(0, 96, 16):
                cli.send(edge_payload(edges[i:i + 16, 0],
                                      edges[i:i + 16, 1]), tenant=5)
            cli.flush(timeout=30)
            await_tenant_windows(eng, (5,), 6)
            eng.finish(5)
            got = eng.labels(5)
        finally:
            cli.close(flush_timeout=None)
            eng.stop()
            srv.stop()
            router.stop()
        counters = bus.snapshot()["counters"]
        # 6 chunks coalesced into two stacks of 3 — two frames, two
        # router drain units, zero rejects.
        assert counters.get("ingest.frames_stacked") == 2
        assert counters.get("ingest.chunks_enqueued") == 6
        assert counters.get("ingest.frames_rejected", 0) == 0
    st = edge_stream_from_edges(
        [(int(a), int(b)) for a, b in edges], vertex_capacity=N_V,
        chunk_size=16, table=IdentityVertexTable(N_V),
    )
    want = np.asarray(st.aggregate(agg, merge_every=1).result())
    assert got.tobytes() == want.tobytes()


# --------------------------------------------------------------------- #
# SIGKILL: the multi-tenant exactly-once wire


def _spawn_child(ckpt, port_file, out, total, framing="plain"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.Popen(
        [sys.executable, CHILD, str(ckpt), str(port_file), str(out),
         str(total), framing],
        env=env,
    )


def _wait_port(port_file, proc, timeout=120):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise AssertionError(
                f"server child exited rc={proc.returncode} before "
                "publishing its port"
            )
        if os.path.exists(port_file):
            return int(open(port_file).read())
        time.sleep(0.02)
    raise AssertionError("server child never published its port")


@pytest.mark.slow
@pytest.mark.faults
@pytest.mark.tenants
@pytest.mark.parametrize("stack", [1, 3])
def test_sigkilled_multitenant_server_resumes_exactly_once(
        tmp_path, stack):
    """Three tenants, distinct seq spaces, one tenant_streams server
    with checkpoint-gated acks, SIGKILLed mid-stream: the restarted
    incarnation re-welcomes every tenant at its durable position and
    final degree vectors (non-idempotent counters) are bit-identical to
    an uninterrupted in-process run.

    ``stack=3`` reruns it with a coalescing client: per-tenant stacks,
    checkpoint-gated acks landing MID-frame, and covering-frame
    redelivery across the restart — stacking must be invisible to the
    multi-tenant exactly-once contract."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _qos_crash_child as child

    from gelly_tpu.library.degrees import degree_aggregate

    TIDS = (0, 1, 2)
    total = 10
    edges = {
        t: np.random.default_rng(300 + t).integers(
            0, child.N_V, (total * child.CHUNK, 2))
        for t in TIDS
    }

    def mk(t, i):
        lo, hi = i * child.CHUNK, (i + 1) * child.CHUNK
        return edge_payload(edges[t][lo:hi, 0], edges[t][lo:hi, 1])

    # Golden: the same chunks through the same engine, in-process,
    # uninterrupted (degrees are additive, so cadence is immaterial —
    # but a double- or dropped-fold changes the counts).
    agg = degree_aggregate(child.N_V, ingest_combine=False)
    geng = MultiTenantEngine(merge_every=2)
    geng.add_tier("deg", agg, child.CHUNK)
    for t in TIDS:
        geng.admit(t, "deg")
    for i in range(total):
        for t in TIDS:
            geng.submit(t, payload_to_chunk(mk(t, i), child.CHUNK,
                                            child.N_V))
    for t in TIDS:
        geng.finish(t)
    golden = {t: np.asarray(v) for t, v in geng.drain().items()}

    framing = "stacked" if stack > 1 else "plain"
    ckpt = tmp_path / "ckpt"
    port_file = str(tmp_path / "port")
    out = str(tmp_path / "final.npz")
    p1 = _spawn_child(ckpt, port_file, out, total, framing)
    port = _wait_port(port_file, p1)
    cli = IngestClient("127.0.0.1", port, tenant_streams=True,
                       send_pause_timeout=60, stack=stack)
    cli.connect()

    def sender():
        try:
            for i in range(total):
                for t in TIDS:
                    cli.send(mk(t, i), tenant=t)
                time.sleep(0.03)
        except IngestError:
            return  # server died mid-send; the suffix resends below

    st = threading.Thread(target=sender, daemon=True)
    st.start()

    # Kill once every tenant has a durable checkpoint and acks flowed.
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        if (all(list(ckpt.glob(f"t{t}-*.npz")) for t in TIDS)
                and all(cli.acked_for(t) >= 2 for t in TIDS)):
            break
        time.sleep(0.02)
    else:
        pytest.fail("no per-tenant checkpoints/acks before the deadline")
    acked_before = {t: cli.acked_for(t) for t in TIDS}
    os.kill(p1.pid, signal.SIGKILL)
    assert p1.wait(timeout=60) == -signal.SIGKILL
    assert not os.path.exists(out)  # died mid-stream
    st.join(timeout=60)

    # The client's per-stream counters are the authoritative record of
    # what was buffered (a send that died mid-call still buffered its
    # frame); everything buffered replays on reconnect, everything
    # beyond it is re-sent below.
    with cli._lock:
        buffered = {t: cli._next.get(t, 0) for t in TIDS}

    os.unlink(port_file)
    p2 = _spawn_child(ckpt, port_file, out, total, framing)
    cli.port = _wait_port(port_file, p2)
    deadline = time.monotonic() + 60
    while True:
        try:
            cli.reconnect()
            break
        except OSError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.05)
    for t in TIDS:  # acked work never rewinds
        assert cli.acked_for(t) >= acked_before[t]
    for t in TIDS:
        for i in range(buffered[t], total):
            cli.send(mk(t, i), tenant=t)
    cli.flush(timeout=180)
    cli.close()
    assert p2.wait(timeout=300) == 0

    final, pos, _ = load_checkpoint(
        out, like=[np.zeros_like(golden[t]) for t in TIDS])
    assert pos == total * len(TIDS)
    for t in TIDS:
        assert final[t].dtype == golden[t].dtype
        assert final[t].tobytes() == golden[t].tobytes()
