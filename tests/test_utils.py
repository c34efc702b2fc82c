"""Native parser, metrics, and prefetch utilities."""

import os

import numpy as np
import pytest

from gelly_tpu.utils.metrics import StageTimer, ThroughputMeter
from gelly_tpu.utils.prefetch import prefetch


def test_prefetch_order_and_completion():
    assert list(prefetch(iter(range(100)), depth=3)) == list(range(100))
    assert list(prefetch(iter([]), depth=2)) == []
    assert list(prefetch(iter([1]), depth=0)) == [1]


def test_prefetch_propagates_exceptions():
    def gen():
        yield 1
        raise RuntimeError("boom")

    it = prefetch(gen(), depth=2)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="boom"):
        list(it)


def test_stage_timer_and_meter():
    t = StageTimer()
    with t("fold"):
        pass
    with t("fold"):
        pass
    rep = t.report()
    assert rep["fold"]["calls"] == 2
    m = ThroughputMeter()
    m.record(100)
    m.record(200)
    assert m.edges == 300


def test_stage_timer_reattribute():
    t = StageTimer()
    t.totals["ingest_compress"] = 2.0
    t.reattribute("ingest_compress", "codec_wait", 0.5)
    assert t.busy() == {"ingest_compress": 1.5, "codec_wait": 0.5}
    # Over-reattribution clamps src at zero (the wait is measured
    # independently of the stage clock, so rounding can exceed it).
    t.reattribute("ingest_compress", "codec_wait", 99.0)
    b = t.busy()
    assert b["ingest_compress"] == 0.0
    assert b["codec_wait"] == 99.5
    # Zero seconds still books the dst row: artifacts distinguish "no
    # wait" from "accounting not active". Negative is treated as zero.
    t2 = StageTimer()
    t2.reattribute("ingest_compress", "codec_wait", 0.0)
    t2.reattribute("ingest_compress", "codec_wait", -1.0)
    assert t2.busy() == {"ingest_compress": 0.0, "codec_wait": 0.0}
    assert t2.counts["codec_wait"] == 2


def test_throughput_meter_single_record_has_rate():
    # A single record() used to leave elapsed == 0 and report 0.0
    # edges/sec despite nonzero edges (ISSUE 5 satellite): the meter now
    # falls back to time-since-meter-creation for the one-sample case.
    import time as _t

    m = ThroughputMeter()
    _t.sleep(0.02)
    m.record(1000)
    assert m.edges == 1000
    assert m.elapsed >= 0.02
    assert m.edges_per_sec > 0.0
    snap = m.snapshot()
    assert snap["edges"] == 1000
    assert snap["edges_per_sec"] == round(m.edges_per_sec, 1) > 0
    assert snap["elapsed_s"] > 0


def test_throughput_meter_empty_and_multi_sample():
    m = ThroughputMeter()
    assert m.elapsed == 0.0 and m.edges_per_sec == 0.0  # no samples: no rate
    import time as _t

    m.record(100)
    _t.sleep(0.01)
    m.record(200)
    # Two samples: the ordinary first-to-last span, not the fallback.
    assert 0.01 <= m.elapsed < 10.0
    assert m.edges == 300


def test_throughput_meter_publishes_gauges():
    from gelly_tpu.obs import EventBus

    bus = EventBus()
    m = ThroughputMeter()
    m.record(50)
    m.publish(bus, prefix="t")
    snap = bus.snapshot()["gauges"]
    assert snap["t.edges"] == 50
    assert snap["t.edges_per_sec"] > 0


def test_trace_is_exception_safe(tmp_path, monkeypatch):
    # A body that raises must propagate ITS exception (never a masked
    # stop_trace error) and must always stop the started trace — no
    # dangling profiler session. The profiler is stubbed (a real CPU
    # start/stop cycle costs ~10s and tests nothing extra about OUR
    # wrapper); the real-profiler integration runs once in
    # test_trace_records_alignment_instants.
    import jax

    from gelly_tpu.utils.metrics import trace

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    with pytest.raises(RuntimeError, match="boom"):
        with trace(str(tmp_path / "t1")):
            raise RuntimeError("boom")
    assert calls == [("start", str(tmp_path / "t1")), ("stop",)]

    # A stop that itself fails must not MASK the body's exception.
    def bad_stop():
        calls.append(("stop",))
        raise ValueError("profiler stop failed")

    monkeypatch.setattr(jax.profiler, "stop_trace", bad_stop)
    with pytest.raises(RuntimeError, match="body error"):
        with trace(str(tmp_path / "t2")):
            raise RuntimeError("body error")
    assert calls[-1] == ("stop",)


def test_trace_noops_when_profiler_unavailable(tmp_path, monkeypatch):
    import jax

    from gelly_tpu.utils.metrics import trace

    def broken_start(log_dir):
        raise RuntimeError("profiler unavailable on this platform")

    monkeypatch.setattr(jax.profiler, "start_trace", broken_start)
    ran = []
    with trace(str(tmp_path / "t")):
        ran.append(1)  # body still runs; no exception escapes
    assert ran == [1]


def test_trace_records_alignment_instants(tmp_path, monkeypatch):
    import jax

    from gelly_tpu.obs import SpanTracer
    from gelly_tpu.utils.metrics import trace

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    tr = SpanTracer()
    with trace(str(tmp_path / "t"), tracer=tr):
        pass
    names = [i["name"] for i in tr.instants()]
    assert names == ["jax_profiler_start", "jax_profiler_stop"]
    start = tr.instants("jax_profiler_start")[0]
    assert start["args"]["trace_id"] == tr.trace_id


@pytest.mark.slow  # real jax.profiler start/stop costs ~10s on CPU; the
# CI obs lane runs it, tier-1 relies on the stubbed wrapper tests above
def test_trace_real_profiler_roundtrip(tmp_path):
    from gelly_tpu.utils.metrics import trace

    with trace(str(tmp_path / "t1")):
        pass
    # No dangling session: a second trace starts cleanly.
    with pytest.raises(RuntimeError, match="boom"):
        with trace(str(tmp_path / "t2")):
            raise RuntimeError("boom")
    with trace(str(tmp_path / "t3")):
        pass


def test_overlap_stats_edge_cases():
    from gelly_tpu.utils.metrics import overlap_stats

    # Zero-wall window with busy stages: efficiency 0.0, never a crash.
    out = overlap_stats({"a": 1.0, "b": 2.0}, total_wall=0.0)
    assert out["overlap_efficiency"] == 0.0
    assert out["stage_busy_max_s"] == 2.0
    assert out["serial_stage_sum_s"] == 3.0
    # No stages at all (or all excluded): efficiency is None, sums zero.
    out = overlap_stats({}, total_wall=1.0)
    assert out["overlap_efficiency"] is None
    assert out["serial_stage_sum_s"] == 0.0
    out = overlap_stats({"total_wall": 5.0}, total_wall=5.0)
    assert out["overlap_efficiency"] is None  # excluded by default
    # Zero-busy stages: max 0 -> None efficiency (no divide).
    out = overlap_stats({"a": 0.0}, total_wall=0.0)
    assert out["overlap_efficiency"] is None


def test_stage_timer_reattribute_unknown_source():
    # Reattributing from a stage that never ran books the dst row and
    # leaves the (implicitly zero) src clamped at zero — artifacts show
    # the accounting was active even when the source stage is absent.
    t = StageTimer()
    t.reattribute("never_ran", "codec_wait", 1.5)
    b = t.busy()
    assert b["never_ran"] == 0.0
    assert b["codec_wait"] == 1.5
    assert t.counts["codec_wait"] == 1


def test_stage_timer_publish_gauges():
    from gelly_tpu.obs import EventBus

    bus = EventBus()
    t = StageTimer()
    t.totals["fold_dispatch"] = 1.25
    t.publish(bus)
    assert bus.snapshot()["gauges"]["stage.fold_dispatch.busy_s"] == 1.25


def _native_available():
    try:
        from gelly_tpu.utils.native import _load

        _load()
        return True
    except Exception:
        return False


@pytest.mark.skipif(not _native_available(), reason="no native toolchain")
def test_native_parser_matches_python(tmp_path):
    from gelly_tpu.core.io import parse_edge_list_text
    from gelly_tpu.utils.native import parse_edge_list_file

    p = tmp_path / "edges.txt"
    p.write_text(
        "% header\n1 2\n3\t4 9.5\n# comment\n  5 6\n\n-7 8\n"
        "9000000000 9000000001\n"
    )
    ns, nd = parse_edge_list_file(str(p))
    ps, pd, _ = parse_edge_list_text(p.read_text())
    np.testing.assert_array_equal(ns, ps)
    np.testing.assert_array_equal(nd, pd)
    # valued path
    ns2, nd2, nv = parse_edge_list_file(str(p), want_vals=True)
    assert nv[1] == 9.5 and nv[0] == 1.0


@pytest.mark.skipif(not _native_available(), reason="no native toolchain")
def test_native_parser_feeds_stream(tmp_path):
    from gelly_tpu import edge_stream_from_file

    p = tmp_path / "edges.txt"
    p.write_text("1 2\n2 3\n3 1\n")
    s = edge_stream_from_file(str(p), vertex_capacity=16, chunk_size=2)
    assert sorted((a, b) for a, b, _ in s.collect_edges()) == [
        (1, 2), (2, 3), (3, 1)
    ]


def test_aggregation_with_prefetch_matches(reference_edges):
    from gelly_tpu import edge_stream_from_edges
    from gelly_tpu.library.connected_components import (
        connected_components, labels_to_components,
    )

    edges = [(a, b) for a, b, _ in reference_edges] + [(6, 7), (8, 9)]
    expected = [[1, 2, 3, 4, 5], [6, 7], [8, 9]]
    for depth in (0, 3):
        s = edge_stream_from_edges(edges, vertex_capacity=32, chunk_size=2)
        agg = connected_components(32)
        labels = s.aggregate(agg, merge_every=2, prefetch_depth=depth).result()
        assert labels_to_components(labels, s.ctx) == expected, depth


@pytest.mark.skipif(not _native_available(), reason="no native toolchain")
def test_native_parser_float_grammar_and_garbage(tmp_path):
    from gelly_tpu.core.io import parse_edge_list_text
    from gelly_tpu.utils.native import parse_edge_list_file

    p = tmp_path / "edges.txt"
    p.write_text("1 2 1e3\n3 4 .5\n5 6 -0.25\n7 8x\n9 10 2.5e-2\n11 12\n")
    ns, nd, nv = parse_edge_list_file(str(p), want_vals=True)
    ps, pd, pv = parse_edge_list_text(p.read_text(), num_value_cols=1)
    np.testing.assert_array_equal(ns, ps)
    np.testing.assert_array_equal(nd, pd)
    np.testing.assert_allclose(nv, pv)
    assert nv.tolist() == [1000.0, 0.5, -0.25, 0.025, 1.0]


def test_prefetch_early_abandon_unblocks_worker():
    import threading
    import time as _t

    produced = []

    def gen():
        for i in range(1000):
            produced.append(i)
            yield i

    before = threading.active_count()
    it = prefetch(gen(), depth=2)
    assert next(it) == 0
    it.close()  # abandon mid-stream
    _t.sleep(0.4)  # worker should notice the cancel and exit
    assert threading.active_count() <= before + 1
    assert len(produced) < 20  # source was not fully drained


def test_native_parser_overflow_reads_as_malformed(tmp_path):
    # An id wider than int64 must be skipped like any malformed line (the
    # python parser raises/skips), never silently wrapped to a wrong id.
    from gelly_tpu.utils.native import parse_edge_list_file

    p = tmp_path / "ovf.txt"
    p.write_text(
        "1 2\n"
        "99999999999999999999999999 3\n"
        "4 170141183460469231731687303715884105727\n"
        "9223372036854775807 6\n"
        "-9223372036854775808 7\n"
        "-9223372036854775809 8\n"
    )
    src, dst = parse_edge_list_file(str(p))
    assert list(zip(src.tolist(), dst.tolist())) == [
        (1, 2),
        (9223372036854775807, 6),  # INT64_MAX parses
        (-9223372036854775808, 7),  # INT64_MIN parses (one past MAX)
    ]


def test_prefetch_preserves_worker_traceback():
    # The consumer-side re-raise must carry the SOURCE frame that failed,
    # not just the prefetch internals (satellite of the resilience PR).
    def gen():
        yield 1
        boom_line_marker = 1 / 0  # noqa: F841

    it = prefetch(gen(), depth=2)
    assert next(it) == 1
    import traceback

    try:
        next(it)
    except ZeroDivisionError as e:
        frames = traceback.extract_tb(e.__traceback__)
        assert any("boom_line_marker" in (f.line or "") for f in frames)
    else:
        raise AssertionError("expected ZeroDivisionError")


def test_prefetch_error_while_queue_full():
    # The source raises while the bounded queue is full and the consumer is
    # slow: the error wrapper must still get through (polling put), and the
    # already-queued items must be delivered first (order preserved).
    import time

    def gen():
        yield from range(4)
        raise RuntimeError("late failure")

    it = prefetch(gen(), depth=1)
    got = []
    time.sleep(0.3)  # let the worker fill the queue and hit the error path
    with pytest.raises(RuntimeError, match="late failure"):
        for x in it:
            got.append(x)
            time.sleep(0.05)  # keep the queue full behind us
    assert got == [0, 1, 2, 3]


def test_prefetch_cancel_while_queue_full():
    # Abandon the consumer while the queue is full; the worker must notice
    # the cancel and exit instead of blocking forever on its put.
    import threading
    import time

    def workers():
        # Only OUR named worker threads: asserting on the global
        # active_count() would flake when an unrelated runtime thread
        # (jax backend, another test's abandoned daemon) appears.
        return [t for t in threading.enumerate()
                if t.name.startswith("gelly-prefetch") and t.is_alive()]

    before = set(workers())
    pulled = []

    def gen():
        for i in range(10_000):
            pulled.append(i)
            yield i

    it = prefetch(gen(), depth=2)
    assert next(it) == 0
    it.close()  # GeneratorExit -> finally -> cancel.set()
    deadline = time.monotonic() + 5.0
    while (set(workers()) - before) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not (set(workers()) - before)
    assert len(pulled) < 100  # worker stopped pulling from the source


def test_prefetch_map_cancel_while_queue_full():
    # A consumer that stops iterating early (explicit close) while the
    # bounded queue is FULL: the submitter must unblock from its parked
    # put and exit, queued-but-unstarted futures must be cancelled (their
    # fn never runs), and the worker pool must wind down — no thread
    # parked forever holding `depth` staged payloads.
    import threading
    import time

    from gelly_tpu.utils.prefetch import prefetch_map

    def submitters():
        return [t for t in threading.enumerate()
                if t.name.startswith("gelly-prefetch-submit")
                and t.is_alive()]

    before = set(submitters())
    pulled = []
    ran = []

    def src():
        for i in range(10_000):
            pulled.append(i)
            yield i

    def fn(x):
        ran.append(x)
        return x * 2

    it = prefetch_map(fn, src(), depth=2, workers=2)
    assert next(it) == 0
    time.sleep(0.3)  # let the submitter fill the queue and park on put
    it.close()  # GeneratorExit -> finally -> cancel + drain + shutdown
    deadline = time.monotonic() + 5.0
    while (set(submitters()) - before) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not (set(submitters()) - before)  # submitter exited
    n_after_close = len(ran)
    time.sleep(0.3)
    # Cancelled futures never run their fn after the close.
    assert len(ran) == n_after_close
    assert len(pulled) < 100  # source was not drained


def test_prefetch_map_external_cancel_unblocks_parked_consumer():
    # A generator can only be close()d between items, so when ANOTHER
    # thread (the executor's H2D leg) is parked inside __next__ waiting
    # on a stalled source, nothing can deliver GeneratorExit to it.
    # Setting the external cancel event must end the parked get within
    # one poll — the stream terminates, the submitter exits, and the
    # stalled source is never pulled again.
    import threading
    import time

    from gelly_tpu.utils.prefetch import prefetch_map

    release = threading.Event()
    cancel = threading.Event()
    pulled = []

    def src():
        pulled.append(0)
        yield 0
        release.wait(10)  # a source stuck on I/O
        for i in range(1, 100):
            pulled.append(i)
            yield i

    it = prefetch_map(lambda x: x * 2, src(), depth=2, workers=1,
                      cancel=cancel)
    got = []

    def consume():
        got.extend(it)  # parks in __next__ on the stalled source

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    deadline = time.monotonic() + 5.0
    while not got and time.monotonic() < deadline:
        time.sleep(0.01)
    assert got == [0]  # consumer is now parked waiting for item 1
    cancel.set()
    t.join(2.0)
    assert not t.is_alive()  # the parked get noticed the event
    assert got == [0]
    release.set()
    time.sleep(0.3)
    # The submitter finishes at most the one pull it was already parked
    # on, then notices the cancel — the source is never drained.
    assert len(pulled) <= 2


def test_prefetch_map_external_cancel_with_fast_source():
    # The cancel event must end the stream even when the source is FAST:
    # the queue is then never empty, so a cancel check only on the
    # empty-queue path would never run and the generator would keep
    # yielding until exhaustion — the documented "setting the event ends
    # the stream" contract requires a per-iteration check.
    import itertools
    import threading

    from gelly_tpu.utils.prefetch import prefetch_map

    cancel = threading.Event()
    it = prefetch_map(lambda x: x, itertools.count(), depth=4, workers=1,
                      cancel=cancel)
    got = []
    for v in it:
        got.append(v)
        if len(got) == 10:
            cancel.set()  # same-thread set: next pull must terminate
    assert got == list(range(10))


def test_prefetch_map_error_while_queue_full():
    import time

    from gelly_tpu.utils.prefetch import prefetch_map

    def src():
        yield from range(4)
        raise RuntimeError("submitter failure")

    it = prefetch_map(lambda x: x * 2, src(), depth=1, workers=2)
    got = []
    time.sleep(0.3)
    with pytest.raises(RuntimeError, match="submitter failure"):
        for x in it:
            got.append(x)
            time.sleep(0.05)
    assert got == [0, 2, 4, 6]


def test_restartable_prefetch_reopens_at_next_undelivered():
    from gelly_tpu.utils.prefetch import restartable_prefetch

    opens = []
    fail_once = {"armed": True}

    def make_iter(pos):
        opens.append(pos)

        def gen():
            for i in range(pos, 10):
                if i == 6 and fail_once["armed"]:
                    fail_once["armed"] = False
                    raise OSError("flaky source")
                yield i

        return gen()

    out = list(restartable_prefetch(make_iter, depth=3,
                                    should_restart=lambda e: True))
    assert out == list(range(10))  # exactly once each
    assert opens[0] == 0 and len(opens) == 2
    # The restart reopened at the next UNDELIVERED index — nothing lost
    # even though the queue held prefetched items when the worker died.
    assert opens[1] <= 6


def test_restartable_prefetch_bounded_restarts():
    from gelly_tpu.utils.prefetch import restartable_prefetch

    def make_iter(pos):
        def gen():
            yield pos
            raise OSError("always down")

        return gen()

    it = restartable_prefetch(make_iter, depth=1, max_restarts=3,
                              should_restart=lambda e: True)
    with pytest.raises(OSError, match="always down"):
        list(it)


def test_restartable_prefetch_respects_should_restart():
    from gelly_tpu.utils.prefetch import restartable_prefetch

    def make_iter(pos):
        def gen():
            yield from range(pos, 3)
            raise ValueError("permanent")

        return gen()

    it = restartable_prefetch(make_iter, depth=1,
                              should_restart=lambda e: False)
    with pytest.raises(ValueError, match="permanent"):
        list(it)


@pytest.mark.racecheck
def test_stage_timer_report_concurrent_with_new_stages():
    """Regression (racecheck RC003 class): report() used to iterate the
    LIVE totals dict — a prefetch worker booking its first sample into a
    NEW stage mid-report raised "dictionary changed size during
    iteration". The snapshot-under-lock fix must survive a hammering."""
    import threading

    timer = StageTimer()
    stop = threading.Event()
    errs = []

    def worker(wid):
        i = 0
        try:
            while not stop.is_set():
                # i cycles so the stage set keeps gaining NEW names (the
                # mid-iteration insert the bug needs) without growing
                # unboundedly — report() stays O(stages) per call.
                with timer(f"stage-{wid}-{i % 64}"):
                    pass
                i += 1
        except BaseException as e:  # pragma: no cover - the regression
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            rep = timer.report()
            for row in rep.values():
                assert row["calls"] >= 1  # totals/counts never skewed
            timer.busy()
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert errs == []


def test_native_library_is_keyed_by_its_source(tmp_path, monkeypatch):
    # The loaded .so is always built from the .cc on disk: an edit gives
    # a new library name, and a stale binary under the old name (say,
    # copied in with the tree) is never the one that loads.
    from gelly_tpu.utils import native

    src = os.path.join(native._NATIVE_DIR, "matching.cc")
    with open(src) as f:
        text = f.read()
    (tmp_path / "matching.cc").write_text(text)
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_libs", {})
    _, _, stale = native._lib_path("matching", "")
    (tmp_path / "matching.cc").write_text(text + "\n// edited\n")
    _, _, fresh = native._lib_path("matching", "")
    assert stale != fresh
    with open(stale, "wb") as f:
        f.write(b"not a shared object")
    native._load_lib("matching")
    assert os.path.exists(fresh)
