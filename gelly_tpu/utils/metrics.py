"""Observability: throughput meters, stage timers, profiler hook.

The reference has none of this in-repo (SURVEY.md §5: only a
``getNetRuntime()`` printout, ``CentralizedWeightedMatching.java:62-64``;
Flink's web UI is never referenced) — the TPU framework owns it instead:

- :class:`StageTimer` — named accumulated wall-clock per pipeline stage,
  each stage also a ``gelly.<stage>`` host span on the profiler's clock;
- :class:`ThroughputMeter` — edges/sec over a window of samples;
- :func:`trace` — context manager around ``jax.profiler`` for device traces.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from jax.profiler import TraceAnnotation


class StageTimer:
    """Accumulates wall-clock per named stage: ``with timer("fold"): ...``

    Thread-safe: ingest stages are timed concurrently from prefetch worker
    threads while the consumer times fold/merge, so the read-modify-write
    accumulation takes a lock.

    Every stage entry is also a ``gelly.<stage>`` host annotation
    (``jax.profiler.TraceAnnotation``): under a profiler trace the stage
    shows beside the device events on the trace's own clock, on the
    thread that ran it; with no trace running it costs one inactive
    TraceMe.
    """

    def __init__(self):
        import threading

        self.totals: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def __call__(self, stage: str):
        with TraceAnnotation("gelly." + stage):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                with self._lock:
                    self.totals[stage] += dt
                    self.counts[stage] += 1

    def report(self) -> dict[str, dict[str, float]]:
        # Snapshot under the lock before building the report: iterating
        # the live dicts while a prefetch worker books its first sample
        # into a NEW stage raises "dictionary changed size during
        # iteration" mid-report (the SpanTracer bug class, racecheck
        # RC003) — and a stage added between reading totals and counts
        # would divide by a missing count.
        with self._lock:
            totals = dict(self.totals)
            counts = dict(self.counts)
        return {
            s: {
                "total_s": round(totals[s], 6),
                "calls": counts[s],
                "mean_ms": round(1e3 * totals[s] / counts[s], 3),
            }
            for s in totals
        }

    def busy(self) -> dict[str, float]:
        """Per-stage BUSY seconds (time inside the stage's context, summed
        across whichever threads ran it). Stages of a pipelined executor
        overlap, so these are NOT additive along the wall clock — compare
        them to total wall via :func:`overlap_stats`."""
        with self._lock:
            return {s: round(t, 6) for s, t in self.totals.items()}

    def publish(self, bus, prefix: str = "stage") -> None:
        """Feed the per-stage busy seconds into an ``obs`` registry as
        gauges (``<prefix>.<stage>.busy_s``) — the pipelined executor
        calls this at teardown so bench/tests read stage accounting off
        the bus instead of holding the timer object."""
        for s, t in self.busy().items():
            bus.gauge(f"{prefix}.{s}.busy_s", t)

    def reattribute(self, src: str, dst: str, seconds: float) -> None:
        """Move ``seconds`` of accumulated time from ``src`` to ``dst`` —
        for lock-wait measured inside a work stage's context (overlap
        accounting must compare wall clock to WORK, not wait). The ``dst``
        row is booked even at 0.0 seconds so artifacts show the
        reclassification is active, not merely absent; ``src`` clamps at
        zero (the wait was measured independently of the stage timer, so
        rounding can put it epsilon above the recorded total)."""
        if seconds < 0:
            seconds = 0.0
        with self._lock:
            self.totals[src] = max(0.0, self.totals[src] - seconds)
            self.totals[dst] += seconds
            self.counts[dst] += 1


def overlap_stats(stage_busy: dict, total_wall: float,
                  exclude: tuple = ("total_wall",)) -> dict:
    """Overlap-aware pipeline accounting.

    ``overlap_efficiency`` = ``total_wall / max(stage_busy)``: 1.0 means
    the wall clock collapsed onto the single slowest stage (perfect
    overlap); values near ``serial_stage_sum_s / max(stage_busy)`` mean
    the stages ran back-to-back (no overlap). ``serial_stage_sum_s`` is
    what the same work costs serially — a pipelined run should land
    ``total_wall`` strictly below it.
    """
    busy = {k: float(v) for k, v in stage_busy.items() if k not in exclude}
    mx = max(busy.values(), default=0.0)
    return {
        "stage_busy": {k: round(v, 4) for k, v in busy.items()},
        "stage_busy_max_s": round(mx, 4),
        "serial_stage_sum_s": round(sum(busy.values()), 4),
        "overlap_efficiency": round(total_wall / mx, 3) if mx else None,
    }


class ThroughputMeter:
    """Running edges/sec: ``meter.record(n)`` after each batch."""

    def __init__(self):
        self.edges = 0
        self.start = None
        self.last = None
        # Construction time: the elapsed fallback for a single-sample
        # meter (first-sample time alone spans no interval).
        self._created = time.perf_counter()

    def record(self, n: int):
        now = time.perf_counter()
        if self.start is None:
            self.start = now
        self.edges += int(n)
        self.last = now

    @property
    def elapsed(self) -> float:
        if self.last is None:
            return 0.0
        span = self.last - self.start
        if span > 0:
            return span
        # A single record() leaves start == last, which read as
        # elapsed == 0 and an edges/sec of 0.0 despite nonzero edges
        # (ISSUE 5 satellite): fall back to time since the meter was
        # created — the interval the one sample actually covers.
        return self.last - self._created

    @property
    def edges_per_sec(self) -> float:
        return self.edges / self.elapsed if self.elapsed > 0 else 0.0

    def snapshot(self) -> dict:
        """Point-in-time reading for heartbeats / bench lines."""
        return {
            "edges": self.edges,
            "elapsed_s": round(self.elapsed, 6),
            "edges_per_sec": round(self.edges_per_sec, 1),
        }

    def publish(self, bus, prefix: str = "throughput") -> None:
        """Feed the current reading into an ``obs`` registry as gauges."""
        bus.gauge(f"{prefix}.edges", self.edges)
        bus.gauge(f"{prefix}.edges_per_sec", round(self.edges_per_sec, 1))


@contextlib.contextmanager
def trace(log_dir: str | None, tracer=None):
    """Device-level profiling via jax.profiler; no-op when log_dir is None.

    Exception-safe (ISSUE 5 satellite): a body that raises can no longer
    leave a dangling started trace — ``stop_trace`` always runs, and a
    failing stop is logged rather than allowed to MASK the body's
    exception. When ``jax.profiler`` is unavailable on the platform (or
    the start itself fails — e.g. a trace is already running), the block
    degrades to a clean no-op: observability must never kill the
    measured run.

    ``tracer`` (an ``obs.SpanTracer``) records start/stop instant events
    carrying its shared ``trace_id``, so the exported span trace and the
    device-side profiler trace captured around the same run can be
    aligned in Perfetto.
    """
    if log_dir is None:
        yield
        return
    import logging

    log = logging.getLogger("gelly_tpu.obs")
    try:
        import jax

        jax.profiler.start_trace(log_dir)
    except Exception as e:  # noqa: BLE001 — profiler absent/busy: no-op
        log.warning("jax.profiler trace unavailable (%s: %s); running "
                    "untraced", type(e).__name__, e)
        yield
        return
    if tracer is not None:
        tracer.instant("jax_profiler_start", log_dir=log_dir,
                       trace_id=tracer.trace_id)
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            # Never mask the body's exception with a failed stop.
            log.warning("jax.profiler stop_trace failed (%s: %s)",
                        type(e).__name__, e)
        if tracer is not None:
            tracer.instant("jax_profiler_stop", log_dir=log_dir)
