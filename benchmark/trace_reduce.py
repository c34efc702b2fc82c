"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time.

The harness wraps the traced sub-window in a host annotation named
:data:`WINDOW` and its own calls in annotations named ``bench.<what>``
(``jax.profiler.TraceAnnotation``), so host spans and device events share
the profiler's clock. From the trace this gives:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices traced;
- ``window_s``: the window's length; the idle share is 1 - busy/window;
- ``device_ops``: the operations that took most device time, by name;
- ``idle_gaps``: the longest stretches with no device operation, each
  named by the innermost ``bench.*`` span the host was in at its middle.

Device events are read from the ``XLA Ops`` line of each ``/device:TPU:``
plane (one event per executed operation; nested operations fall inside
their parent's interval, so the union counts them once, while the
per-operation totals count each). A plane without that line contributes
all its events. The profiler puts device events on the host's clock to
within about 2 ms (a recorded v5e trace showed device events some 1.5 ms
early), so a window has to be long against that: the cells trace seconds.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "bench.traced_window"
PREFIX = "bench."
DEVICE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
MIN_GAP_NS = 1000  # shorter stretches between operations are no gap


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_label(op: str, module: str | None) -> str:
    """``<program>:<op> = <shape> <kind>``: the HLO text without layouts
    and operands, under the name of the program that ran it."""
    head = re.sub(r"\{[^}]*\}", "", op).split("(")[0].strip()
    if module is None:
        return head
    return re.sub(r"\(\d+\)$", "", module) + ":" + head


def _module_at(modules, starts, t: float):
    """The program running at device time ``t`` (modules: (start, end,
    name), sorted)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i][1]:
        return modules[i][2]
    return None


def reduce_profile(pd) -> dict | None:
    """The reduction of a ``jax.profiler.ProfileData``; None when the trace
    holds no window annotation or no device plane (nothing to read)."""
    host_spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE):
            lines = {ln.name: ln for ln in plane.lines}
            chosen = ([lines[OPS_LINE]] if OPS_LINE in lines
                      else list(plane.lines))
            modules = sorted(
                (s, s + d, n) for n, s, d in (
                    _events(lines[MODULES_LINE]) if MODULES_LINE in lines
                    else ()))
            starts = [m[0] for m in modules]
            devices.append([
                (op_label(n, _module_at(modules, starts, s)), s, d)
                for ln in chosen for n, s, d in _events(ln)])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host_spans.extend(ev for ev in _events(ln)
                                  if ev[0].startswith(PREFIX))
    windows = [(s, s + d) for n, s, d in host_spans if n == WINDOW]
    if not windows or not devices:
        return None
    w0, w1 = windows[0]
    window_ns = w1 - w0
    spans = [(s, s + d, n) for n, s, d in host_spans
             if n != WINDOW and s < w1 and s + d > w0]
    busy_ns, op_ns, gaps = [], {}, []
    for evs in devices:
        clipped = []
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                op_ns[name] = op_ns.get(name, 0.0) + (b - a)
        merged = _union(clipped)
        busy_ns.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a > MIN_GAP_NS:
                gaps.append((b - a, _host_at((a + b) / 2, spans)))
    gaps.sort(key=lambda g: -g[0])
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": window_ns / 1e9,
        "devices": len(devices),
        "device_ops": [[n, ns / 1e9] for n, ns in ops],
        "idle_gaps": [[label, ns / 1e9] for ns, label in gaps[:TOP]],
    }


def _host_at(t: float, spans) -> str:
    """The innermost (shortest) harness span covering host time ``t``."""
    best = None
    for s, e, n in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, n)
    return best[1][len(PREFIX):] if best else "outside_bench_spans"


def reduce_dir(log_dir: str) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(find_xplane(log_dir)))
