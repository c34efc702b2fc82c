"""Split a profiler trace's device time by the program's named scopes,
and name its idle gaps by the stage the dispatching thread was in.

What ``trace_reduce`` gives (busy and window seconds, the top operations
by HLO name, gaps named by ``bench.*`` spans) says how busy the device
was, not with what. The program wraps its device phases in
``jax.named_scope`` (``cc.fold``, ``uf.hook``, ...), which the TPU
profiler keeps in each operation's ``tf_op`` metadata stat, and its host
stages in ``gelly.<stage>`` annotations (``utils.metrics.StageTimer``).
From a trace and its metadata (``xplane.read_planes``) this gives:

- ``phases``: for each named scope, the device seconds in the window
  during which an operation whose ``tf_op`` path holds that scope ran:
  the union of their intervals, so nested operations count once.
  ``<program>:unscoped`` is the time in which only operations of that
  program outside every scope ran. The TPU profiler gives a ``while``
  loop's own event no ``tf_op`` (a v5e trace shows it, the fixture of
  ``record_scoped_trace.py``); that event spans its body's operations,
  so it counts only where none of them ran;
- ``phase_execs``: for each scope, how many times its most-run
  operation ran, summed over the compiled variants of each program (one
  per payload shape). Operations in a loop body below the scope are
  preferred where it has any: the profiler records one event per
  iteration of a body operation, and a ``while`` condition runs once
  more. For a scope that holds a loop's body this is the loop's
  iterations;
- ``programs``: for each program of the ``XLA Modules`` line, its
  executions and device seconds in the window;
- ``idle_gaps_by_stage``: the longest stretches with no device
  operation, as ``trace_reduce`` finds them, each named by the innermost
  ``gelly.*`` or ``bench.*`` span on the host thread that holds
  ``bench.pass`` (the one that dispatches the device work).

Seconds are averaged over the devices traced, counts summed over them.
A named scope is a dotted component of the ``tf_op`` path: jax's own
components (``jit(f)``, ``while``, ``body``) carry no dot.
"""

from __future__ import annotations

import re

from . import trace_reduce as tr

STAGES = ("gelly.", tr.PREFIX)
PASS = tr.PREFIX + "pass"
NO_STAGE = "outside_bench_spans"


def scopes_of(tf_op: str) -> list[str]:
    """The named scopes on an operation's ``tf_op`` path (``op_name`` or
    ``op_name:op_type``), outermost first."""
    path = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    return [c for c in path.split("/") if "." in c and "(" not in c]


def _in_loop_below(tf_op: str, scope: str) -> bool:
    """Whether the operation sits in a ``while`` body below ``scope``."""
    rest = tf_op.split(scope + "/", 1)[-1]
    return "while/body" in rest


def _program(module: str) -> tuple[str, int | None]:
    """(name, program id) of an ``XLA Modules`` event name
    (``jit_fold_segments(1234)``)."""
    m = re.fullmatch(r"(.*)\((\d+)\)", module)
    return (m.group(1), int(m.group(2))) if m else (module, None)


def _tf_ops(meta: list[dict]) -> dict:
    """``tf_op`` by (device plane, program id, operation name)."""
    out = {}
    for plane in meta:
        if not plane["name"].startswith(tr.DEVICE):
            continue
        for op in plane["ops"].values():
            pid = op["stats"].get("program_id")
            if pid is not None:
                out[(plane["name"], pid, op["name"])] = str(
                    op["stats"].get("tf_op", ""))
    return out


def _clip(s, d, w0, w1):
    a, b = max(s, w0), min(s + d, w1)
    return (a, b) if b > a else None


def _total(intervals) -> float:
    return sum(b - a for a, b in tr._union(intervals))


def reduce_phases(pd, meta: list[dict]) -> dict | None:
    """The added keys from a ``jax.profiler.ProfileData`` and its
    metadata; None when the trace holds no window annotation or no
    device plane."""
    tf_ops = _tf_ops(meta)
    window, stage_spans = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = list(tr._events(ln))
            for n, s, d in evs:
                if n == tr.WINDOW and window is None:
                    window = (s, s + d)
            if any(n == PASS for n, _, _ in evs):
                stage_spans.extend(
                    (s, s + d, n) for n, s, d in evs
                    if n.startswith(STAGES) and n != tr.WINDOW)
    devices = [p for p in pd.planes if p.name.startswith(tr.DEVICE)]
    if window is None or not devices:
        return None
    w0, w1 = window
    scope_iv, free_iv, prog_iv, prog_n = {}, {}, {}, {}
    op_n, op_scope = {}, {}
    gaps = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        modules = sorted(
            (s, s + d, n) for n, s, d in (
                tr._events(lines[tr.MODULES_LINE])
                if tr.MODULES_LINE in lines else ()))
        starts = [m[0] for m in modules]
        for s, e, n in modules:
            iv = _clip(s, e - s, w0, w1)
            if iv is not None:
                name = _program(n)[0]
                prog_iv.setdefault((plane.name, name), []).append(iv)
                prog_n[name] = prog_n.get(name, 0) + 1
        chosen = ([lines[tr.OPS_LINE]] if tr.OPS_LINE in lines
                  else list(plane.lines))
        busy = []
        for ln in chosen:
            for n, s, d in tr._events(ln):
                iv = _clip(s, d, w0, w1)
                if iv is None:
                    continue
                busy.append(iv)
                module = tr._module_at(modules, starts, s)
                prog, pid = _program(module) if module else ("", None)
                tf_op = tf_ops.get((plane.name, pid, n), "")
                scopes = scopes_of(tf_op)
                free_iv.setdefault((plane.name, prog), [[], []])[
                    0 if scopes else 1].append(iv)
                for sc in scopes:
                    scope_iv.setdefault((plane.name, sc), []).append(iv)
                if scopes:
                    key = (plane.name, pid, n)
                    op_n[key] = op_n.get(key, 0) + 1
                    op_scope[key] = (scopes[-1], _in_loop_below(
                        tf_op, scopes[-1]))
        merged = tr._union(busy)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a > tr.MIN_GAP_NS:
                gaps.append((b - a, _stage_at((a + b) / 2, stage_spans)))
    nd = len(devices)
    phases = {}
    for (_, sc), ivs in scope_iv.items():
        phases[sc] = phases.get(sc, 0.0) + _total(ivs) / nd / 1e9
    for (_, prog), (scoped, free) in free_iv.items():
        if free:
            key = f"{prog}:unscoped"
            phases[key] = phases.get(key, 0.0) + (
                _total(scoped + free) - _total(scoped)) / nd / 1e9
    runs = {}  # (scope, plane, program id) -> (in a loop body, runs)
    for key, n in op_n.items():
        sc, looped = op_scope[key]
        k = (sc,) + key[:2]
        runs[k] = max(runs.get(k, (False, 0)), (looped, n))
    execs = {}
    for (sc, _, _), (_, n) in runs.items():
        execs[sc] = execs.get(sc, 0) + n
    prog_s = {}
    for (_, name), ivs in prog_iv.items():
        prog_s[name] = prog_s.get(name, 0.0) + _total(ivs) / nd / 1e9
    gaps.sort(key=lambda g: -g[0])
    return {
        "phases": dict(sorted(phases.items(), key=lambda kv: -kv[1])),
        "phase_execs": dict(sorted(execs.items())),
        "programs": {name: {"execs": prog_n[name], "device_s": prog_s[name]}
                     for name in sorted(prog_n)},
        "idle_gaps_by_stage": [[label, ns / 1e9]
                               for ns, label in gaps[:tr.TOP]],
    }


def _stage_at(t: float, spans) -> str:
    """The innermost (shortest) stage span covering host time ``t``."""
    best = None
    for s, e, n in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, n)
    return best[1] if best else NO_STAGE


def reduce_file(path: str) -> dict | None:
    from jax.profiler import ProfileData

    from . import xplane

    return reduce_phases(ProfileData.from_file(path), xplane.read_file(path))
