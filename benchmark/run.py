"""Run one benchmark cell on the chip and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
the names in ``BENCHMARK.json`` (``spec.py``); the traffic file names the
driver that runs it. With ``--trace 0`` the line carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics and the
device's busy and window seconds from a profiler trace. Earlier lines
say how set-up went, how late the load ran and what was compared; the
last line on stdout is the result, and the last lines on stderr are the
compared numbers beside their limits.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


def pin_environment() -> None:
    """JAX's compile cache at a fixed path inside this checkout, every
    program cached, and no TPU runtime log under /tmp."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["TPU_LOG_DIR"] = "disabled"


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def say(**line) -> None:
    print(json.dumps(line, default=float), flush=True)


def result(cell, rec: dict, trace: bool) -> dict:
    """The result line from a driver's record: metrics by their readers,
    the device, the breakdown, and the compared numbers last."""
    from benchmark import spec

    metrics = {}
    for m in spec.cell_metrics(cell.bench, cell.name, trace):
        value = spec.metric_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(rec["device"])
    out = {"correct": all(v <= lim for v, lim in rec["checks"].values()),
           "attempted": rec["attempted"], "failed": rec["failed"],
           "metrics": metrics, "device": device}
    tr = rec.get("trace")
    if trace and tr is not None:
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in rec["checks"].items()}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    pin_environment()
    # Import as the package ``benchmark`` from the checkout's root, never
    # this directory's modules by bare name.
    sys.path[:] = [str(REPO)] + [
        p for p in sys.path if Path(p or ".").resolve() != HERE]
    from benchmark import spec

    cell = spec.Cell(spec.load_benchmark(), args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"no result: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform!r} device(s)",
              file=sys.stderr)
        return 3
    from benchmark.compile_clock import CompileClock

    clock = CompileClock()
    rec = spec.driver(cell.traffic).run(cell, args.seed, args.seconds,
                                        bool(args.trace), clock)
    rec["setup_s"] = rec["t_window_start"] - T_START
    say(setup={**rec["setup"], "setup_s": rec["setup_s"]})
    say(window={k: rec.get(k) for k in (
        "window_s", "edges", "passes", "programs_in_window", "reference_s",
        "trace")})
    out = result(cell, rec, bool(args.trace))
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
