"""What drivers share: the plan a configuration names, the device facts,
the profiler window, and the comparison with the reference."""

from __future__ import annotations

import importlib
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

from .. import trace_reduce

DATA = Path(__file__).resolve().parent.parent / ".data"


def build_plan(config: dict):
    """The program's aggregation, as the configuration's ``plan`` names
    it: ``factory`` is ``module:function``, the rest its keywords."""
    plan = dict(config["plan"])
    mod, fn = plan.pop("factory").split(":")
    return getattr(importlib.import_module(mod), fn)(
        config["vertices"], **plan)


def one_chip_mesh():
    import jax

    from gelly_tpu.parallel.mesh import make_mesh

    return make_mesh(1, jax.devices()[:1])


def expected(config: dict, src, dst) -> np.ndarray:
    """The reference's answer over the given edges."""
    ref = importlib.import_module(
        f"benchmark.reference.{config['reference']}")
    return ref.labels(src, dst, config["vertices"])


def expected_from_file(config: dict, path: str) -> np.ndarray:
    """The reference's answer over the edge file the program read (read
    here with numpy alone: little-endian int64 (src, dst) records)."""
    rec = np.memmap(path, dtype="<i8", mode="r").reshape(-1, 2)
    return expected(config, rec[:, 0], rec[:, 1])


def mismatches(got, want: np.ndarray) -> int:
    got = np.asarray(got)
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got != want))


def device_facts(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = None
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


@contextmanager
def annotate(what: str):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax

    with jax.profiler.TraceAnnotation(trace_reduce.PREFIX + what):
        yield


class Profiler:
    """One traced sub-window: ``start()`` then ``stop()`` -> reduction."""

    def __init__(self, tag: str):
        self.dir = str(DATA / "trace" / tag)
        self._ann = None
        self.t0 = self.t1 = None

    def start(self):
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self._ann.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> dict | None:
        import shutil

        try:
            return trace_reduce.reduce_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
