"""Record the small profiler trace the trace-reduction test reads.

    python3 benchmark/tests/record_trace.py <out_dir>

Run on the chip: a few jitted programs inside a ``bench.traced_window``
annotation, with ``bench.*`` host spans around them and a host-only gap
between them, so the trace has device busy time, idle gaps and spans to
name them by. Prints a summary of the planes and lines it holds.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time


def main(out_dir: str) -> int:
    os.environ["TPU_LOG_DIR"] = "disabled"
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    g = jax.jit(lambda x: jnp.cumsum(x, axis=0))
    x = jnp.ones((8192, 8192), jnp.bfloat16)
    f(x).block_until_ready()
    g(x).block_until_ready()
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        with jax.profiler.TraceAnnotation("bench.compute"):
            y = x
            for _ in range(10):
                y = f(y)
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host_sleep"):
            time.sleep(0.1)
        with jax.profiler.TraceAnnotation("bench.compute"):
            g(y).block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", repr(plane.name), lines[:12])
        for ln in plane.lines:
            evs = list(ln.events)[:4]
            if evs:
                print("   line", repr(ln.name),
                      [(e.name, e.start_ns, e.duration_ns) for e in evs])
    print("xplane", path, os.path.getsize(path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
