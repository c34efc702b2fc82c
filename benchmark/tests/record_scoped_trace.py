"""Record the small profiler trace the phase-reduction test reads.

    python3 -m benchmark.tests.record_scoped_trace <out_dir> [<copy_to>]

Run on the chip: one jitted program with two named scopes, ``t.pre``
(one matmul) and ``t.loop`` (a ``lax.while_loop`` of 7 iterations whose
body is scoped ``t.step``), dispatched inside ``bench.traced_window`` >
``bench.pass`` on the main thread. The main thread first waits 80 ms in
a ``gelly.consumer_wait`` stage (``StageTimer``) while a worker thread
holds a 30 ms ``gelly.ingest_compress`` stage inside that wait, then 30
ms in ``bench.pass`` alone: two idle gaps, each to be named by the main
thread's stage. Prints the reduction and, with ``<copy_to>``, copies the
``.xplane.pb`` there.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import threading
import time

TRIPS = 7


def main(out_dir: str, copy_to: str | None = None) -> int:
    os.environ["TPU_LOG_DIR"] = "disabled"
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    from gelly_tpu.utils.metrics import StageTimer

    def scoped(x, trips):
        with jax.named_scope("t.pre"):
            y = jnp.sin(x) @ x

        def body(c):
            i, z = c
            with jax.named_scope("t.step"):
                return i + 1, jnp.tanh(z @ x)

        with jax.named_scope("t.loop"):
            _, z = jax.lax.while_loop(lambda c: c[0] < trips, body, (0, y))
        return z

    f = jax.jit(scoped)
    x = jnp.ones((4096, 4096), jnp.bfloat16) * 0.01
    trips = jnp.int32(TRIPS)  # traced: the compiler cannot unroll it
    f(x, trips).block_until_ready()
    timer = StageTimer()

    def worker():
        time.sleep(0.03)
        with timer("ingest_compress"):
            time.sleep(0.03)

    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        with jax.profiler.TraceAnnotation("bench.pass"):
            w = threading.Thread(target=worker)
            w.start()
            with timer("consumer_wait"):
                time.sleep(0.08)
            w.join()
            time.sleep(0.03)
            with timer("fold_dispatch"):
                y = f(x, trips)
            y.block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    from benchmark import trace_phases, trace_reduce
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    print(json.dumps({"reduce": trace_reduce.reduce_profile(pd),
                      "phases": trace_phases.reduce_file(path)}, indent=1))
    from benchmark import xplane

    for plane in xplane.read_file(path):
        for op in plane["ops"].values():
            if "tf_op" in op["stats"]:
                print("tf_op", repr(op["stats"]["tf_op"]),
                      repr(op["name"][:60]))
    print("xplane", path, os.path.getsize(path))
    if copy_to:
        shutil.copyfile(path, copy_to)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
