"""The program's own tracing: host stages on the profiler's clock, the
consumer's wait as a stage, the fold's lane counters, and the named
device phases of the compact CC fold and close.

- every ``StageTimer`` stage is a ``gelly.<stage>`` host span in a
  ``jax.profiler`` trace;
- the pipelined executor books ``consumer_wait`` once per unit it
  waited for, and once more for the end of the stream;
- ``cc.fold_members`` / ``cc.fold_lanes`` count a stacked payload's real
  member lanes and its padded lanes;
- the compiled fold and close carry every ``jax.named_scope`` of their
  phases in their HLO ``op_name`` metadata, which the TPU profiler
  reports as each operation's ``tf_op``.
"""

import glob
import os
import re

import jax
import numpy as np
import pytest

from gelly_tpu import obs
from gelly_tpu.library.connected_components import (
    connected_components,
    connected_components_compact,
)
from gelly_tpu.utils.metrics import StageTimer, trace

N_V = 512

FOLD_SCOPES = ["cc.fold", "cc.decode", "uf.fast", "uf.check", "uf.tail",
               "uf.fixpoint", "uf.chase", "uf.hook"]
CLOSE_SCOPES = ["cc.close", "cc.close.jump", "cc.close.canon",
                "cc.close.labels"]


def _edges(n_e=3000, seed=5, n_v=N_V):
    rng = np.random.default_rng(seed)
    src = (rng.zipf(1.4, n_e) % n_v).astype(np.int64)
    dst = (rng.zipf(1.4, n_e) % n_v).astype(np.int64)
    return src, dst


class _Chunk:
    def __init__(self, src, dst):
        self.src, self.dst = src, dst
        self.valid = np.ones(src.shape[0], bool)


def _stacked(wire: str, groups: int = 2):
    agg = connected_components_compact(N_V, compact_capacity=N_V,
                                       wire=wire)
    src, dst = _edges()
    payloads = [agg.host_compress(_Chunk(src[i:i + 500], dst[i:i + 500]))
                for i in range(0, src.shape[0], 500)]
    return agg, payloads, groups


def test_stage_is_a_profiler_span(tmp_path):
    from jax.profiler import ProfileData

    timer = StageTimer()
    with trace(str(tmp_path)):
        with timer("probe_stage"):
            jax.numpy.ones(8).block_until_ready()
    assert timer.counts["probe_stage"] == 1
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    host = [e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    assert "gelly.probe_stage" in host


def test_pipelined_executor_books_consumer_wait():
    from gelly_tpu.core.io import EdgeChunkSource
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable
    from gelly_tpu.parallel.mesh import make_mesh

    src, dst = _edges(seed=9)
    stream = edge_stream_from_source(
        EdgeChunkSource(src, dst, chunk_size=256,
                        table=IdentityVertexTable(N_V)), N_V)
    agg = connected_components(N_V, codec="compact", compact_capacity=N_V)
    timer = StageTimer()
    with obs.scope() as bus:
        stream.aggregate(agg, mesh=make_mesh(1), merge_every=4,
                         codec_workers=2, h2d_depth=2, timer=timer).result()
        units = bus.counters["engine.units_folded"]
    assert units > 0
    assert timer.counts["consumer_wait"] == units + 1
    assert timer.busy()["consumer_wait"] > 0.0


@pytest.mark.parametrize("wire,member_key", [("segments", "m"),
                                             ("pairs", "v")])
def test_fold_lane_counters(wire, member_key):
    agg, payloads, groups = _stacked(wire)
    with obs.scope() as bus:
        out = agg.stack_payloads(payloads, groups=groups)
        members = bus.counters["cc.fold_members"]
        lanes = bus.counters["cc.fold_lanes"]
    rows = out[member_key]
    assert rows.shape[0] == groups
    assert lanes == rows.size
    assert members == int((rows >= 0).sum())
    if wire == "segments":
        assert members == int(out["len"].sum())
    assert 0 < members < lanes


def _op_names(fn, *args) -> set:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("program", ["fold_segments", "fold_compressed",
                                     "transform"])
def test_named_scopes_reach_the_compiled_program(program):
    wire = "pairs" if program == "fold_compressed" else "segments"
    agg, payloads, groups = _stacked(wire)
    state = agg.init()
    if program == "transform":
        fn, args, want = agg.transform, (state,), CLOSE_SCOPES
    else:
        fn = agg.fold_compressed
        assert fn.__name__ == program
        args = (state, agg.stack_payloads(payloads, groups=groups))
        want = FOLD_SCOPES + (["cc.segments"] if wire == "segments" else [])
    names = _op_names(fn, *args)
    for scope in want:
        assert any(f"/{scope}/" in n for n in names), (scope, sorted(names))
    # the hook and the chase run inside the exact fixpoint's loop body,
    # and the fixpoint inside the live-lane tail's batch loop
    if program != "transform":
        assert any("/uf.fixpoint/while/body/uf.hook/" in n for n in names)
        assert any("/uf.fixpoint/while/body/uf.chase/" in n for n in names)
        assert any("/uf.tail/while/body/uf.fixpoint/" in n for n in names)
