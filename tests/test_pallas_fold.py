"""Pallas VMEM-blocked gather + fold-backend tests (interpret mode).

Every kernel here runs under ``interpret=True`` on the CPU backend — the
exact code path the TPU compiles — so tier-1 exercises the Pallas fold
without hardware. Shapes are deliberately tiny: the interpreter executes
grid steps serially in Python. ``tests/test_tpu_compile.py`` compiles the
same kernels for a v5e at real widths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gelly_tpu.ops import unionfind
from gelly_tpu.ops.pallas_kernels import (
    blocked_gather,
    gatherable,
    sorted_window_gather,
)

pytestmark = pytest.mark.pallas

N = 1 << 12  # slot space of every fold test (window-blockable)


# --------------------------------------------------------------------- #
# sorted_window_gather — the microkernel


def test_sorted_gather_exact_on_sorted_uniform():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.integers(0, N, N).astype(np.int32))
    idx = np.sort(rng.integers(0, N, 2000)).astype(np.int32)
    got = np.asarray(sorted_window_gather(table, jnp.asarray(idx), tile=512))
    want = np.asarray(table)[idx]
    assert (got >= 0).all()  # dense sorted run: every lane in-window
    assert np.array_equal(got, want)


def test_sorted_gather_hot_duplicates_and_bounds():
    # A hot slot repeated across whole tiles, plus both boundary slots.
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.integers(0, N, N).astype(np.int32))
    idx = np.sort(np.concatenate([
        np.zeros(600, np.int32),
        np.full(900, 7, np.int32),
        np.full(3, N - 1, np.int32),
    ]))
    got = np.asarray(sorted_window_gather(table, jnp.asarray(idx), tile=512))
    want = np.asarray(table)[idx]
    hit = got >= 0
    # Misses may only appear where the run jumps windows — and a miss is
    # a -1 marker, never a wrong value.
    assert np.array_equal(got[hit], want[hit])
    assert hit.mean() > 0.9


def test_sorted_gather_piecewise_seam_marks_misses():
    # Two concatenated sorted runs: the seam tile spans the whole table,
    # so some lanes must come back -1 (unresolved), none wrong.
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.integers(0, N, N).astype(np.int32))
    idx = np.concatenate([
        np.sort(rng.integers(N // 2, N, 512)),
        np.sort(rng.integers(0, N // 2, 512)),
    ]).astype(np.int32)
    # window_rows=4 -> a 512-slot window (1024 doubled), far below the
    # table: the seam tile cannot cover both halves.
    got = np.asarray(sorted_window_gather(
        table, jnp.asarray(idx), tile=256, window_rows=4))
    want = np.asarray(table)[idx]
    hit = got >= 0
    assert np.array_equal(got[hit], want[hit])
    assert not hit.all()  # the seam must be flagged, not fabricated


def test_sorted_gather_rejects_unblockable_table():
    with pytest.raises(ValueError):
        sorted_window_gather(
            jnp.zeros(1000, jnp.int32), jnp.zeros(8, jnp.int32)
        )
    assert not gatherable(1000)
    assert not gatherable((1 << 24) + 128)  # above the f32-exactness bound
    assert gatherable(1 << 12) and gatherable(1 << 24)


def test_blocked_gather_exact_any_order_and_under_jit():
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.integers(0, N, N).astype(np.int32))
    idx = rng.integers(0, N, 1500).astype(np.int32)
    want = np.asarray(table)[idx]
    got = np.asarray(blocked_gather(table, jnp.asarray(idx), tile=512))
    assert np.array_equal(got, want)
    f = jax.jit(lambda t, i: blocked_gather(t, i, tile=512))
    assert np.array_equal(np.asarray(f(table, jnp.asarray(idx))), want)
    # Unblockable table: silently falls back to the plain XLA gather.
    t2 = jnp.asarray(rng.integers(0, 100, 100).astype(np.int32))
    i2 = rng.integers(0, 100, 64).astype(np.int32)
    assert np.array_equal(
        np.asarray(blocked_gather(t2, jnp.asarray(i2))), np.asarray(t2)[i2]
    )
    # Values beyond the f32-exact bound (hashes, not parent ids): the
    # runtime value guard must fall back to the exact plain gather
    # instead of returning f32-rounded neighbors.
    t3 = jnp.asarray(
        (rng.integers(0, 1 << 30, N) | 1).astype(np.int32))  # odd, > 2^24
    got3 = np.asarray(blocked_gather(t3, jnp.asarray(idx), tile=512))
    assert np.array_equal(got3, np.asarray(t3)[idx])


# --------------------------------------------------------------------- #
# union_edges_dedup backend parity — adversarial streams


def _oracle_labels(chunks, n):
    """Python DSU over the whole stream: canonical min-slot labels."""
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    seen = set()
    for src, dst, valid in chunks:
        for u, v, ok in zip(src.tolist(), dst.tolist(), valid.tolist()):
            if not ok:
                continue
            seen.update((u, v))
            ru, rv = find(u), find(v)
            if ru != rv:
                parent[max(ru, rv)] = min(ru, rv)
    return np.array(
        [find(i) if i in seen or parent[i] != i else i for i in range(n)],
        np.int32,
    )


_FOLD_CACHE: dict = {}


def _fold_stream(chunks, backend, unique_cap, tail_cap=None):
    # One jitted fold per (backend, caps): the adversarial streams share
    # shapes, so reusing the executable keeps the tier-1 budget flat.
    key = (backend, unique_cap, tail_cap)
    if key not in _FOLD_CACHE:
        _FOLD_CACHE[key] = jax.jit(
            lambda p, s, d, v: unionfind.union_edges_dedup(
                p, s, d, v, unique_cap=unique_cap, tail_cap=tail_cap,
                backend=backend, interpret=True,
            )
        )
    fold = _FOLD_CACHE[key]
    p = unionfind.fresh_forest(N)
    for src, dst, valid in chunks:
        p = fold(p, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid))
    return np.asarray(unionfind.pointer_jump(p))


def _adversarial_streams():
    rng = np.random.default_rng(7)
    E = 1024
    ones = np.ones(E, bool)
    # hot vertex: half of all edges touch slot 3 (plus self-loops on it)
    hot_s = np.where(rng.random(E) < 0.5, 3, rng.integers(0, N, E))
    hot_d = rng.integers(0, N, E)
    hot_d[::17] = hot_s[::17]  # self-loops
    # already-rooted pairs: the same chunk folded twice (second fold is
    # all no-op unions against an already-built forest)
    rep_s = rng.integers(0, N, E)
    rep_d = rng.integers(0, N, E)
    # chain merges: a long path unioned in shuffled order across chunks
    perm = rng.permutation(2 * E)
    order = rng.permutation(2 * E - 1)
    ch_s = perm[:-1][order]
    ch_d = perm[1:][order]
    # masked lanes mixed with duplicates
    mk_s = rng.integers(0, N, E)
    mk_d = np.concatenate([mk_s[: E // 2], rng.integers(0, N, E // 2)])
    mask = rng.random(E) > 0.4
    return {
        "hot-vertex+self-loops": [
            (hot_s.astype(np.int32), hot_d.astype(np.int32), ones)
        ],
        "already-rooted-repeat": [
            (rep_s.astype(np.int32), rep_d.astype(np.int32), ones),
            (rep_s.astype(np.int32), rep_d.astype(np.int32), ones),
        ],
        "chain-merge": [
            (ch_s[:E].astype(np.int32), ch_d[:E].astype(np.int32), ones),
            (ch_s[E:].astype(np.int32),
             ch_d[E:].astype(np.int32), ones[: E - 1]),
        ],
        "masked-duplicates": [
            (mk_s.astype(np.int32), mk_d.astype(np.int32), mask)
        ],
    }


def test_dedup_backend_parity_on_adversarial_streams():
    for name, chunks in _adversarial_streams().items():
        want = _oracle_labels(chunks, N)
        xla = _fold_stream(chunks, "xla", unique_cap=1024)
        pal = _fold_stream(chunks, "pallas", unique_cap=1024)
        assert np.array_equal(xla, want), f"xla vs oracle: {name}"
        assert np.array_equal(pal, want), f"pallas vs oracle: {name}"


def test_dedup_backend_parity_on_cap_overflows():
    rng = np.random.default_rng(11)
    E = 512
    # all-distinct pairs overflow a tiny unique_cap (exact full-width
    # fallback); a tiny tail_cap overflows the survivor compaction.
    s = (np.arange(E, dtype=np.int32) * 2) % N
    d = ((np.arange(E, dtype=np.int32) * 2) + 1) % N
    chunks = [(s, d, np.ones(E, bool))]
    want = _oracle_labels(chunks, N)
    for ucap, tcap in ((64, None), (E, 8)):
        xla = _fold_stream(chunks, "xla", unique_cap=ucap, tail_cap=tcap)
        pal = _fold_stream(chunks, "pallas", unique_cap=ucap, tail_cap=tcap)
        assert np.array_equal(xla, want), (ucap, tcap)
        assert np.array_equal(pal, want), (ucap, tcap)
    zs = (rng.zipf(1.3, E) % N).astype(np.int32)
    zd = (rng.zipf(1.3, E) % N).astype(np.int32)
    chunks = [(zs, zd, np.ones(E, bool))]
    want = _oracle_labels(chunks, N)
    assert np.array_equal(
        _fold_stream(chunks, "pallas", unique_cap=64, tail_cap=8), want
    )


def test_dedup_pallas_rejects_unblockable_capacity():
    with pytest.raises(ValueError, match="pallas"):
        unionfind.union_edges_dedup(
            jnp.arange(1000, dtype=jnp.int32),
            jnp.zeros(8, jnp.int32), jnp.zeros(8, jnp.int32),
            jnp.ones(8, bool), unique_cap=8, backend="pallas",
        )
    with pytest.raises(ValueError, match="backend"):
        unionfind.union_edges_dedup(
            unionfind.fresh_forest(N), jnp.zeros(8, jnp.int32),
            jnp.zeros(8, jnp.int32), jnp.ones(8, bool), unique_cap=8,
            backend="bogus",
        )


# --------------------------------------------------------------------- #
# plan knob wiring — library + engine


def _cc_module():
    import importlib

    return importlib.import_module("gelly_tpu.library.connected_components")


def test_cc_fold_backend_knob_end_to_end(monkeypatch):
    from gelly_tpu.core.io import EdgeChunkSource
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable

    ccmod = _cc_module()
    # Drop the dedup threshold so CI-sized chunks run the kernel path.
    monkeypatch.setattr(ccmod, "RAW_DEDUP_MIN_CHUNK", 256)
    rng = np.random.default_rng(13)
    E = 2048
    src = (rng.zipf(1.3, E) % N).astype(np.int32)
    dst = (rng.zipf(1.3, E) % N).astype(np.int32)

    def labels(backend):
        stream = edge_stream_from_source(
            EdgeChunkSource(src, dst, chunk_size=512,
                            table=IdentityVertexTable(N)), N)
        agg = ccmod.connected_components(
            N, merge="gather", ingest_combine=False, fold_backend=backend)
        assert agg.fold_backend == ("pallas" if backend == "pallas" else "xla")
        return np.asarray(stream.aggregate(agg, merge_every=4).result())

    assert np.array_equal(labels("xla"), labels("pallas"))


def test_cc_fold_backend_validation():
    ccmod = _cc_module()
    with pytest.raises(ValueError, match="pallas"):
        ccmod.connected_components(1000, fold_backend="pallas")
    with pytest.raises(ValueError, match="fold_backend"):
        ccmod.connected_components(N, fold_backend="bogus")
    # auto resolves to xla until the measured sweep flips it
    assert ccmod.connected_components(N).fold_backend == "xla"


def test_engine_plan_cache_keys_on_fold_backend():
    from gelly_tpu.engine import aggregation as agg_mod
    from gelly_tpu.parallel import mesh as mesh_lib

    ccmod = _cc_module()
    agg = ccmod.connected_components(N, merge="gather", ingest_combine=False)
    m = mesh_lib.make_mesh()
    agg_mod._compiled_plan(agg, m)
    # A rebuilt-for-pallas plan must not reuse the xla executables: the
    # cache key carries fold_backend (jit is lazy, so this is cheap).
    agg.fold_backend = "pallas"
    agg_mod._compiled_plan(agg, m)
    assert len(agg._plan_cache) == 2
    # Key layout: (device ids, axis names, fold_backend, merge_mode).
    assert {k[2] for k in agg._plan_cache} == {"xla", "pallas"}
    assert {k[3] for k in agg._plan_cache} == {"auto"}


@pytest.mark.parametrize("platform, interpret", [
    ("tpu", False), ("cpu", True), ("gpu", None),
])
def test_pallas_interpret_only_on_cpu(monkeypatch, platform, interpret):
    # Compiled on a TPU, interpreted on the CPU; any other platform is an
    # error, never a silent interpreter run on an accelerator.
    from types import SimpleNamespace

    from gelly_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(
        jax, "devices", lambda: [SimpleNamespace(platform=platform)])
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            pk.pallas_interpret()
    else:
        assert pk.pallas_interpret() is interpret
