"""The sort-dedup raw-edge fold (``unionfind.union_edges_dedup``) against
a Python DSU oracle, its CC plan through the engine, and the Pallas
``interpret=`` platform rule.

Shapes are deliberately tiny; ``tests/test_tpu_compile.py`` compiles
the fold for a v5e at ``chip_smoke.py``'s raw-fold shape.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gelly_tpu.ops import unionfind

N = 1 << 12  # slot space of every fold test


# --------------------------------------------------------------------- #
# union_edges_dedup vs the DSU oracle — adversarial streams


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


def _fold_stream(chunks, unique_cap, tail_cap=None):
    # One jitted fold per caps pair: the adversarial streams share
    # shapes, so reusing the executable keeps the tier-1 budget flat.
    key = (unique_cap, tail_cap)
    if key not in _FOLD_CACHE:
        _FOLD_CACHE[key] = jax.jit(
            lambda p, s, d, v: unionfind.union_edges_dedup(
                p, s, d, v, unique_cap=unique_cap, tail_cap=tail_cap,
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


@pytest.mark.parametrize("name", [
    "hot-vertex+self-loops", "already-rooted-repeat", "chain-merge",
    "masked-duplicates",
])
def test_dedup_backend_parity_on_adversarial_streams(name):
    chunks = _adversarial_streams()[name]
    want = _oracle_labels(chunks, N)
    assert np.array_equal(_fold_stream(chunks, unique_cap=1024), want)


def _distinct_pairs_chunks():
    E = 512
    s = (np.arange(E, dtype=np.int32) * 2) % N
    d = ((np.arange(E, dtype=np.int32) * 2) + 1) % N
    return [(s, d, np.ones(E, bool))]


def _zipf_chunks():
    rng = np.random.default_rng(11)
    E = 512
    zs = (rng.zipf(1.3, E) % N).astype(np.int32)
    zd = (rng.zipf(1.3, E) % N).astype(np.int32)
    return [(zs, zd, np.ones(E, bool))]


@pytest.mark.parametrize("chunks, ucap, tcap", [
    # all-distinct pairs overflow a tiny unique_cap (exact full-width
    # fallback); a tiny tail_cap overflows the survivor compaction.
    (_distinct_pairs_chunks, 64, None),
    (_distinct_pairs_chunks, 512, 8),
    (_distinct_pairs_chunks, 64, 8),
    (_zipf_chunks, 64, 8),
], ids=["unique-cap", "tail-cap", "both-caps", "zipf-both-caps"])
def test_dedup_backend_parity_on_cap_overflows(chunks, ucap, tcap):
    chunks = chunks()
    want = _oracle_labels(chunks, N)
    got = _fold_stream(chunks, unique_cap=ucap, tail_cap=tcap)
    assert np.array_equal(got, want), (ucap, tcap)


# --------------------------------------------------------------------- #
# the raw-dedup CC plan through the engine


def test_cc_raw_dedup_plan_end_to_end(monkeypatch):
    import importlib

    from gelly_tpu.core.io import EdgeChunkSource
    from gelly_tpu.core.stream import edge_stream_from_source
    from gelly_tpu.core.vertices import IdentityVertexTable

    ccmod = importlib.import_module("gelly_tpu.library.connected_components")
    # Drop the dedup threshold so CI-sized chunks run union_edges_dedup.
    monkeypatch.setattr(ccmod, "RAW_DEDUP_MIN_CHUNK", 256)
    rng = np.random.default_rng(13)
    E, C = 2048, 512
    src = (rng.zipf(1.3, E) % N).astype(np.int32)
    dst = (rng.zipf(1.3, E) % N).astype(np.int32)
    stream = edge_stream_from_source(
        EdgeChunkSource(src, dst, chunk_size=C,
                        table=IdentityVertexTable(N)), N)
    agg = ccmod.connected_components(N, merge="gather", ingest_combine=False)
    got = np.asarray(stream.aggregate(agg, merge_every=4).result())
    chunks = [(src[i:i + C], dst[i:i + C], np.ones(C, bool))
              for i in range(0, E, C)]
    want = _oracle_labels(chunks, N)
    seen = np.zeros(N, bool)
    seen[src] = seen[dst] = True
    assert np.array_equal(got, np.where(seen, want, -1))


@pytest.mark.pallas
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
