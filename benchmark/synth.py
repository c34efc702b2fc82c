"""Traffic generation: the graph a configuration names, its edges in
their fixed arrival order, and its vertices placed on slots drawn from
the run's seed.

The law is Twitter-2010's. Kwak et al. (WWW 2010, section 3.1) fit a
power law with exponent ``gamma`` = 2.276 to the follower counts; a
degree law ``P(d) ~ d^-gamma`` is a rank law ``d(r) ~ r^-beta`` with
``beta = 1 / (gamma - 1)``. LAW's ``twitter-2010`` lists only vertices
with an arc, so every vertex of the graph has degree at least 1: the
vertex of rank ``r`` has degree ``max(1, floor(C r^-beta))``, with ``C``
set so the degrees sum to twice the configuration's ``edges``. The edges
are a configuration model: the multiset of endpoints, uniformly
shuffled, paired in order.

A deployment serves one graph, so the graph and the order in which its
edges arrive are fixed by the configuration's ``graph_seed``. It has
``graph_vertices`` vertices, 99% of the ``vertices`` slots; the run's
``--seed`` draws which slots they take and places them by a map that
keeps their order (smallest to smallest). Every comparison the program
makes (min-slot roots, first-seen compact ids) then comes out the same,
and so does its work, while every seed's edges and labels differ.
Measured on the chip (PR 22, call 14), graphs drawn from the seed read
passes 8% apart; so did arrival orders of one graph (PR 22, call 5:
29%, one more pointer-jump round in the window close), so a seed that
redrew the graph or reordered it would change the work.

Everything is made in bulk: the shuffle splits the endpoints into a
fixed number of parts and buckets (never the machine's core count, so a
seed gives the same edges everywhere) and runs on threads, since numpy's
samplers, sorts and shuffles release the GIL.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

PARTS = 8  # the shuffle's input parts
BUCKETS = 64  # and output buckets; fixed, so edges never depend on cores
_SLOT_KEY = 0xFFFF_FFFF  # SeedSequence keys of the seeds' draws
_PART_KEY = 0xFFFF_FFFE
_BUCKET_KEY = 0xFFFF_FFFD
_KEEP_KEY = 0xFFFF_FFFC


def seed_key(seed: int) -> int:
    """Any whole number, as numpy's SeedSequence takes it (non-negative)."""
    return int(seed) % (1 << 64)


def threads() -> int:
    return max(1, min(8, os.cpu_count() or 1))


def degrees(n_v: int, n_e: int, gamma: float) -> np.ndarray:
    """i64[n_v]: the degree of the vertex of each rank, summing to
    ``2 * n_e``: ``max(1, floor(C r^-beta))``, the remainder of the
    rounding spread one each over the top ranks."""
    if not n_v <= 2 * n_e:
        raise ValueError(f"{n_e} edges cannot touch {n_v} vertices")
    beta = 1.0 / (gamma - 1.0)

    def at_least(c: float) -> np.ndarray:
        # [k-1] = #{r: floor(c r^-beta) >= k}, k = 1..floor(c): the
        # degrees without a pass over n_v ranks.
        k = np.arange(1, int(c) + 1, dtype=np.float64)
        return np.minimum(n_v, np.floor((c / k) ** (1.0 / beta))).astype(
            np.int64)

    def total(c: float) -> int:
        a = at_least(c)
        return int(a.sum()) + n_v - int(a[0] if a.size else 0)

    lo, hi = 1.0, 2.0
    while total(hi) <= 2 * n_e:
        lo, hi = hi, 2 * hi
    for _ in range(48):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if total(mid) <= 2 * n_e else (lo, mid)
    a = at_least(lo)
    head = np.repeat(np.arange(a.size, 0, -1, dtype=np.int64),
                     -np.diff(np.append(a, 0))[::-1])
    d = np.concatenate([head, np.ones(n_v - head.size, np.int64)])
    rem = 2 * n_e - int(d.sum())
    if not 0 <= rem <= n_v:
        raise ValueError(f"degrees miss their sum by {rem}")
    d[:rem] += 1
    return d


def slots(seed: int, n_v: int) -> np.ndarray:
    """i32[n_v]: the vertex of each rank, a bijection drawn from the seed
    (``r -> (a r + b) mod n_v``, ``a`` prime to ``n_v``), so hot vertices
    land on spread slots."""
    rng = np.random.default_rng([seed_key(seed), _SLOT_KEY])
    while True:
        a = int(rng.integers(1, max(2, n_v)))
        if np.gcd(a, n_v) == 1:
            break
    b = int(rng.integers(0, n_v))
    return ((a * np.arange(n_v, dtype=np.int64) + b) % n_v).astype(np.int32)


def shuffle(x: np.ndarray, seed: int) -> np.ndarray:
    """A uniform permutation of ``x`` drawn from the seed: each element
    goes to a uniform random bucket, then each bucket is shuffled."""
    cut = np.linspace(0, x.size, PARTS + 1).astype(np.int64)

    def draw(p):
        rng = np.random.default_rng([seed_key(seed), _PART_KEY, p])
        b = rng.integers(0, BUCKETS, int(cut[p + 1] - cut[p]),
                         dtype=np.uint8)
        return (np.argsort(b, kind="stable"),
                np.bincount(b, minlength=BUCKETS))

    out = np.empty_like(x)
    with ThreadPoolExecutor(threads()) as ex:
        parts = list(ex.map(draw, range(PARTS)))
        count = np.stack([c for _, c in parts])  # [part, bucket]
        flat = count.T.reshape(-1)  # bucket-major
        start = (np.cumsum(flat) - flat).reshape(BUCKETS, PARTS)

        def scatter(p):
            seg = x[cut[p]:cut[p + 1]][parts[p][0]]
            lo = np.cumsum(count[p]) - count[p]
            for b in range(BUCKETS):
                s = start[b, p]
                out[s:s + count[p, b]] = seg[lo[b]:lo[b] + count[p, b]]

        def inner(b):
            rng = np.random.default_rng([seed_key(seed), _BUCKET_KEY, b])
            rng.shuffle(out[start[b, 0]:start[b, 0] + count[:, b].sum()])

        list(ex.map(scatter, range(PARTS)))
        list(ex.map(inner, range(BUCKETS)))
    return out


def kept(seed: int, n_v: int, k: int) -> np.ndarray:
    """i32[k]: the ``k`` of the ``n_v`` slots that run ``seed`` uses,
    ascending (graph vertex ``i`` goes to slot ``kept[i]``)."""
    rng = np.random.default_rng([seed_key(seed), _KEEP_KEY])
    use = np.ones(n_v, bool)
    use[rng.choice(n_v, n_v - k, replace=False)] = False
    return np.flatnonzero(use).astype(np.int32)


def edges(config: dict, seed: int):
    """The edges of run ``seed`` in arrival order: ``(src, dst)`` i32
    arrays of ``config["edges"]`` edges, the fixed graph of
    ``graph_seed`` (``graph_vertices`` vertices, the degree law of
    ``degree_exponent``) on the slots :func:`kept` draws from ``seed``."""
    g, k, n_e = config["graph_seed"], config["graph_vertices"], config["edges"]
    d = degrees(k, n_e, config["degree_exponent"])
    ends = shuffle(np.repeat(slots(g, k), d), g)
    ends = kept(seed, config["vertices"], k)[ends]
    return ends[0::2], ends[1::2]
