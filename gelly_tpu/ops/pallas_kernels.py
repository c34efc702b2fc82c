"""Pallas TPU kernels for the dense hot ops.

Kernel-selection rationale (why these ops and not others): the TPU earns
its throughput on dense tiled compute (MXU 128×128 systolic matmuls, VPU
8×128 vector ops) streamed through VMEM. Of this framework's hot paths,

- the window-triangle wedge count has a dense reformulation: the per-edge
  common-neighbor sum  Σ_u M[u,a]·M[u,b]  over all canonical edges is a
  gather into  W = MᵀM  — a pure matmul. For dense windows the MXU
  computes W orders of magnitude faster than the VPU walks per-edge column
  pairs, and the edge gather from W afterwards is O(E) scalars.
- the union-find fold is pointer-chasing (``p[p]`` gathers + scatter-min).
  XLA lowers those as element-granule random HBM accesses, measured at a
  flat ~140M touches/s on v5e regardless of table size — 0.04% of the HBM
  roofline, and the wall the whole device fold sits behind (BENCH_r05's
  ``fold_hbm_util: 0.0004``). Mosaic (this jax's TPU Pallas backend) has
  no vector-gather lowering either, so a kernel cannot "just gather
  faster" — but it CAN change the access pattern: when the incoming
  indices are SORTED (which the sort-dedup fold already pays for), each
  index tile touches one small contiguous window of the table. That
  window fits VMEM, and within VMEM a gather is expressible as a one-hot
  row-select matmul on the MXU — trading ~2·W flops per touch (cheap on
  a 197 TFLOP/s part) for the HBM random-access latency (expensive).
  :func:`sorted_window_gather` is that kernel; it doubles as the
  standalone microkernel that measures the achievable blocked
  random-touch rate — the honest roofline the device-fold bench records.

:func:`wedge_count_matrix` is the classic tiled Pallas matmul (grid over
output tiles, full-K accumulation per tile, f32 on the MXU). Every kernel
here takes ``interpret=``; the default (:func:`pallas_interpret`) compiles
on a TPU, interprets on the CPU (so the CPU CI exercises the exact same
kernel code paths), and refuses any other platform.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128  # MXU native tile edge


def _wedge_kernel(a_ref, b_ref, o_ref):
    # a_ref: [N, TM] column block of M; b_ref: [N, TN] column block of M.
    # Output tile o = aᵀ @ b, contracting the full N (wedge-center) axis.
    o_ref[:] = jax.lax.dot_general(
        a_ref[:], b_ref[:],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def wedge_count_matrix(m: jax.Array,
                       interpret: bool | None = None) -> jax.Array:
    """W = MᵀM for a bool wedge mask M[u, x] — W[a, b] = common smaller
    neighbors of a and b. N must be a multiple of 128 (pad the mask).
    ``interpret`` defaults to :func:`pallas_interpret`. The kernel holds
    whole ``(n, 128)`` column blocks in VMEM, so it compiles only where
    :func:`wedge_kernel_fits` (n <= 8064 on v5e)."""
    if interpret is None:
        interpret = pallas_interpret()
    n = m.shape[0]
    if n % TILE:
        raise ValueError(f"wedge matrix size {n} not a multiple of {TILE}")
    mf = m.astype(jnp.float32)
    grid = (n // TILE, n // TILE)
    # The framework traces with x64 on (64-bit id space); Mosaic rejects the
    # i64 grid indices that leak into the index maps, so trace the kernel
    # itself in 32-bit mode — nothing here needs 64-bit.
    with jax.enable_x64(False):
        return pl.pallas_call(
            _wedge_kernel,
            out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
            grid=grid,
            in_specs=[
                pl.BlockSpec((n, TILE), lambda i, j: (0, i)),
                pl.BlockSpec((n, TILE), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((TILE, TILE), lambda i, j: (i, j)),
            interpret=interpret,
        )(mf, mf)


# Scoped VMEM a Mosaic kernel may use on v5e (the compiler's default
# limit; it refuses n=8192 wedge blocks with "scoped vmem 16.12M > 16.00M").
SCOPED_VMEM_BYTES = 16 << 20


def wedge_kernel_fits(n: int) -> bool:
    """Does :func:`wedge_count_matrix` compile for an ``n``-slot mask?
    Each grid step double-buffers two f32 ``(n, 128)`` input blocks and
    one ``(128, 128)`` output tile; the whole K axis is in every block."""
    need = 2 * (2 * n * TILE * 4) + 2 * TILE * TILE * 4
    return n % TILE == 0 and need <= SCOPED_VMEM_BYTES



def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def pallas_interpret() -> bool:
    """The ``interpret=`` default of every kernel here: compiled on a TPU,
    interpreted on the CPU. Any other platform is an error, never a
    silent interpreter run on an accelerator."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile only for TPU and interpret only on the "
        f"CPU; the attached platform is {platform!r}"
    )


# --------------------------------------------------------------------- #
# VMEM-blocked sorted gather — the union-find fold's random-touch kernel


# Lane width of every 2D view (the TPU vector register lane count).
GATHER_LANE = 128
# Window rows per VMEM-resident table block: a window spans
# GATHER_WINDOW_ROWS * 128 table slots (128 rows = 16384 slots = 64 KB of
# i32 — two windows live per grid step, far under the ~16 MB VMEM).
GATHER_WINDOW_ROWS = 128
# Sorted index lanes per grid step. Bigger tiles amortize the per-step
# grid/DMA overhead but widen the value span a tile must cover AND the
# per-step VMEM transients: an (L, 1) i32 buffer pads to L sublanes x
# 128 lanes, so the tile's idx/out/one-hot intermediates cost ~0.5 MB
# each at 1024 lanes (~3 MB/step total — comfortable against the 16 MB
# VMEM with double buffering; 2048 was borderline). 1024 lanes at the
# fold's typical index density (~1/4 of slots touched) span ~4K slots
# against the 32K-slot double window.
GATHER_TILE = 1024

# Exactness bound of the one-hot matmul: table VALUES ride through f32
# products/sums (one nonzero term each), exact only below 2^24.
GATHER_MAX_VALUE = 1 << 24


def _sorted_gather_kernel(wr: int, tile: int,
                          starts_ref, idx_ref, win0_ref, win1_ref, out_ref):
    """One grid step: gather ``tile`` sorted indices from two consecutive
    VMEM-resident table windows (rows [s, s+wr) and [s+wr, s+2wr)).

    The gather itself is a one-hot row-select matmul: ``ohr @ window``
    picks each index's table ROW on the MXU, and a one-hot column mask +
    lane reduce picks the element — no vector-gather primitive needed
    (Mosaic has none). Indices outside both windows come back as -1
    (callers treat them as unresolved lanes, never wrong values).
    """
    lane = GATHER_LANE
    g = pl.program_id(0)
    # All scalars explicitly i32: a python-int operand would weak-promote
    # to i64 when the caller traces under x64, and Mosaic rejects i64.
    base = starts_ref[g] * jnp.int32(wr)
    idx = idx_ref[:]  # (tile, 1) i32, sorted across the whole call
    row = jax.lax.div(idx, jnp.int32(lane))
    col = jax.lax.rem(idx, jnp.int32(lane))
    ohc = (col == jax.lax.broadcasted_iota(jnp.int32, (tile, lane), 1)
           ).astype(jnp.float32)
    val = jnp.zeros((tile, 1), jnp.float32)
    hit = jnp.zeros((tile, 1), jnp.bool_)
    for wref, roff in ((win0_ref, 0), (win1_ref, wr)):
        lrow = row - (base + jnp.int32(roff))
        h = (lrow >= jnp.int32(0)) & (lrow < jnp.int32(wr))
        lr = jnp.where(h, lrow, jnp.int32(-1))  # matches no one-hot row
        ohr = (lr == jax.lax.broadcasted_iota(jnp.int32, (tile, wr), 1)
               ).astype(jnp.float32)
        picked = jax.lax.dot_general(
            ohr, wref[:].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            # HIGHEST is load-bearing: the MXU's default f32 path runs
            # bf16 passes that would TRUNCATE table values needing more
            # than 8 mantissa bits — a plausible-but-wrong parent id,
            # not a miss marker. (The one-hot side is 0/1 and safe at
            # any precision; the values are not.) Interpret-mode CI is
            # exact either way, so only this flag protects hardware.
            precision=jax.lax.Precision.HIGHEST,
        )  # (tile, lane): each lane's table row (or zeros on miss)
        val = val + jnp.sum(picked * ohc, axis=1, keepdims=True)
        hit = hit | h
    out_ref[:] = jnp.where(hit, val.astype(jnp.int32), jnp.int32(-1))


def sorted_window_gather(table: jax.Array, sidx: jax.Array, *,
                         window_rows: int = GATHER_WINDOW_ROWS,
                         tile: int = GATHER_TILE,
                         interpret: bool | None = None) -> jax.Array:
    """``table[sidx]`` for SORTED ``sidx`` via VMEM-resident windows.

    Returns i32 values with ``-1`` marking lanes whose index fell outside
    the tile's double window (possible only where the input is not
    actually sorted, or a tile spans more than ``2 * window_rows * 128``
    slots — e.g. at the seam of a piecewise-sorted array). Misses are
    NEVER wrong values; callers either tolerate them per-lane (the fold
    marks such pairs unresolved for its exact tail) or restore exactness
    wholesale (:func:`blocked_gather`).

    Requirements: ``table`` is 1D i32 with length a multiple of
    ``window_rows * 128`` (>= 2 windows) and every VALUE in
    ``[0, 2^24)`` — the one-hot matmul routes values through f32 products
    (exact below 2^24; forest parent entries are slot ids, always in
    range). Indices must be in ``[0, len(table))``.
    """
    if table.ndim != 1 or sidx.ndim != 1:
        raise ValueError("sorted_window_gather expects 1D table and indices")
    n = table.shape[0]
    lane = GATHER_LANE
    nr = n // lane
    wr = min(window_rows, max(nr // 2, 1))
    if n % lane or nr % wr or nr < 2 * wr:
        raise ValueError(
            f"table length {n} must be a multiple of {lane} and hold at "
            f"least two {wr}-row windows (window_rows={window_rows})"
        )
    if n > GATHER_MAX_VALUE:
        raise ValueError(
            f"table length {n} exceeds the one-hot matmul's f32 exactness "
            f"bound {GATHER_MAX_VALUE} (values must stay below 2^24)"
        )
    if interpret is None:
        interpret = pallas_interpret()
    L = sidx.shape[0]
    if L == 0:
        return jnp.zeros((0,), jnp.int32)
    pad = -L % tile
    if pad:
        # Pad with the last index: keeps the array sorted and the padded
        # tile inside a real window.
        sidx = jnp.concatenate(
            [sidx, jnp.broadcast_to(sidx[-1:], (pad,))]
        )
    G = (L + pad) // tile
    nwb = nr // wr
    starts = jnp.clip(
        (sidx[::tile] // (lane * wr)).astype(jnp.int32), 0, nwb - 2
    )
    kern = functools.partial(_sorted_gather_kernel, wr, tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((tile, 1), lambda g, s: (g, 0)),
            pl.BlockSpec((wr, lane), lambda g, s: (s[g], 0)),
            pl.BlockSpec((wr, lane), lambda g, s: (s[g] + 1, 0)),
        ],
        out_specs=pl.BlockSpec((tile, 1), lambda g, s: (g, 0)),
    )
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((G * tile, 1), jnp.int32),
            interpret=interpret,
        )(
            starts,
            sidx.astype(jnp.int32).reshape(G * tile, 1),
            table.reshape(nr, lane),
            table.reshape(nr, lane),
        )
    return out.reshape(G * tile)[:L]


def gatherable(n: int, *, window_rows: int = GATHER_WINDOW_ROWS) -> bool:
    """Can :func:`sorted_window_gather` serve a table of ``n`` slots?"""
    lane = GATHER_LANE
    nr = n // lane
    wr = min(window_rows, max(nr // 2, 1))
    return (
        0 < n <= GATHER_MAX_VALUE
        and n % lane == 0
        and nr % wr == 0
        and nr >= 2 * wr
    )


def blocked_gather(table: jax.Array, idx: jax.Array, *,
                   window_rows: int = GATHER_WINDOW_ROWS,
                   tile: int = GATHER_TILE,
                   interpret: bool | None = None) -> jax.Array:
    """Exact ``table[idx]`` for ARBITRARY-order indices via the blocked
    kernel: sort the indices (regular op), run the VMEM-blocked gather,
    sort the values back to call order, and repair any window misses with
    one plain XLA gather under a ``lax.cond`` (paid only when a miss
    actually occurred — adversarial spans, never typical sorted runs).

    This is the sort-wrapped form whose profitability the bench's gather
    study measures: it wins exactly when two L-lane sorts cost less than
    the L random HBM touches they replace.

    Exactness preconditions are enforced at RUNTIME, not assumed: a
    table whose length is not window-blockable falls back to the plain
    gather at trace time, and a table holding any value outside
    ``[0, 2^24)`` (beyond the one-hot matmul's f32-exact range — think
    timestamps or hashes rather than parent ids) falls back under a
    ``lax.cond`` (one regular O(n) min/max scan per call, cheap next to
    the gathers). The result is exact ``table[idx]`` for ANY i32 input.
    """
    if not gatherable(table.shape[0], window_rows=window_rows):
        return table[idx]
    pos = jnp.arange(idx.shape[0], dtype=jnp.int32)
    sidx, spos = jax.lax.sort((idx.astype(jnp.int32), pos), num_keys=1)
    svals = sorted_window_gather(
        table, sidx, window_rows=window_rows, tile=tile, interpret=interpret
    )
    _, vals = jax.lax.sort((spos, svals), num_keys=1)
    values_exact = (
        (jnp.min(table) >= 0) & (jnp.max(table) < GATHER_MAX_VALUE)
    )
    return jax.lax.cond(
        values_exact,
        lambda: jax.lax.cond(
            jnp.any(vals < 0),
            lambda: jnp.where(vals < 0, table[idx], vals),
            lambda: vals,
        ),
        lambda: table[idx],
    )
