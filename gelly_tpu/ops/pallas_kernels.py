"""Pallas TPU kernels for the dense hot ops.

Kernel-selection rationale: the TPU earns its throughput on dense tiled
compute (MXU 128×128 systolic matmuls, VPU 8×128 vector ops) streamed
through VMEM. The window-triangle wedge count has a dense
reformulation: the per-edge common-neighbor sum  Σ_u M[u,a]·M[u,b]  over
all canonical edges is a gather into  W = MᵀM  — a pure matmul. For
dense windows the MXU computes W instead of the VPU walking per-edge
column pairs, and the edge gather from W afterwards is O(E) scalars.

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
