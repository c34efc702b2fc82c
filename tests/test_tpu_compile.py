"""v5e ahead-of-time compiles of the wedge Pallas kernel, of the raw
sort-dedup fold, of the segment fold's lane derivation, and of the
sparse degree fold.

The TPU compiler is installed here and compiles for a described
``v5e:2x2`` topology with no chip attached: it refuses what interpret
mode accepts (VMEM overruns, unaligned blocks, i64 in a kernel). Every
kernel case passes ``interpret=False`` and asserts the kernel is in the
compiled text. The topology is described only inside the ``topo``
fixture (libtpu loads once per process; see the on-chip-measurement
guide, section 2), and the persistent compile cache is off around the
compiles (an AOT entry cannot be read back without a chip).
"""

import os

import jax
import jax.numpy as jnp
import pytest

from gelly_tpu.library import triangles
from gelly_tpu.ops import pallas_kernels as pk

TABLE = 1 << 24  # north-star vertex capacity
# Largest mask _pick_method lets the wedge kernel take (8064 on v5e).
WEDGE_MAX_N = max(n for n in range(pk.TILE, 1 << 14, pk.TILE)
                  if pk.wedge_kernel_fits(n))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_raw_dedup_fold_compiles_at_the_smoke_capacity(one_chip):
    # chip_smoke.py's raw-fold forest (2^24 slots) with the caps the CC
    # plan's fold passes, over a 2^16-edge chunk: the smoke's 2^22-edge
    # chunk compiles the same loops and branches, only more slowly.
    from gelly_tpu.ops import unionfind

    e = 1 << 16
    c = _compile(
        lambda p, s, d, v: unionfind.union_edges_dedup(
            p, s, d, v, unique_cap=max(1 << 20, 3 * (e >> 4))),
        _spec((TABLE,), jnp.int32, one_chip),
        _spec((e,), jnp.int32, one_chip), _spec((e,), jnp.int32, one_chip),
        _spec((e,), jnp.bool_, one_chip),
    )
    text = c.as_text()
    assert " sort(" in text and "tpu_custom_call" not in text


def test_wedge_kernel_compiles_at_the_largest_auto_size(one_chip):
    n = WEDGE_MAX_N
    c = _compile(lambda m: pk.wedge_count_matrix(m, interpret=False),
                 _spec((n, n), jnp.bool_, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_wedge_kernel_refused_one_tile_past_the_bound(one_chip):
    # The bound is the compiler's: one 128-tile more overruns scoped VMEM.
    n = WEDGE_MAX_N + pk.TILE
    assert not pk.wedge_kernel_fits(n)
    with pytest.raises(Exception, match="vmem"):
        _compile(lambda m: pk.wedge_count_matrix(m, interpret=False),
                 _spec((n, n), jnp.bool_, one_chip))


def test_lane_segment_starts_compiles_without_a_loop(one_chip):
    # The segment fold's lane derivation at the file-fed cell's fold shape
    # (1,572,864 member lanes, 2^19 lengths): one scatter and a running
    # sum, no while loop (a search over the lengths would be a loop of
    # log2(capr) steps over every lane).
    from gelly_tpu.library.connected_components import lane_segment_starts

    c = _compile(lambda ln: lane_segment_starts(ln, 6 << 18),
                 _spec((1, 1 << 19), jnp.int32, one_chip))
    text = c.as_text()
    assert " scatter(" in text and " while(" not in text


def _degree_fold_text(one_chip, delta_dtype) -> list:
    # The sparse degree fold at the degree cell's shape: a 2^21-lane
    # pair bucket into i64[41,652,230]. Returns the compiled scatters.
    from gelly_tpu.library.degrees import degree_aggregate

    n = 41_652_230
    agg = degree_aggregate(n, codec="sparse")
    c = _compile(agg.fold_compressed, _spec((n,), jnp.int64, one_chip),
                 {"v": _spec((1, 1 << 21), jnp.int32, one_chip),
                  "d": _spec((1, 1 << 21), delta_dtype, one_chip)})
    text = c.as_text()
    assert " scatter(" in text and " while(" not in text
    assert "/deg.fold/" in text
    return [ln for ln in text.splitlines() if " scatter(" in ln]


def test_degree_fold_compiles_at_the_cell_shape(one_chip):
    # The per-chunk i32 payload scatters on s32 into a zero vector, not
    # on the u32 halves with a carry that the v5e's int64 scatter is.
    scatters = _degree_fold_text(one_chip, jnp.int32)
    assert all("s32[41652230]" in ln and "u32[" not in ln
               for ln in scatters), scatters


def test_degree_fold_compiles_for_group_combined_payloads(one_chip):
    # An i64 (group-combined) payload keeps the int64 scatter-add, which
    # the v5e emulates on u32 halves.
    scatters = _degree_fold_text(one_chip, jnp.int64)
    assert any("u32[41652230]" in ln for ln in scatters), scatters


def test_auto_never_picks_mxu_past_the_bound(monkeypatch):
    # Steer _pick_method as if on a TPU: dense 4096-slot windows take the
    # MXU, 8192-slot windows (which the compiler refuses) take gather.
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    assert triangles._pick_method("auto", 4096)(4096) == "mxu"
    assert triangles._pick_method("auto", WEDGE_MAX_N)(1 << 20) == "mxu"
    assert triangles._pick_method("auto", 8192)(8192) == "gather"
    assert triangles._pick_method("auto", 8192)(1 << 20) == "gather"
