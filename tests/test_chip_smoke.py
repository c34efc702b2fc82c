"""chip_smoke.py at tiny sizes on the CPU mesh: every phase runs its
real code path here (Pallas kernels interpreted), so the script cannot
rot between chip runs; on the CPU its ``main()`` refuses to run."""

import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from bench import synth_edges  # noqa: E402

TINY = {
    "n_v": 1 << 12, "n_e": 1 << 14, "chunk": 1 << 10, "compact_m": 1 << 12,
    "merge_every": 4, "raw_chunk": 1 << 10, "raw_chunks": 4,
    "tri_n": 256, "tri_window_edges": 1 << 9, "tri_windows": 2,
    "served_edges": 1 << 12, "wire_chunk": 1 << 10, "stack": 2,
    "mesh_edges": 1 << 13, "mesh_merge_every": 4,
}


@pytest.fixture(scope="module")
def edges():
    return synth_edges(TINY["n_e"], TINY["n_v"], seed=chip_smoke.SEED)


@pytest.fixture(autouse=True)
def _data_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "DATA_DIR", str(tmp_path))


def test_stream_cc_and_served_phases(edges):
    state = {}
    out = chip_smoke.phase_stream_cc(TINY, edges, state)
    assert out["parity"] and out["windows"] >= 4 and out["mismatches"] == 0
    served = chip_smoke.phase_served(TINY, edges, state)
    assert served["parity"], served
    assert served["acked"] == served["frames"] == 4


def test_raw_fold_phase(edges):
    out = chip_smoke.phase_raw_fold(TINY, edges)
    assert out["parity"], out
    # Interpreted on the CPU: no compiled kernel, by construction.
    assert out["pallas_kernel"] is False


def test_window_triangles_phase():
    out = chip_smoke.phase_window_triangles(TINY)
    assert out["parity"], out
    assert out["windows"] == TINY["tri_windows"] and out["triangles"] > 0
    assert out["auto_is_mxu"] is False  # CPU: auto never picks the MXU


def test_mesh_phase(edges):
    out = chip_smoke.phase_mesh(TINY, edges, 4)
    assert out["parity"], out
    assert out["shard_devices"] == 4


def test_main_refuses_the_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert out and '"ok"' not in out[-1]
    assert all('"ok": true' not in line for line in out)
