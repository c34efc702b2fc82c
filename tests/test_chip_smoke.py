"""chip_smoke.py at tiny sizes on the CPU mesh: every phase runs its
real code path here (Pallas kernels interpreted), so the script cannot
rot between chip runs; on the CPU its ``main()`` refuses to run."""

import json
import os
import sys
from types import SimpleNamespace

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from chip_smoke import synth_edges  # noqa: E402

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
    assert out["edges"] == TINY["raw_chunk"] * TINY["raw_chunks"]
    assert out["xla_temp_bytes"] > 0


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


class _StubClock:
    compile_s = 0.0
    cache_hits = 0

    def mark(self):
        return self.compile_s, self.cache_hits


def _fail(*_):
    raise ValueError("boom")


@pytest.mark.parametrize("fn, passed, error", [
    (lambda: {"parity": True, "edges": 8}, True, None),
    (lambda: {"parity": False, "edges": 8}, False, None),
    (_fail, False, "ValueError: boom"),
], ids=["passes", "parity-false", "raises"])
def test_run_phase_reports_each_outcome(capsys, fn, passed, error):
    assert chip_smoke.run_phase("p", _StubClock(), fn) is passed
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "p" and line["parity"] is passed
    assert line.get("error") == error
    assert {"wall_s", "compile_s", "cache_hits"} <= set(line)


def test_main_exits_1_without_ok_when_a_phase_fails(monkeypatch, capsys):
    from gelly_tpu.utils import compile_cache

    tpu = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [tpu])
    monkeypatch.setitem(sys.modules, "libtpu", None)
    monkeypatch.setattr(chip_smoke, "CompileClock", _StubClock)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(chip_smoke, "synth_edges", lambda *a, **k: None)
    ran = []

    def phase(name, parity):
        def run(*_):
            ran.append(name)
            return {"parity": parity}
        return run

    for name in ("stream_cc", "window_triangles", "served"):
        monkeypatch.setattr(chip_smoke, f"phase_{name}", phase(name, True))
    monkeypatch.setattr(chip_smoke, "phase_raw_fold",
                        phase("raw_fold", False))
    assert chip_smoke.main([]) == 1
    # A failed phase does not stop the later ones.
    assert ran == ["stream_cc", "raw_fold", "window_triangles", "served"]
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert not any("ok" in x for x in lines)
    assert [x["parity"] for x in lines if x["phase"] in ran] \
        == [True, False, True, True]
