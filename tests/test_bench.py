"""bench.py's failure reporting: an error line makes the run exit
non-zero, and a device missing from the peak table is an error."""

import os
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import bench  # noqa: E402


@pytest.mark.parametrize("line, failed", [
    ({"metric": "cc", "value": 1.0}, False),
    ({"metric": "cc", "error": None}, False),
    ({"metric": "cc", "error": "label parity FAILED"}, True),
    ({"metric": "cc", "obs": {"error": "boom"}}, True),
    ({"metric": "cc", "sweep": {"device_fold_pallas_error": "x"}}, True),
    ({"metric": "cc", "rows": [{"ok": 1}, {"error": "x"}]}, True),
])
def test_main_exit_code_follows_error_lines(monkeypatch, line, failed):
    def run():
        bench.emit(line)
        return 0

    monkeypatch.setattr(bench, "_BENCH_LINES", [])
    monkeypatch.setattr(bench, "_run_workloads", run)
    monkeypatch.setattr(
        "gelly_tpu.utils.compile_cache.enable_compile_cache", lambda: "")
    assert bench.main() == (1 if failed else 0)


def test_chip_peaks_unknown_tpu_is_an_error(monkeypatch):
    import jax

    def devices(kind):
        return lambda: [SimpleNamespace(platform="tpu", device_kind=kind)]

    monkeypatch.setattr(jax, "devices", devices("TPU v5 lite"))
    assert bench.chip_peaks()["peak_hbm_gbps"] == 819.0
    monkeypatch.setattr(jax, "devices", devices("TPU v99"))
    with pytest.raises(RuntimeError, match="TPU v99"):
        bench.chip_peaks()
