"""The trace reduction, on hand-made planes with known answers and on a
small trace recorded on a v5e chip (``record_trace.py``)."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmark import trace_reduce as tr

SAMPLE = Path(__file__).parent / "data" / "v5e_sample.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def profile(device_ops, host, modules=()):
    return NS(planes=[
        plane("/device:TPU:0", XLA_Modules=list(modules),
              XLA_Ops=list(device_ops)),
        plane("/device:CUSTOM:Megascale Trace"),
        plane("/host:CPU", python3=list(host)),
    ])


def test_busy_is_the_union_clipped_to_the_window():
    ops = [ev("%a = s32[8]{0} add(x)", 0, 300),  # half outside the window
           ev("%b = s32[8]{0} mul(x)", 200, 200),  # overlaps a
           ev("%a = s32[8]{0} add(x)", 5000, 2000)]
    host = [ev("bench.traced_window", 150, 9850),
            ev("bench.wait", 400, 4000), ev("bench.emit", 7000, 3000)]
    got = tr.reduce_profile(profile(ops, host))
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(9850e-9)
    assert got["busy_s"] == pytest.approx((400 - 150 + 2000) * 1e-9)
    assert got["device_ops"][0] == ["%a = s32[8] add", pytest.approx(2150e-9)]
    # gaps: 400..5000 (host in "wait"), 7000..10000 (host in "emit")
    assert got["idle_gaps"] == [["wait", pytest.approx(4600e-9)],
                                ["emit", pytest.approx(3000e-9)]]


def test_ops_are_named_by_their_program():
    ops = [ev("%w = s32[4]{0:T(1024)} while(s32[4] %c), body=%b", 10, 5)]
    mods = [ev("jit_transform(12345)", 0, 100)]
    host = [ev("bench.traced_window", 0, 100)]
    got = tr.reduce_profile(profile(ops, host, mods))
    assert got["device_ops"][0][0] == "jit_transform:%w = s32[4] while"


def test_nothing_to_read_gives_nothing():
    assert tr.reduce_profile(profile([ev("x", 0, 1)], [])) is None
    assert tr.reduce_profile(NS(planes=[plane("/host:CPU", python3=[
        ev("bench.traced_window", 0, 10)])])) is None


def test_recorded_v5e_trace():
    from jax.profiler import ProfileData

    got = tr.reduce_profile(ProfileData.from_file(str(SAMPLE)))
    assert got is not None and got["devices"] == 1
    # ten 8192^2 bf16 matmuls, a 100 ms host sleep, one cumsum
    assert 0.15 < got["window_s"] < 0.5
    assert 0.02 < got["busy_s"] < got["window_s"] - 0.09
    label, gap = got["idle_gaps"][0]
    assert label == "host_sleep" and 0.09 < gap < 0.2
    assert got["device_ops"][0][0].startswith("jit__lambda:")
