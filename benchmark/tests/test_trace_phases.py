"""The phase reduction and the metadata reader, on hand-made planes with
known answers and on traces recorded on a v5e chip (``record_trace.py``,
``record_scoped_trace.py``)."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmark import trace_phases as tp
from benchmark import xplane

from .test_trace_reduce import SAMPLE, ev, plane

US = 1000  # ns


def meta(*ops):
    """Device metadata: (name, program id, tf_op) per operation."""
    return [{"name": "/device:TPU:0", "ops": {
        i: {"name": n, "stats": {"program_id": pid, "tf_op": tf}}
        for i, (n, pid, tf) in enumerate(ops)}}]


def test_scopes_are_the_dotted_path_components():
    assert tp.scopes_of(
        "jit(fold_segments)/cc.fold/uf.fixpoint/while/body/uf.hook/"
        "scatter-min:") == ["cc.fold", "uf.fixpoint", "uf.hook"]
    assert tp.scopes_of(
        "jit(f)/cc.segments/vmap(jit(searchsorted))/vmap()/while") == [
        "cc.segments"]
    assert tp.scopes_of("jit(<lambda>)/dot_general:") == []
    assert tp.scopes_of("") == []


def test_phases_executions_programs_and_gaps():
    ops = [ev("%pre", 0, 100 * US),
           ev("%while", 100 * US, 600 * US),
           ev("%step", 100 * US, 200 * US),
           ev("%step", 400 * US, 200 * US),
           ev("%tail", 800 * US, 100 * US)]
    mods = [ev("jit_f(7)", 0, 900 * US)]
    pass_thread = [ev("bench.traced_window", 0, 2000 * US),
                   ev("bench.pass", 0, 2000 * US),
                   ev("gelly.consumer_wait", 1000 * US, 500 * US)]
    worker = [ev("gelly.ingest_compress", 1400 * US, 100 * US)]
    pd = NS(planes=[
        plane("/device:TPU:0", XLA_Modules=mods, XLA_Ops=ops),
        NS(name="/host:CPU", lines=[NS(name="python3", events=pass_thread),
                                    NS(name="python3", events=worker)]),
    ])
    md = meta(("%pre", 7, "jit(f)/a.pre/mul:"),
              ("%while", 7, "jit(f)/a.loop/while"),
              ("%step", 7, "jit(f)/a.loop/while/body/a.step/add:"),
              ("%tail", 7, ""))
    got = tp.reduce_phases(pd, md)
    assert got["phases"] == {
        "a.loop": pytest.approx(600e-6), "a.step": pytest.approx(400e-6),
        "a.pre": pytest.approx(100e-6), "jit_f:unscoped": pytest.approx(100e-6)}
    assert got["phase_execs"] == {"a.loop": 1, "a.pre": 1, "a.step": 2}
    assert got["programs"] == {"jit_f": {"execs": 1,
                                         "device_s": pytest.approx(900e-6)}}
    # 900..2000 us: the pass thread is in consumer_wait at its middle
    # (the shorter worker span there is another thread's); 700..800 us:
    # in bench.pass alone.
    assert got["idle_gaps_by_stage"] == [
        ["gelly.consumer_wait", pytest.approx(1100e-6)],
        ["bench.pass", pytest.approx(100e-6)]]


def test_loop_events_without_tf_op_and_program_variants():
    # As on the TPU: the while loop's own event carries no tf_op and
    # spans its body's events; two compiled variants of one program (one
    # per payload shape) each run the loop, 2 and 3 iterations.
    ops = [ev("%while", 0, 300 * US),          # variant 1, its loop
           ev("%body", 0, 100 * US), ev("%body", 150 * US, 100 * US),
           ev("%while", 500 * US, 400 * US),   # variant 2, its loop
           ev("%body", 500 * US, 100 * US), ev("%body", 650 * US, 100 * US),
           ev("%body", 800 * US, 50 * US)]
    mods = [ev("jit_f(1)", 0, 300 * US), ev("jit_f(2)", 500 * US, 400 * US)]
    pd = NS(planes=[
        plane("/device:TPU:0", XLA_Modules=mods, XLA_Ops=ops),
        plane("/host:CPU", python3=[ev("bench.traced_window", 0, 1000 * US)]),
    ])
    md = meta(("%while", 1, ""), ("%while", 2, ""),
              ("%body", 1, "jit(f)/a.loop/while/body/a.step/add:"),
              ("%body", 2, "jit(f)/a.loop/while/body/a.step/add:"))
    got = tp.reduce_phases(pd, md)
    assert got["phase_execs"] == {"a.step": 5}
    assert got["phases"]["a.step"] == pytest.approx(450e-6)
    # the loops' own time outside every body event: 50+50+50+50+50 us
    assert got["phases"]["jit_f:unscoped"] == pytest.approx(250e-6)
    assert got["programs"] == {"jit_f": {"execs": 2,
                                         "device_s": pytest.approx(700e-6)}}


def test_a_loop_body_counts_its_iterations_not_its_condition():
    ops = [ev("%cond", t * US, 10 * US) for t in range(0, 400, 100)] + [
        ev("%body", t * US + 20 * US, 10 * US) for t in range(0, 300, 100)]
    pd = NS(planes=[
        plane("/device:TPU:0", XLA_Modules=[ev("jit_g(3)", 0, 500 * US)],
              XLA_Ops=ops),
        plane("/host:CPU", python3=[ev("bench.traced_window", 0, 500 * US)]),
    ])
    md = meta(("%cond", 3, "jit(g)/u.chase/while/cond/reduce_or:"),
              ("%body", 3, "jit(g)/u.chase/while/body/gather:"))
    got = tp.reduce_phases(pd, md)
    assert got["phase_execs"] == {"u.chase": 3}
    # no thread holds bench.pass: no stage names the gaps
    assert {g[0] for g in got["idle_gaps_by_stage"]} == {tp.NO_STAGE}


def test_nothing_to_read_gives_nothing():
    assert tp.reduce_phases(NS(planes=[plane("/host:CPU", python3=[
        ev("bench.traced_window", 0, 10)])]), []) is None
    assert tp.reduce_phases(NS(planes=[
        plane("/device:TPU:0", XLA_Ops=[ev("x", 0, 1)])]), []) is None


def test_reader_finds_the_samples_tf_op():
    planes = xplane.read_file(str(SAMPLE))
    dev = [p for p in planes if p["name"] == "/device:TPU:0"][0]
    fusions = [op for op in dev["ops"].values()
               if op["name"].startswith("%fusion = bf16[8192,8192]")]
    assert len(fusions) == 1
    stats = fusions[0]["stats"]
    assert stats["tf_op"] == "jit(<lambda>)/dot_general:"
    assert stats["program_id"] == 3716318064654290215
    assert stats["hlo_category"] == "convolution fusion"


def test_reader_skips_what_it_does_not_need():
    # field 1 (varint 150), field 2 (bytes "hi"), field 3 (fixed64),
    # field 4 (fixed32)
    msg = (b"\x08\x96\x01" + b"\x12\x02hi" + b"\x19" + bytes(8)
           + b"\x25" + bytes(4))
    got = [(n, wt, bytes(v) if isinstance(v, memoryview) else v)
           for n, wt, v in xplane.fields(memoryview(msg))]
    assert got == [(1, 0, 150), (2, 2, b"hi"), (3, 1, bytes(8)),
                   (4, 5, bytes(4))]
    assert xplane.read_planes(b"") == []


def test_sample_reduces_without_scopes():
    got = tp.reduce_file(str(SAMPLE))
    assert got is not None
    assert set(got["programs"]) == {"jit__lambda"}
    assert got["programs"]["jit__lambda"]["execs"] == 11
    # no operation of the sample is scoped: all of it is unscoped
    assert set(got["phases"]) == {"jit__lambda:unscoped"}
    # no thread holds bench.pass in the sample
    assert got["idle_gaps_by_stage"][0][0] == tp.NO_STAGE


SCOPED = Path(__file__).parent / "data" / "v5e_scoped.xplane.pb"


def test_recorded_v5e_scoped_trace():
    # record_scoped_trace.py: t.pre (one matmul), then t.loop, a while
    # loop of 7 iterations whose body is t.step; an 80 ms consumer_wait
    # on the pass thread (another thread's 30 ms span inside it), then
    # 30 ms in bench.pass alone.
    got = tp.reduce_file(str(SCOPED))
    assert got["phase_execs"] == {"t.pre": 1, "t.step": 7}
    ph = got["phases"]
    assert ph["t.step"] == ph["t.loop"] and ph["t.loop"] > 2 * ph["t.pre"]
    prog = got["programs"]["jit_scoped"]
    assert prog["execs"] == 1
    # the scopes and the loop's own unscoped time fill the program
    assert ph["t.pre"] + ph["t.loop"] + ph["jit_scoped:unscoped"] == (
        pytest.approx(prog["device_s"], rel=1e-3))
    assert ph["jit_scoped:unscoped"] < 0.05 * prog["device_s"]
    (first, gap1), (second, gap2) = got["idle_gaps_by_stage"][:2]
    assert first == "gelly.consumer_wait" and 0.07 < gap1 < 0.2
    assert second == "bench.pass" and gap2 < gap1


def test_the_scoped_fixtures_tf_ops():
    dev = [p for p in xplane.read_file(str(SCOPED))
           if p["name"] == "/device:TPU:0"][0]
    tf_ops = {op["stats"]["tf_op"] for op in dev["ops"].values()
              if "tf_op" in op["stats"]}
    assert "jit(scoped)/t.pre/dot_general:" in tf_ops
    assert "jit(scoped)/t.loop/while/body/t.step/dot_general:" in tf_ops


def test_phase_report_on_a_tiny_cell(tmp_path):
    from benchmark import phase_report

    from .tiny import tiny_cell

    rep = phase_report.report(tiny_cell("cc-twitter2010-file"),
                              2**31 + 303, passes=2)
    assert len(rep["passes"]) == 2
    for ps in rep["passes"]:
        c = ps["counters"]
        assert ps["chunks"] > 0 and c["engine.units_folded"] > 0
        assert ps["derived"]["fold_lane_fill"] == (
            c["cc.fold_members"] / c["cc.fold_lanes"])
        assert ps["derived"]["consumer_wait_ms_per_medge"] > 0
        # no TPU plane in a CPU trace: nothing for the device readings
        assert ps["phases"] is None
        assert ps["derived"]["fold_device_ms_per_medge"] is None
    assert rep["passes"][0]["counters"] == rep["passes"][1]["counters"]
