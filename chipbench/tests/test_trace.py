"""The trace reduction: busy union, idle share, idle labels, kernel and
program time, on hand-made traces and on one recorded on the chip."""
import pytest

from chipbench.harness import trace as tracing
from chipbench.harness.trace import MODULES_LINE, OPS_LINE, Trace


def _trace():
    # window 0..100 ns; ops overlap at 10-30 and 20-40, then 60-70
    ops = [("fusion.1", 10, 20), ("fusion.2", 20, 20), ("route", 60, 10)]
    mods = [("jit_stage_step", 10, 30), ("jit_route", 60, 10)]
    host = [("chipbench.wave", 0, 100), ("chipbench.chain", 5, 40),
            ("chipbench.emit", 45, 10), ("chipbench.route", 55, 20)]
    return Trace([{OPS_LINE: ops, MODULES_LINE: mods}], host)


def test_busy_union_and_idle():
    tr = _trace()
    r = tracing.reduce(tr)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(40e-9)          # 10-40 and 60-70
    assert tracing.busy_intervals(tr.devices[0][OPS_LINE], (0, 100)) == \
        [(10, 40), (60, 70)]
    # idle gaps 0-10, 40-60 and 70-100, labelled at their midpoints
    assert r.idle_by_label == {"chain": pytest.approx(10e-9),
                               "emit": pytest.approx(20e-9),
                               "wave": pytest.approx(30e-9)}


def test_labels_take_the_innermost_span():
    label = tracing.Labeller(_trace().host)
    assert label(50) == "emit"
    assert label(30) == "chain"
    assert label(80) == "wave"
    assert label(150) == "outside"


def test_program_and_op_time():
    r = tracing.reduce(_trace())
    assert r.modules_s["jit_stage_step"] == pytest.approx(30e-9)
    assert r.ops_s["route"] == pytest.approx(10e-9)
    b = tracing.breakdown(r)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(20e-9)]
    assert len(b["idle_gaps"]) <= 10


def test_events_outside_the_window_are_clipped():
    tr = _trace()
    tr.devices[0][OPS_LINE].append(("late", 95, 50))
    r = tracing.reduce(tr)
    assert r.busy_s == pytest.approx(45e-9)
    assert r.ops_s["late"] == pytest.approx(5e-9)


def test_no_window_or_no_device_reads_nothing():
    tr = _trace()
    assert tracing.reduce(Trace(tr.devices, [])) is None
    assert tracing.reduce(Trace([], tr.host)) is None


def test_round_trip_through_json():
    tr = _trace()
    again = Trace.from_json(tr.to_json())
    assert tracing.reduce(again) == tracing.reduce(tr)


# ---- a slice of a trace recorded on the chip --------------------------------

def _recorded():
    from chipbench.harness.cell import BENCH, load_json
    return Trace.from_json(load_json(BENCH / "tests" / "data"
                                     / "trace_v5e_golden18.json"))


def test_recorded_busy_union_matches_a_bitmap():
    import numpy as np

    tr = _recorded()
    r = tracing.reduce(tr)
    w0, w1 = tracing.window(tr)
    assert r.window_s == pytest.approx((w1 - w0) / 1e9)
    # the same union, counted on a 100 ns grid
    covered = np.zeros(int((w1 - w0) // 100) + 1, bool)
    for _, s, d in tr.devices[0][OPS_LINE]:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            covered[int((a - w0) // 100):int(np.ceil((b - w0) / 100))] = True
    assert r.busy_s == pytest.approx(covered.sum() * 100e-9, rel=0.02)
    assert 0 < r.busy_s < r.window_s


def test_recorded_idle_is_labelled_by_host_spans():
    r = tracing.reduce(_recorded())
    assert set(r.idle_by_label) <= {"wave", "chain", "emit", "route", "admit",
                                     "outside"}
    assert sum(r.idle_by_label.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    # between tokens the host works in run_queue outside the hops
    assert max(r.idle_by_label, key=r.idle_by_label.get) == "wave"


def test_recorded_program_and_op_time():
    r = tracing.reduce(_recorded())
    stage = sum(v for k, v in r.modules_s.items() if "stage_step" in k)
    assert 0 < stage <= r.busy_s * 1.001
    convert = sum(v for k, v in r.ops_s.items() if k.startswith("%convert"))
    assert 0 < convert < stage
    assert sum(r.ops_s.values()) >= r.busy_s
