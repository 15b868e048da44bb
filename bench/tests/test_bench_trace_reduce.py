"""trace_reduce: busy union, module times, gap attribution."""
from pathlib import Path

import pytest

import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def test_reduction_of_known_events(monkeypatch):
    ms = 1_000_000
    devices = {"/device:TPU:0": {
        "XLA Modules": [("jit__grid_jit(7)", 10 * ms, 40 * ms),
                        ("jit__grid_jit(7)", 60 * ms, 90 * ms),
                        ("jit_other(3)", 95 * ms, 130 * ms)],
        "XLA Ops": [("fusion.1", 10 * ms, 30 * ms), ("while.2", 25 * ms, 40 * ms),
                    ("fusion.1", 60 * ms, 90 * ms), ("copy.3", 95 * ms, 130 * ms)],
    }}
    spans = [("bench.window", 0, 120 * ms), ("bench.sample", 0, 9 * ms),
             ("bench.pad", 40 * ms, 58 * ms), ("bench.dispatch", 58 * ms, 120 * ms)]
    monkeypatch.setattr(trace_reduce, "events",
                        lambda path: (devices, spans, [], []))
    red = trace_reduce.reduce("unused")
    assert red["window_s"] == pytest.approx(0.120)
    # busy: [10, 40] + [60, 90] + [95, 120] clipped to the window
    assert red["busy_s"] == pytest.approx(0.085)
    assert red["modules"]["jit__grid_jit"] == {"seconds": pytest.approx(0.060),
                                               "count": 2}
    assert red["modules"]["jit_other"]["seconds"] == pytest.approx(0.025)
    assert red["ops"]["fusion.1"] == pytest.approx(0.050)
    assert trace_reduce.kernel_seconds(red, "_grid_jit") == (
        pytest.approx(0.060), 2)
    # gaps: [0, 10] sample, [40, 60] pad (18 of 20 ms), [90, 95] dispatch
    assert [(n, round(s, 6)) for n, s in red["idle_gaps"]] == [
        ("bench.pad", 0.02), ("bench.sample", 0.01), ("bench.dispatch", 0.005)]
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0][0] == "fusion.1" and len(bd["idle_gaps"]) == 3


def test_host_mode_executions(monkeypatch):
    ms = 1_000_000
    spans = [("bench.window", 0, 100 * ms), ("bench.dispatch", 5 * ms, 50 * ms),
             ("bench.dispatch", 50 * ms, 100 * ms)]
    # two conversions and the kernel in the first dispatch; the kernel
    # and its re-dispatch at a longer scan in the second, each launched
    # inside the dispatch of its jitted function
    execs = [(6 * ms, 7 * ms), (7 * ms, 8 * ms), (8 * ms, 40 * ms),
             (51 * ms, 60 * ms), (60 * ms, 95 * ms)]
    pjit = [(5 * ms, 7 * ms, "convert_element_type"),
            (6.5 * ms, 7.5 * ms, "convert_element_type"),
            (7.5 * ms, 9 * ms, "_grid_jit"), (50 * ms, 52 * ms, "_grid_jit"),
            (59 * ms, 61 * ms, "_grid_jit"), (60 * ms, 60.5 * ms, "_inner")]
    monkeypatch.setattr(trace_reduce, "events",
                        lambda path: ({}, spans, execs, pjit))
    red = trace_reduce.reduce("unused")
    assert [x[4] for x in red["executions"]] == [
        "convert_element_type", "convert_element_type", "_grid_jit",
        "_grid_jit", "_inner"]
    assert red["busy_s"] == pytest.approx(0.078)
    secs, n = trace_reduce.kernel_seconds(red, "_grid_jit")
    assert n == 2 and secs == pytest.approx(0.041)
    # a kernel that no execution ran: nothing to report
    assert trace_reduce.kernel_seconds(red, "_fleet_grid_core") == (0, 0)
    assert red["idle_gaps"][0] == ("bench.dispatch", pytest.approx(0.011))
    ops = dict(trace_reduce.breakdown(red)["device_ops"])
    assert ops["_grid_jit in bench.dispatch"] == pytest.approx(0.041)


def test_pairing_drops_executions_launched_before_the_trace():
    assert trace_reduce._pair([10, 20], [5, 15, 30]) == [(10, 15), (20, 30)]


def test_op_names_are_short():
    assert trace_reduce.op_name(
        "%fusion.12 = f32[8]{0:T(128)} fusion(f32[8]{0} %p), kind=kLoop") == (
        "%fusion.12 fusion")
    assert trace_reduce.op_name(
        "%while.8 = (u32[], f32[2]{0}) while((u32[], f32[2]{0}) %t)") == (
        "%while.8 while")


def test_recorded_host_mode_trace():
    """A v5e trace of one run_grid call at 4 lanes x 4096 slots."""
    red = trace_reduce.reduce(str(DATA / "host_mode.xplane.pb"))
    assert 0 < red["busy_s"] < red["window_s"]
    assert {who for _, _, who, _, _ in red["executions"]} == {"bench.dispatch"}
    assert sorted(fn for *_, fn in red["executions"]) == [
        "_grid_jit", "broadcast_in_dim", "convert_element_type"]
    secs, n = trace_reduce.kernel_seconds(red, "_grid_jit")
    assert n == 1 and 0.5 * red["busy_s"] < secs <= red["busy_s"]
    assert {w for w, _ in red["idle_gaps"]} <= {"bench.dispatch", "other"}


def test_recorded_device_mode_trace():
    """A v5e trace of one sweep_solve call over one operating point."""
    red = trace_reduce.reduce(str(DATA / "device_mode.xplane.pb"))
    assert red["devices"] == 1 and 0 < red["busy_s"] < red["window_s"]
    secs, n = trace_reduce.kernel_seconds(red, "_rvi_loop_batched")
    # the f32 lockstep loop and its f64 finish: most of the busy time
    assert n == 2 and 0.9 * red["busy_s"] < secs <= red["window_s"]
    ops = trace_reduce.breakdown(red)["device_ops"]
    assert ops[0][0].endswith(" while") and len(ops) == 10
    assert {w for w, _ in red["idle_gaps"]} <= {"bench.sweep_solve", "other"}


def test_window_span_is_required(monkeypatch):
    monkeypatch.setattr(trace_reduce, "events", lambda path: ({}, [], [], []))
    with pytest.raises(ValueError):
        trace_reduce.reduce("unused")
