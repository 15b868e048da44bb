"""spans: device busy and idle time split along the program's host spans."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

import spans
import trace_reduce
from cells import run_cpu

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000

SOLVE = ["solve_idle_ms.build", "solve_idle_ms.evaluate", "solve_idle_ms.rvi",
         "solve_idle_ms.sweep", "f64_share.solve"]
SIM = ["scan_fill.sim", "wrapper_idle_ms.sim"]


def _span(name, start, end, **args):
    return (name, start * MS, end * MS, args)


def _busy(*ivals):
    return [[s * MS, e * MS] for s, e in ivals]


def _idle(out, name):
    rec = out["outside"] if name == "outside" else out["spans"][name]
    return round(rec["idle_s"] * 1e3, 9)


def test_innermost_span_takes_the_time():
    sp = [_span("repro.a", 10, 90), _span("repro.b", 20, 40)]
    out = spans.attribute((0, 100 * MS), sp, _busy((0, 10), (50, 60)))
    # idle [10, 20] a, [20, 40] b, [40, 50] a, [60, 90] a, [90, 100] outside
    assert _idle(out, "repro.a") == 50 and _idle(out, "repro.b") == 20
    assert _idle(out, "outside") == 10
    assert out["spans"]["repro.a"]["busy_s"] == pytest.approx(0.010)
    assert out["outside"]["busy_s"] == pytest.approx(0.010)


def test_nested_spans_split_one_gap():
    sp = [_span("repro.a", 0, 100), _span("repro.b", 20, 30),
          _span("repro.c", 40, 45)]
    out = spans.attribute((0, 100 * MS), sp, _busy((0, 10), (50, 100)))
    # the one gap [10, 50]: b its [20, 30], c its [40, 45], a the rest
    assert _idle(out, "repro.b") == 10 and _idle(out, "repro.c") == 5
    assert _idle(out, "repro.a") == 25 and _idle(out, "outside") == 0
    assert out["idle_s"] == pytest.approx(0.040)


def test_parts_sum_to_the_window_idle():
    sp = [_span("repro.a", 5, 35), _span("repro.b", 8, 12),
          _span("repro.a", 50, 70), _span("repro.b", 55, 56),
          _span("repro.c", 56, 60)]
    busy = _busy((1, 2), (9, 11), (30, 52), (57, 58), (80, 90))
    out = spans.attribute((0, 100 * MS), sp, busy)
    parts = sum(r["idle_s"] for r in out["spans"].values()) + out["outside"]["idle_s"]
    assert parts == pytest.approx(out["idle_s"]) == pytest.approx(0.100 - 0.036)
    busy_parts = sum(r["busy_s"] for r in out["spans"].values()) + out["outside"]["busy_s"]
    assert busy_parts == pytest.approx(0.036)


def test_time_under_no_span_is_outside():
    out = spans.attribute((0, 100 * MS), [], _busy((10, 20)))
    assert out["spans"] == {} and _idle(out, "outside") == 90
    # a span of another program phase before the window counts nowhere
    out = spans.attribute((0, 100 * MS), [_span("repro.a", -50, -10)], [])
    assert out["spans"] == {} and _idle(out, "outside") == 100


def test_counts_arguments_and_stalls():
    sp = [_span("repro.grid.post", 0, 10, steps_run=8, steps_used=6),
          _span("repro.grid.post", 20, 60, steps_run=8, steps_used=7),
          _span("repro.grid.run", 60, 70, steps_run=8, tag="x")]
    out = spans.attribute((0, 100 * MS), sp, _busy((5, 10), (62, 70)))
    post = out["spans"]["repro.grid.post"]
    assert post["count"] == 2 and post["args"] == {"steps_run": 16, "steps_used": 13}
    assert post["idle_max_s"] == pytest.approx(0.040)
    assert post["empty"] == 1  # the device ran nothing under the second
    assert out["spans"]["repro.grid.run"]["args"] == {"steps_run": 8}


def test_a_span_outlasting_its_parent_is_cut():
    sp = [_span("repro.a", 0, 50), _span("repro.b", 40, 60)]
    segs = spans.segments(0, 100 * MS, sp)
    assert [(a // MS, b // MS, i) for a, b, i in segs] == [
        (0, 40, 0), (40, 50, 1), (50, 100, -1)]


def test_kernels_by_span():
    sp = [_span("repro.rvi.f32", 0, 50), _span("repro.rvi.f64", 50, 90)]
    kernels = [("jit__rvi_loop_batched", 10 * MS, 40 * MS),
               ("jit__rvi_loop_batched", 55 * MS, 95 * MS)]
    out = spans.attribute((0, 100 * MS), sp, _busy((10, 40), (55, 95)), kernels)
    k = out["kernels"]["jit__rvi_loop_batched"]
    assert k == {"repro.rvi.f32": pytest.approx(0.030),
                 "repro.rvi.f64": pytest.approx(0.035),
                 "outside": pytest.approx(0.005)}


def test_metrics():
    sp = [_span("repro.sweep.solve", 0, 40), _span("repro.smdp.build", 0, 10),
          _span("repro.evaluate.greedy", 10, 14), _span("repro.rvi.solve", 14, 30),
          _span("repro.rvi.f32", 15, 22), _span("repro.rvi.f64", 22, 28),
          _span("repro.evaluate.batched", 30, 38),
          _span("repro.sweep.solve", 50, 90)]
    busy = _busy((16, 20), (23, 26))
    out = spans.attribute((0, 100 * MS), sp, busy)
    read = spans.metrics(out)
    assert sorted(read) == sorted(SOLVE)
    assert read["solve_idle_ms.build"] == pytest.approx(5.0)  # 10 ms, 2 grids
    assert read["solve_idle_ms.evaluate"] == pytest.approx(6.0)
    assert read["solve_idle_ms.rvi"] == pytest.approx(4.5)  # 16 - 7 busy
    assert read["solve_idle_ms.sweep"] == pytest.approx(21.0)  # 2 + 40
    assert read["f64_share.solve"] == pytest.approx(300 / 7)
    # the parts and the time outside make up the window's idle
    outside = out["outside"]["idle_s"] * 1e3
    total = sum(read[m] for m in SOLVE[:4]) * 2 + outside
    assert total == pytest.approx(out["idle_s"] * 1e3)

    sp = [_span("repro.fleet.prepare", 0, 10, lanes=2),
          _span("repro.fleet.run", 10, 80, steps_run=1024),
          _span("repro.fleet.post", 80, 85, steps_run=1024, steps_used=768)]
    out = spans.attribute((0, 100 * MS), sp, _busy((5, 80)))
    read = spans.metrics(out)
    assert sorted(read) == sorted(SIM)
    assert read["scan_fill.sim"] == pytest.approx(75.0)
    assert read["wrapper_idle_ms.sim"] == pytest.approx(10.0)


@pytest.mark.parametrize("fixture", ["device_mode.xplane.pb", "host_mode.xplane.pb"])
def test_nothing_without_program_spans(fixture):
    """A program that opens no spans: no numbers, and no error; the busy
    union is the reduction's."""
    path = str(DATA / fixture)
    red = trace_reduce.reduce(path)
    out = spans.read(path, red)
    assert out["spans"] == {} and spans.metrics(out) == {}
    assert out["outside"]["busy_s"] == pytest.approx(red["busy_s"])
    assert out["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"])


def test_recorded_trace_with_program_spans():
    """A v5e trace of one sweep_solve call over four operating points, the
    program's spans on: the device's work falls inside them, on one clock."""
    path = str(DATA / "spans_device_mode.xplane.pb")
    red = trace_reduce.reduce(path)
    out = spans.read(path, red)
    names = set(out["spans"])
    assert {"repro.sweep.solve", "repro.smdp.build", "repro.evaluate.greedy",
            "repro.evaluate.batched", "repro.rvi.solve", "repro.rvi.f32",
            "repro.rvi.f64"} <= names
    assert out["spans"]["repro.sweep.solve"]["count"] == 1
    assert out["spans"]["repro.smdp.build"]["args"] == {"specs": 4, "s_max": 128}
    # the lockstep loops run inside the f32 and f64 spans, and each of
    # those spans holds device work
    loop = out["kernels"]["jit__rvi_loop_batched"]
    inside = loop.get("repro.rvi.f32", 0) + loop.get("repro.rvi.f64", 0)
    secs, n = trace_reduce.kernel_seconds(red, "_rvi_loop_batched")
    assert n == 2 and inside >= 0.95 * secs
    for name in ("repro.rvi.f32", "repro.rvi.f64"):
        assert out["spans"][name]["count"] == 1
        assert out["spans"][name]["empty"] == 0
    # the split covers the window: busy and idle as the reduction has them
    busy = sum(r["busy_s"] for r in out["spans"].values()) + out["outside"]["busy_s"]
    assert busy == pytest.approx(red["busy_s"])
    assert out["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"])
    assert out["outside"]["idle_s"] < 0.1 * out["idle_s"]
    read = spans.metrics(out)
    assert sorted(read) == sorted(SOLVE)
    assert all(v > 0 for v in read.values())
    assert 0 < read["f64_share.solve"] < 100
    assert sum(read[m] for m in SOLVE[:4]) + out["outside"]["idle_s"] * 1e3 == (
        pytest.approx(out["idle_s"] * 1e3))


@pytest.mark.parametrize("name,post", [("p4.sim.poisson", "repro.grid.post"),
                                       ("cmdr.fleet.bursty", "repro.fleet.post")])
def test_capture_splits_the_harness_window(name, post):
    """A traced CPU run of a simulation cell through the harness, split
    by ``capture`` before the harness deletes its trace; the harness's
    reduction is restored after."""
    reduce = trace_reduce.reduce
    with spans.capture() as found:
        out = run_cpu(name, trace=1)
    assert trace_reduce.reduce is reduce
    assert out["correct"] and len(found) == 1
    sp = found[0]
    calls = sp["spans"][post]["count"]
    assert calls >= 1 and sp["spans"][post.replace("post", "prepare")]["count"] == calls
    args = sp["spans"][post]["args"]
    assert args["steps_run"] >= args["steps_used"] > 0
    assert 0 < spans.metrics(sp)["scan_fill.sim"] <= 100


def test_cli_refuses_without_a_chip():
    p = subprocess.run(
        [sys.executable, str(Path(spans.__file__)), "--workload", "cmdr.solve.light",
         "--seed", "3", "--seconds", "1"],
        cwd=Path(spans.__file__).parents[1], capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
