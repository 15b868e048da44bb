"""Program spans: the named host spans (jax.profiler.TraceAnnotation) that
the benchmark's trace reduction attributes device time to.

A two-spec sweep_solve, a run_grid and a run_fleet_grid call are profiled
on the CPU: the spans nest as the attribution expects, the simulators'
``post`` spans count the scan steps they ran and used, and every result
is bitwise the same with the profiler on and off.
"""
import dataclasses
import glob

import jax
import numpy as np
import pytest

from repro.core import (
    GOOGLENET_P4_ENERGY,
    GOOGLENET_P4_LATENCY,
    ServiceModel,
    SMDPSpec,
    sweep_solve,
)
from repro.serving import pad_arrivals_batch, run_fleet_grid, run_grid

SVC = ServiceModel(latency=GOOGLENET_P4_LATENCY, family="det")
BMAX = 32
MEANS = np.array([0.0] + [float(SVC.mean(b)) for b in range(1, BMAX + 1)])
ENERGY = np.array([0.0] + [float(GOOGLENET_P4_ENERGY(b)) for b in range(1, BMAX + 1)])


def _spec(rho):
    lam = rho * BMAX / float(SVC.mean(BMAX))
    return SMDPSpec(
        lam=lam, service=SVC, energy=GOOGLENET_P4_ENERGY, b_min=1,
        b_max=BMAX, w1=1.0, w2=1.0, s_max=64, c_o=100.0,
    )


def _arrivals(lanes, per_lane, rate, seed=0):
    rng = np.random.default_rng(seed)
    return pad_arrivals_batch(
        [np.cumsum(rng.exponential(1.0 / rate, per_lane)) for _ in range(lanes)],
        size=per_lane + 4,
    )


def _profiled(tmp_path, fn):
    """fn()'s result and the program spans [(name, start, end, args,
    thread)] of a profile around it."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for t, line in enumerate(plane.lines):
                spans += [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats), (plane.name, t))
                    for ev in line.events if ev.name.startswith("repro.")
                ]
    return out, spans


def _inside(inner, outer):
    return inner[4] == outer[4] and outer[1] <= inner[1] and inner[2] <= outer[2]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _same(a, b):
    """Bitwise equality of nested results (NaN equal to NaN)."""
    if isinstance(a, dict):
        assert a.keys() == b.keys()
        for k in a:
            _same(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _same(x, y)
    elif dataclasses.is_dataclass(a):
        for f in dataclasses.fields(a):
            if f.name != "wall_time_s":  # the host clock, never the answer
                _same(getattr(a, f.name), getattr(b, f.name))
    elif isinstance(a, (np.ndarray, np.generic, float, int, bool)):
        x, y = np.asarray(a), np.asarray(b)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()
    else:
        assert a == b


def test_sweep_spans_nest_and_leave_results_unchanged(tmp_path):
    specs = [_spec(0.1), _spec(0.2)]
    plain = sweep_solve(specs)
    traced, spans = _profiled(tmp_path, lambda: sweep_solve(specs))
    _same(plain, traced)
    (sweep,) = _named(spans, "repro.sweep.solve")
    assert sweep[3]["specs"] == 2
    builds = _named(spans, "repro.smdp.build")
    solves = _named(spans, "repro.rvi.solve")
    assert builds and solves
    assert builds[0][3] == {"specs": 2, "s_max": 64}
    for child in builds + solves + _named(spans, "repro.evaluate.greedy") + (
        _named(spans, "repro.evaluate.batched")
    ):
        assert _inside(child, sweep), child[0]
    # every loop runs inside a solve: the coarse float32 loop first, then
    # the float64 finish
    f32, f64 = _named(spans, "repro.rvi.f32"), _named(spans, "repro.rvi.f64")
    assert f32 and len(f32) == len(f64)
    for a, b in zip(sorted(f32, key=lambda s: s[1]), sorted(f64, key=lambda s: s[1])):
        assert a[2] <= b[1]
        assert any(_inside(a, s) and _inside(b, s) for s in solves)


def test_mixed_precision_off_runs_float64_only(tmp_path):
    from repro.core import build_smdp_batched
    from repro.core.rvi import relative_value_iteration_batched

    batch = build_smdp_batched([_spec(0.2)])
    _, spans = _profiled(
        tmp_path,
        lambda: relative_value_iteration_batched(batch, mixed_precision=False),
    )
    assert [s[0] for s in sorted(spans, key=lambda s: s[1])] == [
        "repro.rvi.solve", "repro.rvi.f64"]


@pytest.mark.parametrize("kind", ["grid", "fleet"])
def test_simulator_spans_count_steps(tmp_path, kind):
    table = np.asarray(sweep_solve([_spec(0.7)])[0].policy)[None]
    if kind == "grid":
        arr = _arrivals(4, 4092, 0.7 * BMAX / float(SVC.mean(BMAX)))

        def call():
            return run_grid(table, arr, means=MEANS, zeta=ENERGY, b_max=BMAX)
    else:
        arr = _arrivals(2, 4092, 8 * 0.7 * BMAX / float(SVC.mean(BMAX)))

        def call():
            return run_fleet_grid(
                table, arr, routers=("jsq", "batch_aware"), n_replicas=8,
                means=MEANS, zeta=ENERGY, b_max=BMAX,
            )

    call()  # compiles; run_grid also learns its scan length here
    plain = call()
    traced, spans = _profiled(tmp_path, call)
    _same(plain, traced)
    assert [s[0] for s in sorted(spans, key=lambda s: s[1])] == [
        f"repro.{kind}.prepare", f"repro.{kind}.run", f"repro.{kind}.post"]
    (prep,) = _named(spans, f"repro.{kind}.prepare")
    assert prep[3]["lanes"] == arr.shape[0]
    (run,) = _named(spans, f"repro.{kind}.run")
    (post,) = _named(spans, f"repro.{kind}.post")
    assert post[3]["steps_run"] == run[3]["steps_run"]
    assert post[3]["steps_run"] >= post[3]["steps_used"] > 0
    assert post[3]["steps_used"] == int(np.max(traced["n_steps_used"]))
    # both loops stop once no instance is active
    assert (post[3]["steps_used"] <= post[3]["steps_executed"]
            <= post[3]["steps_run"])
    assert post[3]["steps_executed"] == traced["steps_executed"]


def test_faulted_fleet_spans_count_faults(tmp_path):
    """A faulted fleet grid: ``prepare`` counts the lanes' finite fault
    boundaries, ``post`` the grid's crashed attempts and dropped requests,
    and the results are bitwise the same with the profiler on and off."""
    from repro.serving import FaultSchedule

    table = np.asarray(sweep_solve([_spec(0.7)])[0].policy)[None]
    arr = _arrivals(2, 4092, 8 * 0.7 * BMAX / float(SVC.mean(BMAX)))
    span = float(arr[0, 4091])
    inf = np.inf
    bounds = np.full((8, 4), inf)
    bounds[0] = (0.3 * span, 0.3 * span + 20.0, 0.3 * span + 20.5, inf)
    faults = [
        FaultSchedule(bounds=bounds, mult=np.ones((8, 1)), max_retries=0),
        FaultSchedule(bounds=bounds[:, :2],
                      mult=np.where(np.arange(64) % 7 == 0, 2.0, 1.0)[None]
                      .repeat(8, axis=0), max_retries=0),
    ]

    def call():
        return run_fleet_grid(
            table, arr, routers=("jsq", "batch_aware"), n_replicas=8,
            means=MEANS, zeta=ENERGY, b_max=BMAX, faults=faults,
        )

    plain = call()
    traced, spans = _profiled(tmp_path, call)
    _same(plain, traced)
    (prep,) = _named(spans, "repro.fleet.prepare")
    (post,) = _named(spans, "repro.fleet.post")
    assert prep[3]["fault_bounds"] == 5
    assert post[3]["crashes"] == int(traced["n_crashes"].sum()) > 0
    assert post[3]["dropped"] == int(traced["n_dropped"].sum()) > 0
