"""The failover cell: its plain reference agrees with the program under
crashes, drops and stragglers and, with no faults, with the fault-free
reference; a CPU run crashes and replays every boundary; faults on the
crash path read ``correct`` false; a program without ``faults=`` is
refused at once."""
import numpy as np
import pytest

import common
import deploy
from cells import run_cpu
from common import BenchError
from reference.faults import simulate_fleet_faults
from reference.sim import simulate_fleet
from reference.smdp import solve_point

CELL = "cmdr.fleet.failover"
M = 8


def _lane(seed, n=3000):
    cfg = common.load_json(common.BENCH / "configs" / "googlenet-p4.json")
    lat, zeta = deploy.profile(cfg)
    lam = deploy.arrival_rate(cfg, 0.7)
    table = solve_point(lam, lat, zeta, 1, 32, 1.0, 1.0, 128)["policy"][:129]
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / (M * lam), n)), table, lat, zeta


def _schedule(span, kind):
    """Bounds and multipliers: one outage on replica 0, which is busy most
    of the time, or a crash, a repair and at once a second crash of the
    retried batch; stragglers on every replica."""
    rng = np.random.default_rng(7)
    bounds = np.full((M, 4), np.inf)
    if kind == "outage":
        bounds[0, :2] = (0.3 * span, 0.3 * span + 40.0)
        bounds[5, :2] = (0.6 * span, 0.7 * span)
    else:
        t = 0.4 * span
        bounds[0] = (t, t + 20.0, t + 20.5, t + 40.0)
    mult = np.where(rng.random((M, 512)) < 0.05, 3.0, 1.0)
    return bounds, mult


@pytest.mark.parametrize("router", ["jsq", "batch_aware"])
@pytest.mark.parametrize("kind,max_retries", [("outage", 2), ("retry", 1)])
def test_fault_reference_equals_program(router, kind, max_retries):
    from repro.serving import FaultSchedule, pad_arrivals_batch
    from repro.serving import simulate_fleet as program

    arr, table, lat, zeta = _lane(11)
    bounds, mult = _schedule(arr[-1], kind)
    sch = FaultSchedule(bounds=bounds, mult=mult, max_retries=max_retries)
    got = program(np.tile(table[None], (M, 1)), pad_arrivals_batch([arr])[0],
                  router=router, means=lat, zeta=zeta, b_max=32, faults=sch)
    ref = simulate_fleet_faults(arr, table, M, router, lat, zeta, 32, bounds,
                                mult, max_retries)
    assert ref["n_crashes"] > 0 and ref["near_ties"] == 0
    if kind == "retry":
        assert ref["n_dropped"] > 0
    for k in ("n_served", "n_batches", "n_epochs", "n_crashes", "n_dropped"):
        assert int(getattr(got, k)) == ref[k], k
    for k in ("t_final", "energy", "lat_sum"):
        assert abs(getattr(got, k) / ref[k] - 1) < 1e-12, k
    low = simulate_fleet_faults(arr, table, M, router, lat, zeta, 32, bounds,
                                mult, max_retries, dtype=np.float32)
    assert abs(low["lat_sum"] / ref["lat_sum"] - 1) > 1e-9


@pytest.mark.parametrize("router", ["jsq", "batch_aware"])
def test_empty_schedule_is_the_fault_free_reference(router):
    arr, table, lat, zeta = _lane(12)
    plain = simulate_fleet(arr, table, M, router, lat, zeta, 32)
    empty = simulate_fleet_faults(arr, table, M, router, lat, zeta, 32,
                                  np.zeros((M, 0)), np.ones((M, 1)), 2)
    assert empty == dict(plain, n_crashes=0, n_dropped=0)


def test_cpu_run_crashes_and_replays_every_boundary():
    sink = []
    out = run_cpu(CELL, sink=sink)
    assert out["correct"], out["checks"]
    counters = sink[0].counters()
    lanes = common.cell(common.benchmark(), CELL)[2]["cpu_test"]["lanes"]
    assert counters["calls"] >= 1
    assert all(c > 0 for c in counters["crashes"])
    assert counters["fault_bounds"] == [2 * lanes] * counters["calls"]
    assert counters["unreplayed"] == [0] * counters["calls"]
    assert counters["steps_per_request"] > 2


def _plant(kind):
    def plant(entry):
        run = entry.program["run_fleet_grid_faults"]

        def broken(*a, **k):
            if kind == "ignored":  # the schedule never reaches the kernel
                k["faults"] = None
            out = {k2: np.array(v) for k2, v in run(*a, **k).items()}
            if kind == "uncounted":
                out["n_crashes"] = np.zeros_like(out["n_crashes"])
            return out

        entry.program["run_fleet_grid_faults"] = broken

    return plant


@pytest.mark.parametrize("kind", ["uncounted", "ignored"])
def test_crash_path_fault_is_caught(kind):
    out = run_cpu(CELL, plant=_plant(kind))
    assert not out["correct"], out["checks"]
    assert out["checks"]["crash_mismatch"]["value"] > 0


def test_program_without_faults_is_refused(monkeypatch):
    import repro.serving

    def run_fleet_grid(tables, arrivals, **kw):
        raise AssertionError("never called")

    monkeypatch.setattr(repro.serving, "run_fleet_grid", run_fleet_grid)
    _, cfg, mix = common.cell(common.benchmark(), CELL)
    with pytest.raises(BenchError, match="faults="):
        common.make_entry(cfg, mix, 1, test=True)
