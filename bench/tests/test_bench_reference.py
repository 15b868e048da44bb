"""The plain reference agrees with the program where both are sound, and
its float32 control does not."""
import numpy as np
import pytest

import common
import deploy
from reference.sim import simulate_fleet, simulate_single, threshold_gaps
from reference.smdp import solve_point


def _cfg(name):
    return common.load_json(common.BENCH / "configs" / f"{name}.json")


@pytest.mark.parametrize("config,rho,w2", [
    ("googlenet-p4", 0.2, 0.5), ("googlenet-p4", 0.9, 4.0),
    ("command-r-plus-104b-decode", 0.3, 2.0),
])
def test_reference_tables_equal_sweep(config, rho, w2):
    from repro.core import sweep_solve

    cfg = _cfg(config)
    lat, zeta = deploy.profile(cfg)
    res = sweep_solve([deploy.program_spec(cfg, rho, w2)])[0]
    ref = solve_point(deploy.arrival_rate(cfg, rho), lat, zeta, cfg["b_min"],
                      cfg["b_max"], cfg["w1"], w2, cfg["s_max"])
    assert res.spec.s_max == ref["s_max"]
    np.testing.assert_array_equal(res.rvi.policy, ref["policy"])
    assert abs(res.eval.w_bar / ref["w_bar"] - 1) < 1e-10
    assert abs(res.eval.p_bar / ref["p_bar"] - 1) < 1e-10
    lo = solve_point(deploy.arrival_rate(cfg, rho), lat, zeta, cfg["b_min"],
                     cfg["b_max"], cfg["w1"], w2, cfg["s_max"], dtype=np.float32)
    assert abs(lo["w_bar"] / ref["w_bar"] - 1) > 1e-9


def test_decode_profile_from_published_widths():
    cfg = _cfg("command-r-plus-104b-decode")
    assert deploy.param_count(cfg["model"]) == 103_809_024_000
    lat, zeta = deploy.profile(cfg)
    # weight-bound: 32 tokens x 207.6 GB / (32 chips x 819 GB/s) ~ 253 ms
    assert 250 < lat[1] < 260 and lat[32] / lat[1] < 1.35
    assert np.all(np.diff(lat[1:]) >= 0) and np.all(np.diff(zeta[1:]) > 0)


def _arrivals(lam, n, seed):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / lam, n))


@pytest.mark.parametrize("router", [None, "jsq", "batch_aware"])
def test_reference_simulators_equal_program(router):
    from repro.serving import pad_arrivals_batch, run_fleet_grid, run_grid

    cfg = _cfg("googlenet-p4")
    lat, zeta = deploy.profile(cfg)
    lam = deploy.arrival_rate(cfg, 0.7)
    table = solve_point(lam, lat, zeta, 1, 32, 1.0, 1.0, 128)["policy"][:129]
    m = 1 if router is None else 8
    arr = pad_arrivals_batch([_arrivals(m * lam, 3000, s) for s in (1, 2)])
    if router is None:
        out = run_grid(table[None], arr, means=lat, zeta=zeta, b_max=32)
    else:
        out = run_fleet_grid(table[None], arr, routers=(router,),
                             n_replicas=8, means=lat, zeta=zeta, b_max=32)
    for lane in range(2):
        if router is None:
            ref = simulate_single(arr[lane], table, lat, zeta, 32)
        else:
            ref = simulate_fleet(arr[lane], table, 8, router, lat, zeta, 32)
        for k in ("n_served", "n_batches", "n_epochs"):
            assert int(np.ravel(out[k][lane])[0]) == ref[k], k
        for k in ("t_final", "energy", "lat_sum"):
            assert abs(np.ravel(out[k][lane])[0] / ref[k] - 1) < 1e-12, k


def test_threshold_gaps_match_program():
    from repro.serving.fleet import threshold_gaps as program_gaps

    rng = np.random.default_rng(0)
    for _ in range(5):
        table = np.where(rng.random(40) < 0.4, 0, rng.integers(1, 9, 40))
        np.testing.assert_array_equal(
            program_gaps(table[None])[0, 0], threshold_gaps(table))
