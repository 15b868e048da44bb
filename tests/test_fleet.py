"""Fleet serving lane: M=1 equivalence with the single-server compiled
kernel per arrival mode, Python-reference router agreement per routing
policy, conservation/dominance invariants, snapshot()/restore() through
router state, chunked streaming vs materialized record, the record-slot
cap, the count-zero metrics convention, and the mesh-sharded grid."""
import dataclasses
import os
import subprocess
import sys
from functools import partial
from pathlib import Path

import numpy as np
import pytest

from repro.core import GOOGLENET_P4_ENERGY, GOOGLENET_P4_LATENCY, ServiceModel
from repro.core.policies import q_policy
from repro.serving import (
    FaultSchedule,
    FleetStream,
    PythonFleet,
    ServingMetrics,
    histogram_quantiles,
    pad_arrivals_batch,
    run_fleet_grid,
    simulate_compiled,
    simulate_fleet,
    simulate_fleet_stream,
    threshold_gaps,
    verify_fleet,
)
from repro.serving.arrivals import MMPP2, DiurnalProcess

ROOT = Path(__file__).resolve().parent.parent

SVC = ServiceModel(latency=GOOGLENET_P4_LATENCY, family="det")
BMAX = 16
#: per-replica load ~0.7 at M=1 (each M-replica test scales lam by M)
LAM = 0.7 * BMAX / float(SVC.mean(BMAX))
ENERGY = np.array(
    [0.0] + [float(GOOGLENET_P4_ENERGY(b)) for b in range(1, BMAX + 1)]
)
MEANS = np.array([0.0] + [float(SVC.mean(b)) for b in range(1, BMAX + 1)])
TABLE = q_policy(6, 96, BMAX)
#: heterogeneous fleet: each replica its own control limit
HET_QS = (4, 6, 8, 12)
HET_TABLES = np.stack([q_policy(q, 96, BMAX) for q in HET_QS])
ROUTER_NAMES = ["rr", "jsq", "pow2", "batch_aware"]


def _trace(mode: str, n: int = 1200, seed: int = 0, lam: float = LAM):
    rng = np.random.default_rng(seed)
    if mode == "poisson":
        return np.cumsum(rng.exponential(1.0 / lam, n))
    if mode == "mmpp2":
        m = MMPP2(lam1=0.3 * lam, lam2=1.3 * lam, dwell1=60.0, dwell2=30.0)
        times, _ = m.sample_arrivals(n / m.mean_rate, rng)
        return times
    assert mode == "diurnal"
    proc = DiurnalProcess(base=lam, amp=0.6 * lam, period=120.0)
    return np.array([proc.next(rng).time for _ in range(n)])


class TestM1Equivalence:
    """ISSUE acceptance: the M=1 fleet lane is decision-for-decision
    identical to serving/compiled.py on Poisson, MMPP2, and diurnal."""

    @pytest.mark.parametrize("mode", ["poisson", "mmpp2", "diurnal"])
    def test_matches_single_server_kernel(self, mode):
        out = verify_fleet(
            TABLE, _trace(mode), router="jsq", service=SVC,
            energy_table=ENERGY, b_max=BMAX,
        )
        assert out["n_decisions"] > 0

    @pytest.mark.parametrize("router", ROUTER_NAMES)
    def test_every_router_degenerates_at_m1(self, router):
        verify_fleet(
            TABLE, _trace("poisson"), router=router, service=SVC,
            energy_table=ENERGY, b_max=BMAX,
        )

    def test_m1_bitwise_vs_compiled(self):
        tr = _trace("poisson")
        res = simulate_fleet(
            TABLE, tr, router="rr", means=MEANS, zeta=ENERGY, b_max=BMAX,
            record=True,
        )
        ref = simulate_compiled(
            TABLE, tr, means=MEANS, zeta=ENERGY, b_max=BMAX, record=True,
        )
        assert np.array_equal(res.batch_sizes, ref.actions[ref.actions > 0])
        assert np.array_equal(
            res.latencies[res.served], np.asarray(ref.latencies)
        )
        assert res.t_final == ref.t_final
        assert res.energy == ref.energy
        assert res.n_epochs == ref.n_epochs


class TestFleetVerify:
    """Python reference router loop == compiled lane, per routing policy,
    on a heterogeneous 4-replica fleet."""

    @pytest.mark.parametrize("router", ROUTER_NAMES)
    def test_router_agreement(self, router):
        out = verify_fleet(
            HET_TABLES, _trace("poisson", lam=4 * LAM), router=router,
            service=SVC, energy_table=ENERGY, b_max=BMAX, slo=3.0,
        )
        assert out["n_decisions"] > 0

    @pytest.mark.parametrize("router", ["jsq", "pow2"])
    def test_budget_and_horizon_cuts(self, router):
        tr = _trace("poisson", lam=4 * LAM)
        verify_fleet(
            HET_TABLES, tr, router=router, service=SVC,
            energy_table=ENERGY, b_max=BMAX, n_epochs=500, drain=False,
        )
        verify_fleet(
            HET_TABLES, tr, router=router, service=SVC,
            energy_table=ENERGY, b_max=BMAX,
            horizon=float(tr[len(tr) // 2]),
        )

    def test_stochastic_service_shared_draws(self):
        svc = ServiceModel(latency=GOOGLENET_P4_LATENCY, family="expo")
        verify_fleet(
            HET_TABLES, _trace("poisson", lam=4 * LAM), router="jsq",
            service=svc, energy_table=ENERGY, b_max=BMAX,
        )


class TestThresholdGaps:
    def test_control_limit_gaps(self):
        tab = q_policy(4, 16, 8)
        g = threshold_gaps(tab[None, None, :])[0, 0]
        # queue q: arrivals still needed (beyond the next one) to reach
        # the table's first serving state — 4-long countdown, then 0
        assert np.array_equal(g[:5], [3, 2, 1, 0, 0])
        assert (g[5:] == 0).all()

    def test_never_serving_row_gets_max_gap(self):
        tab = np.zeros((1, 1, 8), dtype=np.int64)
        g = threshold_gaps(tab)
        assert (g == 8).all()  # clamped to L: worst-ranked target


class TestFleetInvariants:
    def test_request_conservation_per_router(self):
        traces = [_trace("poisson", seed=s, lam=4 * LAM) for s in range(2)]
        arr = pad_arrivals_batch(traces)
        cut = float(traces[0][800])
        out = run_fleet_grid(
            np.stack([TABLE, q_policy(10, 96, BMAX)]), arr,
            routers=ROUTER_NAMES, n_replicas=4, means=MEANS, zeta=ENERGY,
            b_max=BMAX, horizon=cut, drain=False,
        )
        # admitted = routed = served + still-queued, per (S, P, R) lane
        assert (out["n_route"].sum(axis=-1) == out["n_admitted"]).all()
        assert (
            out["n_served"] + out["qlen"].sum(axis=-1) == out["n_admitted"]
        ).all()
        # the horizon cut dropped the unadmitted tail, same for every lane
        n_in = np.array([(t < cut).sum() for t in traces])
        assert (out["n_admitted"] == n_in[:, None, None]).all()

    def test_jsq_dominates_pow2_at_high_rho(self):
        """Stochastic dominance on time-averaged backlog at rho = 0.9,
        averaged over seeds: JSQ < pow2 (classic supermarket-model
        ordering; q_time_avg = lat_sum / span by Little's law).  The
        regime matters: with GoogLeNet-style sublinear batch latency,
        LESS-informed routing batches better (JSQ herds arrivals onto
        just-idled replicas, shattering batches), so the classic ordering
        needs linear per-request latency and stochastic service."""
        bmax, c, M = 4, 0.05, 8
        means = np.array([0.0] + [c * b for b in range(1, bmax + 1)])
        lam = 0.9 * M / c
        traces, draws = [], []
        for s in range(6):
            r = np.random.default_rng(s)
            traces.append(np.cumsum(r.exponential(1.0 / lam, 4000)))
            draws.append(r.exponential(1.0, 2 * 4000 + M + 8))
        out = run_fleet_grid(
            q_policy(1, 64, bmax)[None], pad_arrivals_batch(traces),
            routers=("jsq", "pow2", "rr"), n_replicas=M, means=means,
            b_max=bmax, draws=np.stack(draws),
        )
        q = out["q_time_avg"][:, 0, :].mean(axis=0)  # (R,) seed-avg
        assert q[0] < q[1], q  # jsq beats pow2
        assert q[0] < q[2], q  # ...and blind round-robin

    @pytest.mark.parametrize("router", ["pow2", "batch_aware"])
    def test_snapshot_restore_through_router_state(self, router):
        tr = _trace("poisson", lam=4 * LAM)
        fl = PythonFleet(
            HET_TABLES, tr, router=router, means=MEANS, zeta=ENERGY,
            b_max=BMAX, slo=3.0,
        )
        for _ in range(400):
            if not fl.step():
                break
        snap = fl.snapshot()
        fl.run()
        ref = (
            list(fl.decisions), fl.latencies.copy(), fl.energy,
            fl.arr_server.copy(), fl.slo_miss, fl.t,
        )
        fl.restore(snap)
        fl.run()
        assert list(fl.decisions) == ref[0]
        assert np.array_equal(fl.latencies, ref[1], equal_nan=True)
        assert fl.energy == ref[2]
        assert np.array_equal(fl.arr_server, ref[3])
        assert (fl.slo_miss, fl.t) == (ref[4], ref[5])


class TestStreaming:
    """ISSUE acceptance: chunked streaming reproduces the materialized-
    record aggregates at >= 10x the chunk size."""

    def test_stream_matches_one_shot_exactly(self):
        tr = _trace("poisson", n=6000, lam=4 * LAM)
        one = simulate_fleet(
            HET_TABLES, tr, router="jsq", means=MEANS, zeta=ENERGY,
            b_max=BMAX, slo=3.0,
        )
        st = simulate_fleet_stream(
            HET_TABLES, tr, chunk_size=512, router="jsq", means=MEANS,
            zeta=ENERGY, b_max=BMAX, slo=3.0,
        )
        assert st.n_served == one.n_served == 6000
        assert st.n_batches == one.n_batches
        assert np.isclose(st.lat_sum, one.lat_sum, rtol=1e-12)
        assert np.isclose(st.energy, one.energy, rtol=1e-12)
        assert st.slo_miss == one.slo_miss
        assert st.t_final == one.t_final
        assert np.array_equal(st.hist, one.hist)

    def test_p2_quantiles_within_sketch_tolerance(self):
        # homogeneous fleet: a heterogeneous one has multimodal latency,
        # where the P2 marker sketch is known-biased at the tails
        tabs = np.tile(TABLE[None], (4, 1))
        tr = _trace("poisson", n=6000, lam=4 * LAM)
        one = simulate_fleet(
            tabs, tr, router="jsq", means=MEANS, b_max=BMAX, record=True
        )
        true_q = np.percentile(one.latencies[one.served], [50, 95])
        fs = FleetStream(tabs, router="jsq", means=MEANS, b_max=BMAX)
        for lo in range(0, len(tr), 512):
            fs.push(tr[lo:lo + 512])
        res = fs.finish()
        rep = fs.report()
        hq = histogram_quantiles(res.hist, res.hist_edges, [0.5, 0.95])
        for sketch in (rep["P50"], hq[0]):
            assert abs(sketch - true_q[0]) / true_q[0] < 0.05
        for sketch in (rep["P95"], hq[1]):
            assert abs(sketch - true_q[1]) / true_q[1] < 0.05
        assert rep["W_mean"] == pytest.approx(res.lat_sum / res.n_served)

    def test_pow2_stream_shares_router_uniforms(self):
        tr = _trace("poisson", n=3000, lam=4 * LAM)
        ru = np.random.default_rng(5).random((len(tr), 2))
        one = simulate_fleet(
            HET_TABLES, tr, router="pow2", means=MEANS, b_max=BMAX,
            router_u=ru,
        )
        st = simulate_fleet_stream(
            HET_TABLES, tr, chunk_size=700, router="pow2", means=MEANS,
            b_max=BMAX, router_u=ru,
        )
        assert st.n_batches == one.n_batches
        assert np.isclose(st.lat_sum, one.lat_sum, rtol=1e-12)
        assert np.array_equal(st.n_routed, one.n_routed)


class TestRecordSlotCap:
    def test_cap_raises_with_streaming_pointer(self):
        arr = np.cumsum(np.full(200, 0.01))
        with pytest.raises(ValueError, match="FleetStream"):
            simulate_compiled(
                TABLE, arr, means=MEANS, b_max=BMAX, record=True,
                max_record_slots=64,
            )

    def test_cap_ignores_aggregate_only_runs(self):
        arr = np.cumsum(np.full(200, 0.01))
        res = simulate_compiled(
            TABLE, arr, means=MEANS, b_max=BMAX, record=False,
            max_record_slots=64,
        )
        assert res.n_served == 200


class TestCountZeroMetrics:
    """ISSUE satellite: empty / single-event lanes report NaN with count
    zero, on both the Python sketches and the compiled aggregate path."""

    def test_serving_metrics_empty(self):
        rep = ServingMetrics().report()
        for k in ("W_mean", "P50", "P95", "P99", "mean_batch"):
            assert np.isnan(rep[k]), k
        assert rep["n_served"] == 0.0

    def test_serving_metrics_single_event(self):
        m = ServingMetrics()
        m.observe_batch([1.5], zeta=2.0, t_now=3.0)
        rep = m.report()
        assert rep["W_mean"] == 1.5 and rep["P50"] == 1.5
        assert rep["mean_batch"] == 1.0

    def test_histogram_quantiles_empty_and_poisoned(self):
        edges = np.linspace(0.0, 10.0, 9)
        assert np.isnan(
            histogram_quantiles(np.zeros(10), edges, [0.5, 0.99])
        ).all()
        bad = np.zeros(10)
        bad[3] = np.nan
        assert np.isnan(histogram_quantiles(bad, edges, [0.5])).all()

    def test_starved_lane_compiled_path(self):
        # horizon before the first arrival: nothing admitted or served
        tr = 10.0 + np.cumsum(np.full(50, 0.1))
        out = run_fleet_grid(
            TABLE[None], pad_arrivals_batch([tr]), routers=("jsq",),
            n_replicas=2, means=MEANS, zeta=ENERGY, b_max=BMAX,
            horizon=1.0, drain=False,
        )
        assert out["n_served"][0, 0, 0] == 0
        assert np.isnan(out["w_mean"][0, 0, 0])
        assert np.isnan(out["power"][0, 0, 0])
        assert np.isnan(
            histogram_quantiles(
                out["hist"][0, 0, 0], out["hist_edges"], [0.5]
            )
        ).all()

    def test_starved_replicas_in_fleet(self):
        # 2 arrivals round-robined across 4 replicas: two never serve
        res = simulate_fleet(
            np.tile(TABLE[None], (4, 1)), np.array([0.1, 0.2]),
            router="rr", means=MEANS, zeta=ENERGY, b_max=BMAX,
        )
        assert res.n_served == 2
        assert (res.n_served_m == [1, 1, 0, 0]).all()
        assert int(res.hist.sum()) == 2


class TestFleetBeliefLane:
    """phase_mode="belief_argmax" lowers the posterior to the fleet's
    phase stream — same plumbing as simulate_compiled's belief lane."""

    def _stack_and_beliefs(self, n=900, seed=31):
        from repro.serving.arrivals import PhaseBeliefFilter, belief_forward_jax

        trace = _trace("mmpp2", n=n, seed=seed, lam=2 * LAM)
        filt = PhaseBeliefFilter(
            rates=[0.3 * 2 * LAM, 1.3 * 2 * LAM],
            gen=[[-1 / 60.0, 1 / 60.0], [1 / 30.0, -1 / 30.0]],
        )
        bel = np.asarray(belief_forward_jax(trace, filt)[0])
        stacks = np.stack([
            np.stack([q_policy(4, 96, BMAX), q_policy(10, 96, BMAX)])
            for _ in range(2)
        ])  # (M=2, K=2, L)
        return trace, bel, stacks

    def test_belief_argmax_equals_explicit_phases(self):
        trace, bel, stacks = self._stack_and_beliefs()
        kw = dict(
            router="jsq", means=MEANS, zeta=ENERGY, b_max=BMAX, record=True
        )
        r_bel = simulate_fleet(
            stacks, trace, phase_mode="belief_argmax", beliefs=bel, **kw
        )
        r_ph = simulate_fleet(
            stacks, trace, phases=np.argmax(bel, axis=-1), **kw
        )
        np.testing.assert_array_equal(r_bel.actions, r_ph.actions)
        np.testing.assert_array_equal(r_bel.servers, r_ph.servers)
        np.testing.assert_allclose(r_bel.lat_sum, r_ph.lat_sum)
        assert r_bel.n_served == r_ph.n_served

    def test_belief_mix_m1_matches_single_server_kernel(self):
        # an M=1 belief-mix fleet replays simulate_compiled's mix lane
        from repro.serving.compiled import simulate_compiled

        trace, bel, stacks = self._stack_and_beliefs(n=600)
        kw = dict(means=MEANS, zeta=ENERGY, b_max=BMAX, record=True)
        r = simulate_fleet(
            stacks[:1], trace, phase_mode="belief_mix", beliefs=bel,
            router="rr", **kw
        )
        s = simulate_compiled(
            stacks[0], trace, phase_mode="belief_mix", beliefs=bel, **kw
        )
        np.testing.assert_array_equal(
            r.actions[r.actions > 0], s.batch_sizes
        )
        assert r.n_served == s.n_served
        np.testing.assert_allclose(
            r.latencies[r.served], s.latencies
        )
        np.testing.assert_allclose(r.energy, s.energy)
        np.testing.assert_allclose(r.t_final, s.t_final)

    def test_belief_mix_certified_python_vs_compiled(self):
        trace, bel, stacks = self._stack_and_beliefs(n=500)
        svc = ServiceModel(latency=GOOGLENET_P4_LATENCY, family="det")
        for router in ("jsq", "batch_aware"):
            verify_fleet(
                stacks, trace, router=router, service=svc,
                energy_table=ENERGY, b_max=BMAX,
                phase_mode="belief_mix", beliefs=bel,
            )

    def test_belief_mix_differs_from_argmax_somewhere(self):
        # a mixed posterior between distant per-phase thresholds must
        # produce at least one action the MAP row would not
        trace, bel, stacks = self._stack_and_beliefs(n=900)
        kw = dict(
            router="jsq", means=MEANS, zeta=ENERGY, b_max=BMAX, record=True
        )
        r_mix = simulate_fleet(
            stacks, trace, phase_mode="belief_mix", beliefs=bel, **kw
        )
        r_map = simulate_fleet(
            stacks, trace, phase_mode="belief_argmax", beliefs=bel, **kw
        )
        assert r_mix.n_served == r_map.n_served == len(trace)
        assert len(r_mix.actions) != len(r_map.actions) or (
            (r_mix.actions != r_map.actions).any()
        )

    def test_grid_belief_argmax_equals_explicit_phases(self):
        trace, bel, stacks = self._stack_and_beliefs(n=700)
        arr = pad_arrivals_batch([trace])
        bels = np.zeros(arr.shape + (2,))
        bels[0, : len(trace)] = bel
        bels[0, len(trace):, 0] = 1.0  # pad rows: any valid posterior
        g_bel = run_fleet_grid(
            stacks[None], arr, routers=("jsq",), means=MEANS, zeta=ENERGY,
            b_max=BMAX, phase_mode="belief_argmax", beliefs=bels,
        )
        g_ph = run_fleet_grid(
            stacks[None], arr, routers=("jsq",), means=MEANS, zeta=ENERGY,
            b_max=BMAX, phases=np.argmax(bels, axis=-1),
        )
        for k in ("n_served", "lat_sum", "energy", "t_final"):
            np.testing.assert_allclose(g_bel[k], g_ph[k])

    def test_oracle_mode_rejects_beliefs(self):
        trace, bel, stacks = self._stack_and_beliefs(n=50)
        with pytest.raises(ValueError, match="belief"):
            simulate_fleet(
                stacks, trace, beliefs=bel, means=MEANS, b_max=BMAX
            )


_FAULT_M = 4
#: grid aggregate -> simulate_fleet's FleetResult field
_GRID_FIELDS = {
    "t_final": "t_final", "n_served": "n_served", "n_batches": "n_batches",
    "n_epochs": "n_epochs", "n_admitted": "n_admitted", "energy": "energy",
    "lat_sum": "lat_sum", "slo_miss": "slo_miss", "terminated": "terminated",
    "hist": "hist", "n_crashes": "n_crashes", "n_dropped": "n_dropped",
    "n_shed": "n_shed", "qlen": "qlen", "busy": "busy",
    "n_route": "n_routed", "n_srv": "n_served_m",
}


def _faulted_grid_inputs():
    """Three lanes (Poisson, MMPP2, Poisson) of a 4-replica fleet with 0,
    1 and 3 outages: stragglers in the first and last, and in the last a
    replica that crashes, is repaired, and crashes its retry at once, so
    max_retries = 1 drops the batch."""
    lam = _FAULT_M * LAM
    traces = [_trace("poisson", seed=3, lam=lam),
              _trace("mmpp2", seed=4, lam=lam),
              _trace("poisson", seed=5, lam=lam)]
    arr = pad_arrivals_batch(traces)
    rng = np.random.default_rng(9)
    strag = np.where(rng.random((2, _FAULT_M, 64)) < 0.2, 3.0, 1.0)
    inf = np.inf
    none = np.full((_FAULT_M, 0), inf)
    one = np.array([[40.0, 70.0], [inf, inf], [inf, inf], [inf, inf]])
    three = np.array([[20.0, 35.0, 35.5, 50.0], [inf] * 4, [inf] * 4,
                      [90.0, 100.0, inf, inf]])
    schedules = [
        FaultSchedule(bounds=none, mult=strag[0], max_retries=1),
        FaultSchedule(bounds=one, mult=np.ones((_FAULT_M, 1)), max_retries=1),
        FaultSchedule(bounds=three, mult=strag[1], max_retries=1),
    ]
    policies = np.stack([TABLE, q_policy(10, 96, BMAX)])
    return arr, policies, schedules


class TestFleetGrid:
    def test_grid_cell_matches_simulate_fleet(self):
        traces = [_trace("poisson", seed=s, lam=4 * LAM) for s in range(2)]
        arr = pad_arrivals_batch(traces)
        policies = np.stack([TABLE, q_policy(10, 96, BMAX)])
        out = run_fleet_grid(
            policies, arr, routers=ROUTER_NAMES, n_replicas=4,
            means=MEANS, zeta=ENERGY, b_max=BMAX, router_seed=7,
        )
        ru = np.random.default_rng(7).random(arr.shape + (2,))
        ref = simulate_fleet(
            np.tile(policies[1][None], (4, 1)), traces[1], router="pow2",
            means=MEANS, zeta=ENERGY, b_max=BMAX,
            router_u=ru[1][: len(traces[1])],
        )
        i = ROUTER_NAMES.index("pow2")
        assert out["n_served"][1, 1, i] == ref.n_served
        assert out["n_batches"][1, 1, i] == ref.n_batches
        assert np.isclose(out["lat_sum"][1, 1, i], ref.lat_sum)
        assert np.isclose(out["energy"][1, 1, i], ref.energy)
        assert np.isclose(out["t_final"][1, 1, i], ref.t_final)

    @pytest.mark.parametrize(
        "lens, max_epochs, dispatches",
        [((5, 900, 400), None, 1), ((300, 600), 400, 2), ((300, 900), 250, 1)],
        ids=["uneven-lanes", "escalated", "budget-cut"],
    )
    def test_early_exit_matches_fixed_length_scan(
        self, monkeypatch, lens, max_epochs, dispatches
    ):
        """The grid's loop stops once every instance is done; each
        aggregate is bitwise `_fleet_jit`'s over the full step count."""
        from repro.serving import fleet
        from repro.serving.compiled import default_hist_edges

        sizes = []
        grid_fn = fleet._fleet_grid_fn

        def spy(mesh, n_steps, *rest):
            sizes.append(n_steps)
            return grid_fn(mesh, n_steps, *rest)

        monkeypatch.setattr(fleet, "_fleet_grid_fn", spy)
        rng = np.random.default_rng(5)
        arr = pad_arrivals_batch(
            [np.cumsum(rng.exponential(1.0 / (4 * LAM), n)) for n in lens]
        )
        policies = np.stack([TABLE, q_policy(10, 96, BMAX)])
        routers = ("jsq", "batch_aware")
        M = 4
        out = run_fleet_grid(
            policies, arr, routers=routers, n_replicas=M, means=MEANS,
            zeta=ENERGY, b_max=BMAX, max_epochs=max_epochs, router_seed=3,
        )
        assert len(sizes) == dispatches
        if max_epochs is not None:  # the budget cuts some instance short
            assert (out["n_epochs"] == max_epochs).any()
        n_steps = sizes[-1]
        assert out["n_steps_used"].max() <= out["steps_executed"] < n_steps

        S, N = arr.shape
        max_eps = (
            2 * max(lens) + M + 4 if max_epochs is None else max_epochs
        )
        ru = np.random.default_rng(3).random((S, N, 2))
        q0 = np.full((M, 1), np.inf)
        zm = np.zeros(M, dtype=np.int64)
        for p, pol in enumerate(policies):
            tab = np.repeat(pol[None, None, :], M, axis=0)
            for s in range(S):
                for r, router in enumerate(routers):
                    agg = fleet._fleet_jit(
                        tab, threshold_gaps(tab), arr[s], np.full(N, np.inf),
                        np.zeros(N, dtype=np.int64), np.zeros((1, 1)),
                        np.zeros(1), ru[s], q0, q0, np.ones(1), MEANS, ENERGY,
                        default_hist_edges(MEANS), q0, np.ones((M, 1)),
                        fleet.router_id(router), 0.0, np.inf, max_eps, True,
                        BMAX, fleet._NO_BUFFER, 0, 0, 0, np.full(M, np.inf),
                        zm, np.ones(M, dtype=bool), zm, zm, zm, False,
                        np.inf, n_steps=n_steps, record=False, mix=False,
                    )
                    for k, v in agg.items():
                        want, got = np.asarray(v), out[k][s, p, r]
                        assert want.dtype == got.dtype, k
                        assert want.tobytes() == got.tobytes(), (k, s, p, r)

    def test_faulted_grid_matches_simulate_fleet_bitwise(self):
        """Each lane's own schedule: every aggregate of every (lane,
        policy, router) instance is simulate_fleet(faults=that schedule)'s,
        bit for bit, given the same draws and router uniforms."""
        arr, policies, schedules = _faulted_grid_inputs()
        out = run_fleet_grid(
            policies, arr, routers=ROUTER_NAMES, n_replicas=_FAULT_M,
            means=MEANS, zeta=ENERGY, b_max=BMAX, router_seed=11,
            faults=schedules,
        )
        # the schedules exercise what they should: no crash in the first
        # lane, some in the second, in every instance of the last, and a
        # drop after max_retries there
        assert (out["n_crashes"][0] == 0).all()
        assert (out["n_crashes"][1] > 0).any()
        assert (out["n_crashes"][2] > 0).all()
        assert (out["n_dropped"][2] > 0).any()
        ru = np.random.default_rng(11).random(arr.shape + (2,))
        for s, sch in enumerate(schedules):
            for p, pol in enumerate(policies):
                for r, router in enumerate(ROUTER_NAMES):
                    ref = simulate_fleet(
                        np.tile(pol[None], (_FAULT_M, 1)), arr[s],
                        router=router, means=MEANS, zeta=ENERGY, b_max=BMAX,
                        router_u=ru[s], faults=sch,
                    )
                    for key, field in _GRID_FIELDS.items():
                        want = np.asarray(getattr(ref, field))
                        got = out[key][s, p, r]
                        assert want.tobytes() == np.asarray(
                            got, dtype=want.dtype).tobytes(), (key, s, p, r)

    def test_one_schedule_serves_every_lane(self):
        arr, policies, schedules = _faulted_grid_inputs()
        kw = dict(routers=("jsq", "batch_aware"), n_replicas=_FAULT_M,
                  means=MEANS, zeta=ENERGY, b_max=BMAX)
        one = run_fleet_grid(policies, arr, faults=schedules[2], **kw)
        each = run_fleet_grid(policies, arr, faults=[schedules[2]] * 3, **kw)
        for k, v in one.items():
            assert np.array_equal(v, each[k], equal_nan=True), k

    def test_fault_schedules_are_checked(self):
        arr, policies, schedules = _faulted_grid_inputs()
        kw = dict(n_replicas=_FAULT_M, means=MEANS, b_max=BMAX)
        with pytest.raises(ValueError, match="2 fault schedules for 3"):
            run_fleet_grid(policies, arr, faults=schedules[:2], **kw)
        with pytest.raises(ValueError, match="max_retries"):
            run_fleet_grid(policies, arr, faults=schedules[:2] + [
                dataclasses.replace(schedules[2], max_retries=2)], **kw)
        with pytest.raises(ValueError, match="covers 3 replicas"):
            run_fleet_grid(
                policies, arr, faults=FaultSchedule.none(3), **kw)

    def test_fault_free_grid_lowers_the_constant_program(self):
        """faults=None compiles the grid whose kernels take the constants
        +inf boundaries, unit multipliers and max_retries 0: no lane
        arrays, no further parameters."""
        import jax
        import jax.numpy as jnp

        from repro.serving import fleet

        M, S, N = _FAULT_M, 3, 64
        f64, i64 = jnp.float64, jnp.int64
        shapes = [
            jax.ShapeDtypeStruct(s, d) for s, d in (
                ((2, M, 1, 97), i64), ((2, M, 1, 97), i64), ((2,), i64),
                ((S, N), f64), ((S, N), f64), ((S, N), i64),
                ((S, 1, 1), f64), ((S, N, 2), f64), ((S, 1), f64),
                ((BMAX + 1,), f64), ((BMAX + 1,), f64), ((65,), f64),
            )
        ]
        scalars = (0.0, np.inf, 2 * N + M + 4, True, BMAX)

        def text(fn, *extra):
            return fn.lower(*shapes, *scalars, *extra).as_text()

        free = text(fleet._fleet_grid_fn(None, 256, False))
        consts = text(jax.jit(partial(
            fleet._fleet_grid_core, n_steps=256, mix=False)))
        assert free == consts
        lanes = [jax.ShapeDtypeStruct((S, M, 2), f64),
                 jax.ShapeDtypeStruct((S, M, 4), f64)]
        faulted = text(fleet._fleet_grid_fn(None, 256, False, True),
                       *lanes, 2)
        assert faulted != free

    def test_one_device_mesh_parity(self):
        from repro.launch.mesh import make_sim_mesh

        traces = [_trace("poisson", seed=s, lam=4 * LAM) for s in range(2)]
        arr = pad_arrivals_batch(traces)
        policies = np.stack([TABLE, q_policy(10, 96, BMAX)])
        kw = dict(
            routers=("jsq", "rr"), n_replicas=4, means=MEANS, zeta=ENERGY,
            b_max=BMAX, router_seed=7,
        )
        plain = run_fleet_grid(policies, arr, **kw)
        mesh = run_fleet_grid(policies, arr, mesh=make_sim_mesh(), **kw)
        for k, v in plain.items():
            assert np.allclose(v, mesh[k], equal_nan=True), k


_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.core import GOOGLENET_P4_LATENCY, ServiceModel
from repro.core.policies import q_policy
from repro.launch.mesh import make_sim_mesh
from repro.serving import pad_arrivals_batch, run_fleet_grid

assert jax.device_count() == 8
SVC = ServiceModel(latency=GOOGLENET_P4_LATENCY, family="det")
BMAX = 16
lam = 0.7 * 4 * BMAX / float(SVC.mean(BMAX))
means = np.array([0.0] + [float(SVC.mean(b)) for b in range(1, BMAX + 1)])
# 3 lanes on 8 devices: exercises the pad-to-multiple + trim path
traces = [np.cumsum(np.random.default_rng(s).exponential(1.0 / lam, 600))
          for s in range(3)]
arr = pad_arrivals_batch(traces)
tabs = np.stack([q_policy(6, 96, BMAX), q_policy(10, 96, BMAX)])
kw = dict(routers=("jsq", "pow2"), n_replicas=4, means=means, b_max=BMAX)
plain = run_fleet_grid(tabs, arr, **kw)
shard = run_fleet_grid(tabs, arr, mesh=make_sim_mesh(), **kw)
for k, v in plain.items():
    assert np.allclose(v, shard[k], equal_nan=True), k
print("OK sharded == plain")
"""

_JAX_ENV = {k: v for k, v in os.environ.items() if k.startswith("JAX_")}


@pytest.mark.slow
def test_fleet_grid_sharded_8dev():
    r = subprocess.run(
        [sys.executable, "-c", _SHARD_SCRIPT],
        cwd=ROOT,
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root", **_JAX_ENV},
        capture_output=True,
        text=True,
        timeout=500,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK sharded == plain" in r.stdout


_FAULT_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import numpy as np, jax
sys.path.insert(0, "tests")
from test_fleet import (_FAULT_M, _faulted_grid_inputs, BMAX, ENERGY, MEANS,
                        ROUTER_NAMES)
from repro.launch.mesh import make_sim_mesh
from repro.serving import run_fleet_grid

assert jax.device_count() == 8
# 3 lanes on 8 devices: the lane-0 padding repeats lane 0's schedule too
arr, policies, schedules = _faulted_grid_inputs()
kw = dict(routers=ROUTER_NAMES, n_replicas=_FAULT_M, means=MEANS,
          zeta=ENERGY, b_max=BMAX, faults=schedules)
plain = run_fleet_grid(policies, arr, **kw)
shard = run_fleet_grid(policies, arr, mesh=make_sim_mesh(), **kw)
assert plain["n_crashes"].sum() > 0 and plain["n_dropped"].sum() > 0
for k, v in plain.items():
    assert np.array_equal(v, shard[k], equal_nan=True), k
print("OK faulted sharded == plain")
"""


def test_faulted_fleet_grid_sharded_8dev():
    r = subprocess.run(
        [sys.executable, "-c", _FAULT_SHARD_SCRIPT],
        cwd=ROOT,
        env={"PYTHONPATH": str(ROOT / "src"),
             "PATH": os.environ.get("PATH", ""),
             "HOME": os.environ.get("HOME", ""), **_JAX_ENV},
        capture_output=True,
        text=True,
        timeout=500,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK faulted sharded == plain" in r.stdout
