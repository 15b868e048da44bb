"""Compiled serving backend: decision-for-decision equivalence with the
Python engine per arrival mode, sketch-quantile tolerance, the vmapped
seeds x tables grid, bank stacking, and the service-profile bank axis."""
import dataclasses
from functools import partial

import numpy as np
import pytest

from repro.core import (
    GOOGLENET_P4_ENERGY,
    GOOGLENET_P4_LATENCY,
    AffineProfile,
    ServiceModel,
)
from repro.core.policies import greedy_policy, q_policy, static_policy
from repro.serving import (
    GreedyScheduler,
    QPolicyScheduler,
    ServingEngine,
    SMDPScheduler,
    StaticScheduler,
    as_action_table,
    histogram_quantiles,
    pad_arrivals,
    pad_arrivals_batch,
    run_grid,
    simulate_compiled,
    verify_backends,
)
from repro.serving.arrivals import (
    MMPP2,
    mmpp2_times_jax,
    poisson_times_jax,
)

SVC = ServiceModel(latency=GOOGLENET_P4_LATENCY, family="det")
BMAX = 32
LAM = 0.7 * BMAX / float(SVC.mean(BMAX))
ENERGY = np.array(
    [0.0] + [float(GOOGLENET_P4_ENERGY(b)) for b in range(1, BMAX + 1)]
)
TABLE = q_policy(8, 128, BMAX)


def _trace(mode: str, n: int = 2500, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if mode == "poisson":
        return np.cumsum(rng.exponential(1.0 / LAM, n))
    if mode == "mmpp2":
        m = MMPP2(lam1=0.3 * LAM, lam2=1.3 * LAM, dwell1=60.0, dwell2=30.0)
        times, _ = m.sample_arrivals(n / m.mean_rate, rng)
        return times
    # deterministic trace with bursts and gaps (exercises waits + drain)
    gaps = np.tile([0.1, 0.1, 0.1, 5.0, 0.5], n // 5)
    return np.cumsum(gaps)


class TestBackendEquivalence:
    """ISSUE acceptance: identical schedules + latencies on shared traces."""

    @pytest.mark.parametrize("mode", ["poisson", "mmpp2", "trace"])
    def test_decisions_and_latencies_identical(self, mode):
        out = verify_backends(
            TABLE, _trace(mode), service=SVC, energy_table=ENERGY,
            b_max=BMAX,
        )
        assert out["n_decisions"] > 0
        assert out["max_latency_err"] <= 1e-9

    @pytest.mark.parametrize("mode", ["poisson", "mmpp2", "trace"])
    def test_epoch_bounded_and_horizon_runs(self, mode):
        tr = _trace(mode)
        verify_backends(
            TABLE, tr, service=SVC, energy_table=ENERGY, b_max=BMAX,
            n_epochs=700,
        )
        verify_backends(
            TABLE, tr, service=SVC, energy_table=ENERGY, b_max=BMAX,
            horizon=float(tr[len(tr) // 2]), n_epochs=None,
        )

    @pytest.mark.parametrize("family", ["expo", "erlang", "hyperexpo"])
    def test_stochastic_service_shared_draws(self, family):
        """A shared unit-draw sequence aligns both backends for every
        service family (each is a scale mixture around the batch mean)."""
        svc = ServiceModel(latency=GOOGLENET_P4_LATENCY, family=family)
        verify_backends(
            TABLE, _trace("poisson", 1500), service=svc,
            energy_table=ENERGY, b_max=BMAX,
        )

    def test_slo_miss_accounting_identical(self):
        out = verify_backends(
            TABLE, _trace("poisson", 1500), service=SVC,
            energy_table=ENERGY, b_max=BMAX, slo=8.0,
        )
        assert out["python"].n_slo_miss == out["compiled"].n_slo_miss > 0

    def test_drain_capped_at_b_max(self):
        """Tail drain serves in b_max-capped batches, like the Python
        kernel (never one mega-batch)."""
        never = np.zeros(130, dtype=np.int64)  # always wait -> forced drain
        res = simulate_compiled(
            never, np.full(20, 0.5),
            means=np.array([0.0] + [float(SVC.mean(b)) for b in range(1, 5)]),
            b_max=4, record=True,
        )
        assert res.n_served == 20
        assert res.batch_sizes.max() <= 4
        assert len(res.batch_sizes) == 5


class TestEngineBackendParity:
    def _engine(self, **kw):
        return ServingEngine(
            SMDPScheduler.from_table(TABLE), b_max=BMAX, service=SVC,
            energy_table=ENERGY, seed=11, **kw,
        )

    def test_poisson_det_is_draw_for_draw(self):
        """Deterministic service consumes no service randomness, so the
        eagerly pre-generated arrival stream is the exact lazy stream:
        both backends reproduce each other at equal seeds."""
        r_py = self._engine(lam=LAM).run(1500)
        r_c = self._engine(lam=LAM).run(1500, backend="compiled")
        np.testing.assert_array_equal(r_py.batch_sizes, r_c.batch_sizes)
        np.testing.assert_allclose(r_py.latencies, r_c.latencies, atol=1e-9)
        np.testing.assert_allclose(r_py.energy, r_c.energy)
        np.testing.assert_allclose(r_py.span, r_c.span)

    def test_run_after_compiled_continues_the_stream(self):
        """Over-drawn arrivals are buffered: a python run after a compiled
        run sees the same stream as two python runs."""
        e1, e2 = self._engine(lam=LAM), self._engine(lam=LAM)
        e1.run(800)
        ref = e1.run(800)
        e2.run(800, backend="compiled")
        cont = e2.run(800)
        np.testing.assert_array_equal(ref.batch_sizes, cont.batch_sizes)
        np.testing.assert_allclose(ref.latencies, cont.latencies, atol=1e-9)

    def test_no_serve_compiled_run_preserves_queue_rids(self):
        """A compiled run that serves nothing must not re-mint rids for
        requests admitted before it (state-sync regression)."""
        e_ref = self._engine(lam=LAM)
        e_cmp = self._engine(lam=LAM)
        e_ref.run(40)
        e_cmp.run(40)  # identical prefix: some requests now queued
        assert [r.rid for r in e_cmp.queue]
        never = SMDPScheduler.from_table(np.zeros(130, dtype=np.int64))
        e_ref.scheduler = never
        e_cmp.scheduler = never
        e_ref.run(5)
        e_cmp.run(5, backend="compiled")  # wait-only: serves nothing
        assert [r.rid for r in e_cmp.queue] == [r.rid for r in e_ref.queue]
        # continuation still numbers future admissions identically (the
        # python loop pre-assigns its peeked request's rid, the compiled
        # path assigns on the later peek — same sequence either way)
        e_ref.run(20)
        e_cmp.run(20)
        assert [r.rid for r in e_cmp.queue] == [r.rid for r in e_ref.queue]
        assert e_cmp.next_rid == e_ref.next_rid

    def test_adaptive_scheduler_compiled_matches_python(self):
        """AdaptiveController now lowers to the in-carry adaptive lane:
        the compiled backend reproduces the Python engine decision-for-
        decision (it used to be rejected with a TypeError)."""
        from repro.serving import AdaptiveController, SMDPSchedulerBank

        bank = SMDPSchedulerBank(
            {(LAM,): TABLE, (2 * LAM,): static_policy(8, 128)},
            key_names=("lam",),
        )

        def mk():
            return ServingEngine(
                AdaptiveController(bank, ewma=0.2, margin=0.1, min_dwell=5.0),
                lam=LAM, b_max=BMAX, service=SVC, energy_table=ENERGY,
                seed=11,
            )

        e_py, e_c = mk(), mk()
        r_py = e_py.run(1200)
        r_c = e_c.run(1200, backend="compiled")
        np.testing.assert_array_equal(r_py.batch_sizes, r_c.batch_sizes)
        np.testing.assert_allclose(r_py.latencies, r_c.latencies, atol=1e-9)
        np.testing.assert_allclose(r_py.energy, r_c.energy)
        # post-run controller state is synced from the kernel carry
        assert e_c.scheduler.key == e_py.scheduler.key
        assert e_c.scheduler.n_switches == e_py.scheduler.n_switches
        np.testing.assert_allclose(
            e_c.scheduler.estimator.rate, e_py.scheduler.estimator.rate,
            rtol=1e-12,
        )

    def test_window_estimator_stays_python_only(self):
        """Window-mode estimators have no O(1) scan carry: the compiled
        lowering refuses them loudly."""
        from repro.serving import AdaptiveController, SMDPSchedulerBank
        from repro.serving.metrics import RateEstimator

        bank = SMDPSchedulerBank(
            {(LAM,): TABLE, (2 * LAM,): static_policy(8, 128)},
            key_names=("lam",),
        )
        eng = ServingEngine(
            AdaptiveController(bank, estimator=RateEstimator(window=16)),
            lam=LAM, b_max=BMAX, service=SVC, energy_table=ENERGY,
        )
        with pytest.raises(TypeError, match="EWMA"):
            eng.run(100, backend="compiled")

    def test_sketch_metrics_in_report(self):
        rep = self._engine(lam=LAM).run(4000, backend="compiled")
        assert set(rep.metrics) >= {"W_mean", "P50", "P95", "P99", "power"}
        np.testing.assert_allclose(
            rep.metrics["W_mean"], rep.latencies.mean(), rtol=1e-9
        )
        np.testing.assert_allclose(
            rep.metrics["P95"], np.percentile(rep.latencies, 95), rtol=0.05
        )


class TestQuantileSketch:
    """ISSUE acceptance: sketch vs np.percentile tolerance band."""

    @pytest.mark.parametrize("dist", ["expo", "lognormal", "uniform"])
    @pytest.mark.parametrize("q", [0.5, 0.95, 0.99])
    def test_histogram_quantiles_tolerance(self, dist, q):
        import zlib

        rng = np.random.default_rng(zlib.crc32(f"{dist}:{q}".encode()))
        n = 40_000
        data = {
            "expo": lambda: rng.exponential(5.0, n),
            "lognormal": lambda: rng.lognormal(1.0, 0.7, n),
            "uniform": lambda: rng.uniform(1.0, 30.0, n),
        }[dist]()
        edges = np.geomspace(data.min() * 0.5, data.max() * 2.0, 257)
        counts = np.zeros(258)
        idx = np.clip(np.searchsorted(edges, data, side="right"), 0, 257)
        np.add.at(counts, idx, 1)
        got = histogram_quantiles(counts, edges, q)[0]
        true = np.percentile(data, q * 100)
        assert abs(got - true) / true < 0.05, (got, true)

    def test_engine_sketch_matches_exact_percentiles(self):
        tr = _trace("poisson", 4000)
        rep = ServingEngine(
            SMDPScheduler.from_table(TABLE), arrivals=tr, b_max=BMAX,
            service=SVC, energy_table=ENERGY,
        ).run(n_epochs=None, backend="compiled")
        for q, key in ((50, "P50"), (95, "P95"), (99, "P99")):
            true = np.percentile(rep.latencies, q)
            assert abs(rep.metrics[key] - true) / true < 0.05

    def test_under_and_overflow_clamp_to_edges(self):
        edges = np.geomspace(1.0, 10.0, 11)
        counts = np.zeros(12)
        counts[0] = 100  # all mass below edges[0]
        assert histogram_quantiles(counts, edges, [0.5])[0] == edges[0]
        counts = np.zeros(12)
        counts[-1] = 100
        assert histogram_quantiles(counts, edges, [0.5])[0] == edges[-1]


class TestSheddingBackendEquivalence:
    """Managed-queue lane certification: buffer= / shed_expired= on the
    compiled backend reproduce the Python loop's refusals, expiry sweeps,
    decisions and latencies exactly, per arrival mode."""

    @staticmethod
    def _otrace(mode: str, n: int = 1500) -> np.ndarray:
        # the plain fixture runs at 0.7x capacity; compress gaps so the
        # waiting room actually fills and deadlines actually lapse
        return _trace(mode, n) * 0.55

    @pytest.mark.parametrize("mode", ["poisson", "mmpp2", "trace"])
    def test_buffer_refusals_identical(self, mode):
        # the deterministic trace arrives in 3-bursts: only a shallow
        # room ever refuses there
        out = verify_backends(
            TABLE, self._otrace(mode), service=SVC, energy_table=ENERGY,
            b_max=BMAX, buffer=2 if mode == "trace" else 10,
        )
        assert out["python"].n_shed > 0
        assert out["python"].n_shed == out["compiled"].n_shed

    @pytest.mark.parametrize("mode", ["poisson", "mmpp2", "trace"])
    def test_expiry_sweeps_identical(self, mode):
        out = verify_backends(
            TABLE, self._otrace(mode), service=SVC, energy_table=ENERGY,
            b_max=BMAX, slo=4.0, shed_expired=True,
        )
        assert out["python"].n_expired > 0
        assert out["python"].n_expired == out["compiled"].n_expired

    @pytest.mark.parametrize("mode", ["poisson", "mmpp2", "trace"])
    def test_buffer_and_expiry_together(self, mode):
        verify_backends(
            TABLE, self._otrace(mode), service=SVC, energy_table=ENERGY,
            b_max=BMAX, buffer=14, slo=5.0, shed_expired=True,
        )

    def test_stochastic_service_with_shedding(self):
        svc = ServiceModel(latency=GOOGLENET_P4_LATENCY, family="expo")
        verify_backends(
            TABLE, self._otrace("poisson"), service=svc,
            energy_table=ENERGY, b_max=BMAX, buffer=12, slo=5.0,
            shed_expired=True,
        )

    def test_epoch_budget_and_horizon_with_shedding(self):
        tr = self._otrace("poisson")
        verify_backends(
            TABLE, tr, service=SVC, b_max=BMAX, n_epochs=250, buffer=10,
            slo=4.0, shed_expired=True,
        )
        verify_backends(
            TABLE, tr, service=SVC, b_max=BMAX,
            horizon=float(tr[len(tr) // 2]), buffer=10, slo=4.0,
            shed_expired=True,
        )

    def test_phase_stack_with_shedding(self):
        tr = self._otrace("poisson")
        tabs = np.stack([q_policy(4, 128, BMAX), q_policy(12, 128, BMAX)])
        ph = (np.arange(len(tr)) // 150) % 2
        verify_backends(
            tabs, tr, service=SVC, b_max=BMAX, phases=ph, buffer=16,
            slo=5.0, shed_expired=True,
        )

    def test_buffer_zero_starves_both_backends(self):
        out = verify_backends(
            TABLE, self._otrace("poisson", 400), service=SVC, b_max=BMAX,
            buffer=0,
        )
        assert out["python"].n_served == out["compiled"].n_served == 0
        assert out["compiled"].n_shed == 400

    def test_surviving_queue_accounting(self):
        """queue_slots + counters partition every door-seen arrival."""
        tr = self._otrace("poisson", 600)
        res = simulate_compiled(
            q_policy(20, 128, BMAX), tr,
            means=np.array(
                [0.0] + [float(SVC.mean(b)) for b in range(1, BMAX + 1)]
            ),
            b_max=BMAX, buffer=40, deadlines=tr + 25.0, shed_expired=True,
            drain=False, record=True,
        )
        assert res.queue_slots is not None
        assert (
            res.n_served + res.n_expired + len(res.queue_slots)
            == res.n_admitted - res.n_shed
        )

    def test_non_monotone_deadlines_rejected(self):
        tr = np.arange(1.0, 9.0)
        dl = tr + np.array([20.0, 16.0, 12.0, 8.0, 4.0, 2.0, 1.0, 0.5])
        with pytest.raises(ValueError, match="nondecreasing"):
            simulate_compiled(
                TABLE, tr, means=np.array([0.0, 1.0]), b_max=1,
                deadlines=dl, shed_expired=True,
            )

    def test_belief_mode_with_buffer_rejected(self):
        with pytest.raises(ValueError, match="belief"):
            simulate_compiled(
                np.stack([TABLE, TABLE]), np.arange(1.0, 5.0),
                means=np.array([0.0, 1.0]), b_max=1, buffer=4,
                phase_mode="belief_mix",
                beliefs=np.full((4, 2), 0.5),
            )


class TestGridRunner:
    def test_grid_matches_python_engines(self):
        """One vmapped dispatch == the seeds x tables python loop."""
        traces = [_trace("poisson", 1200, seed=s) for s in (1, 2)]
        arrs = pad_arrivals_batch(traces)
        tabs = np.stack(
            [q_policy(8, 128, BMAX), static_policy(8, 128),
             greedy_policy(128, 1, BMAX)]
        )
        means = np.array(
            [0.0] + [float(SVC.mean(b)) for b in range(1, BMAX + 1)]
        )
        g = run_grid(tabs, arrs, means=means, zeta=ENERGY, b_max=BMAX)
        assert g["w_mean"].shape == (2, 3)
        for s, tr in enumerate(traces):
            for p in range(3):
                rep = ServingEngine(
                    SMDPScheduler.from_table(tabs[p]), arrivals=tr,
                    b_max=BMAX, service=SVC, energy_table=ENERGY,
                ).run(n_epochs=None)
                np.testing.assert_allclose(
                    g["w_mean"][s, p], rep.latencies.mean(), atol=1e-9
                )
                np.testing.assert_allclose(
                    g["energy"][s, p], rep.energy, atol=1e-9
                )
                assert g["n_served"][s, p] == rep.n_served

    def test_grid_power_nan_without_energy_source(self):
        """run_grid follows the engine's have_energy convention: no zeta
        source (or no served batch) reports NaN power, never 0."""
        arrs = np.stack([pad_arrivals(_trace("poisson", 300))[0]])
        tabs = np.stack([TABLE])
        means = np.array(
            [0.0] + [float(SVC.mean(b)) for b in range(1, BMAX + 1)]
        )
        g = run_grid(tabs, arrs, means=means, b_max=BMAX)
        assert np.isnan(g["power"]).all()
        g = run_grid(tabs, arrs, means=means, zeta=ENERGY, b_max=BMAX)
        assert np.isfinite(g["power"]).all() and (g["power"] > 0).all()

    def test_step_escalation_completes_short_initial_guess(self):
        """A lane needing more steps than the initial bucket re-dispatches
        doubled and still finishes (serve-one-at-a-time: epochs ~ 2n)."""
        from repro.serving import compiled as C

        C._NSTEPS_CACHE.clear()
        tab = q_policy(1, 128, 1)  # b_max=1: one serve per arrival
        tr = _trace("poisson", 900)
        res = simulate_compiled(
            tab, tr, means=np.array([0.0, float(SVC.mean(1))]), b_max=1,
            record=True,
        )
        assert res.n_served == 900
        assert res.terminated
        assert len(res.batch_sizes) == 900  # b_max=1: one serve per request

    @pytest.mark.parametrize(
        "case, lens, max_epochs, dispatches",
        [("oracle", (5, 900, 300), None, 1),
         ("one-at-a-time", (250, 550), None, 2),
         ("oracle", (300, 1200), 150, 1),
         ("belief_mix", (5, 700, 400), None, 1),
         ("adaptive", (5, 900, 300), None, 1)],
        ids=["uneven-lanes", "escalated", "budget-cut", "belief-mix",
             "adaptive"],
    )
    def test_grid_early_exit_matches_fixed_length_scan(
        self, monkeypatch, case, lens, max_epochs, dispatches
    ):
        """The grid's loop stops once every instance is done; each
        aggregate is bitwise that of `_scan_core`'s fixed scan over the
        full step count."""
        import jax

        from repro.serving import AdaptiveController, SMDPSchedulerBank
        from repro.serving import compiled as C

        monkeypatch.setattr(C, "_NSTEPS_CACHE", {})
        jit_name = "_grid_adaptive_jit" if case == "adaptive" else "_grid_jit"
        grid_fn, sizes = getattr(C, jit_name), []

        def spy(*args):
            sizes.append(args[-2])  # n_steps
            return grid_fn(*args)

        monkeypatch.setattr(C, jit_name, spy)
        rng = np.random.default_rng(5)
        arr = pad_arrivals_batch(
            [np.cumsum(rng.exponential(1.0 / LAM, n)) for n in lens]
        )
        S, N = arr.shape
        means = np.array(
            [0.0] + [float(SVC.mean(b)) for b in range(1, BMAX + 1)]
        )
        kw = dict(means=means, zeta=ENERGY, b_max=BMAX,
                  max_epochs=max_epochs)
        bel = np.zeros((S, 1, 1))
        lane = None
        if case == "adaptive":
            bank = SMDPSchedulerBank(
                {(0.5 * LAM,): static_policy(4, 128), (LAM,): TABLE,
                 (2 * LAM,): static_policy(16, 128)},
                key_names=("lam",),
            )
            lane = C.AdaptiveLane.from_controller(
                AdaptiveController(bank, ewma=0.2, margin=0.1, min_dwell=5.0)
            )
            tables = lane.tables[None]
            out = C.run_grid_adaptive(arr, adaptive=lane, **kw)
            out = {k: v[:, None] if isinstance(v, np.ndarray) else v
                   for k, v in out.items()}
        elif case == "belief_mix":
            tables = np.stack([
                np.stack([TABLE, static_policy(8, 128)]),
                np.stack([static_policy(4, 128), TABLE]),
            ])
            bel = rng.dirichlet(np.ones(2), (S, N))
            out = run_grid(tables, arr, phase_mode="belief_mix",
                           beliefs=bel, **kw)
        else:
            tables = (
                np.stack([TABLE, static_policy(8, 128)])
                if case == "oracle"  # else: serve one request per epoch
                else np.minimum(np.arange(TABLE.shape[-1]), 1)[None]
            )[:, None, :]
            out = run_grid(tables, arr, **kw)
        assert len(sizes) == dispatches
        if max_epochs is not None:  # the budget cuts some instance short
            assert (out["n_epochs"] == max_epochs).any()
        n_steps = sizes[-1]
        assert out["n_steps_used"].max() <= out["steps_executed"] < n_steps

        max_eps = 2 * max(lens) + 2 if max_epochs is None else max_epochs
        fixed = partial(
            C._scan_core, n_steps=n_steps, record=False,
            mix=case == "belief_mix", adaptive=lane is not None,
        )
        dl, ph = np.full(N, np.inf), np.zeros(N, dtype=np.int64)
        rest = (np.ones(1), means, ENERGY, C.default_hist_edges(means), 0.0,
                np.inf, max_eps, True, BMAX,
                None if lane is None else lane.lowered())
        if lane is not None:
            # against the same lanes vmapped over the fixed scan: one lane
            # alone may round the controller's estimate in its last bit
            lanes = jax.jit(jax.vmap(
                fixed, (None, 0, None, None, 0) + (None,) * 10
            ))(tables[0], arr, dl, ph, bel, *rest)
        for s in range(S):
            for p, tab in enumerate(tables):
                agg = (
                    jax.jit(fixed)(tab, arr[s], dl, ph, bel[s], *rest)
                    if lane is None
                    else {k: v[s] for k, v in lanes.items()}
                )
                for k, v in agg.items():
                    want, got = np.asarray(v), out[k][s, p]
                    assert want.dtype == got.dtype, k
                    assert want.tobytes() == got.tobytes(), (k, s, p)


class TestSchedulerLowering:
    @pytest.mark.parametrize(
        "sched",
        [
            StaticScheduler(8),
            GreedyScheduler(2, BMAX),
            QPolicyScheduler(12, BMAX),
            SMDPScheduler.from_table(TABLE),
        ],
        ids=lambda s: s.name,
    )
    def test_as_action_table_matches_decide(self, sched):
        table = as_action_table(sched, BMAX)
        for q in list(range(0, 64)) + [200, 10**6]:
            a_tab = int(table[min(q, len(table) - 1)])
            a_tab = max(0, min(a_tab, q, BMAX))
            a_dec = max(0, min(sched.decide(q), q, BMAX))
            assert a_tab == a_dec, (sched.name, q)

    def test_bank_stacked_pads_with_last_entry(self):
        from repro.serving import SMDPSchedulerBank

        bank = SMDPSchedulerBank(
            {(1.0,): np.array([0, 1, 2]), (2.0,): np.array([0, 1, 2, 3, 4])},
            key_names=("lam",),
        )
        keys, stacked = bank.stacked()
        assert stacked.shape == (2, 5)
        np.testing.assert_array_equal(stacked[0], [0, 1, 2, 2, 2])
        np.testing.assert_array_equal(stacked[1], [0, 1, 2, 3, 4])
        # padded row decides identically to the original table (eq. 30)
        sch = SMDPScheduler.from_table(np.array([0, 1, 2]))
        for q in range(8):
            assert int(stacked[0][min(q, 4)]) == sch.decide(q)


class TestProfileAxis:
    """ROADMAP open item: service-profile id wired into bank + serving."""

    def _bank(self):
        from repro.core.sweep import sweep_bank
        from repro.configs.googlenet_p4 import paper_spec

        base = paper_spec(rho=0.4, w2=1.0, s_max=48)
        base = dataclasses.replace(
            base, b_max=8, lam=0.4 * 8 / float(base.service.mean(8))
        )
        fast = ServiceModel(
            latency=AffineProfile(slope=0.1, intercept=0.6), family="det"
        )
        profiles = {
            0: {},
            1: {"service": fast,
                "energy": AffineProfile(slope=10.0, intercept=8.0)},
        }
        return sweep_bank(base, [0.5 * base.lam, base.lam],
                          profiles=profiles), base

    def test_profile_keyed_bank_and_lookup(self):
        bank, base = self._bank()
        assert bank.key_names == ("lam", "w2", "profile")
        assert len(bank) == 4
        t0 = bank.scheduler(lam=base.lam, w2=1.0, profile=0.0).table
        t1 = bank.scheduler(lam=base.lam, w2=1.0, profile=1.0).table
        assert not np.array_equal(t0, t1)

    def test_adaptive_controller_pins_profile(self):
        from repro.serving import AdaptiveController

        bank, base = self._bank()
        ctrl = AdaptiveController(bank, w2=1.0, profile=1.0, ewma=0.5)
        assert ctrl.key[2] == 1.0
        # drive the estimator across the rate regimes: the retuned key
        # moves along lam but stays inside the pinned profile slice
        t = 0.0
        for gap in [2.0] * 50 + [0.1] * 200:
            t += gap
            ctrl.observe_arrival(t)
        assert ctrl.key[2] == 1.0
        eng = ServingEngine(
            ctrl, lam=base.lam, b_max=8,
            service=base.service, energy_table=np.zeros(9),
        )
        rep = eng.run(300)
        assert rep.n_served > 0


class TestJaxSamplers:
    def test_poisson_times_statistics(self):
        import jax

        t = np.asarray(poisson_times_jax(jax.random.PRNGKey(0), 2.0, 20000))
        assert np.all(np.diff(t) > 0)
        assert abs(len(t) / t[-1] - 2.0) / 2.0 < 0.05

    def test_mmpp2_times_sorted_and_rate(self):
        import jax

        m = MMPP2(lam1=1.0, lam2=5.0, dwell1=50.0, dwell2=50.0)
        times, mask = mmpp2_times_jax(jax.random.PRNGKey(1), m, 30000)
        times, mask = np.asarray(times), np.asarray(mask)
        n = int(mask.sum())
        assert np.all(np.isinf(times[n:]))
        assert np.all(np.diff(times[:n]) >= 0)
        rate = n / times[n - 1]
        assert abs(rate - m.mean_rate) / m.mean_rate < 0.1

    def test_mmpp2_feeds_compiled_kernel(self):
        """jax-sampled MMPP2 arrivals drop straight into the scan kernel."""
        import jax

        m = MMPP2(lam1=0.3 * LAM, lam2=1.3 * LAM, dwell1=60.0, dwell2=30.0)
        times, mask = mmpp2_times_jax(jax.random.PRNGKey(2), m, 4096)
        means = np.array(
            [0.0] + [float(SVC.mean(b)) for b in range(1, BMAX + 1)]
        )
        res = simulate_compiled(
            TABLE, np.asarray(times), means=means, zeta=ENERGY, b_max=BMAX
        )
        assert res.n_served == int(np.asarray(mask).sum())
        assert res.terminated
