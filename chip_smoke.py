#!/usr/bin/env python3
"""On-chip smoke test of the main path: solve -> bank -> compiled lane -> fleet.

    python chip_smoke.py             # one TPU chip: every phase below
    python chip_smoke.py --chips 4   # only the lane-sharded fleet grid,
                                     # on 4 chips vs the same grid on one

Everything runs in this one process, through the entry points a user
calls, at deployment sizes:

  solve_paper   sweep_solve over the paper's GoogLeNet/P4 deployment, a
                9 x 8 (rho, w2) grid with the defaults (accel="auto",
                guard=True, auto-grown s_max); tables equal the scalar
                float64 solve() oracle run on the host CPU backend, no
                guard-ladder rung fired, and backup="pallas" (the lowered
                Mosaic kernel) gives the same tables
  solve_arch    the same for one ARCHS decode deployment, 4 x 4 grid
  serve         sweep_bank -> SMDPScheduler bank; verify_backends
                (compiled lane on the chip vs the Python engine on the
                host, decision for decision) on Poisson and MMPP2 traces;
                run_grid on 64 lanes x 2^20 Poisson arrival slots, checked
                against the solved policy's analytical mean latency/power
  fleet         run_fleet_grid, M = 8 replicas behind jsq and batch_aware,
                16 lanes x 2^18 MMPP2 arrival slots; verify_fleet per
                router; a FleetStream in 4 chunks equals the one-shot run

Each phase prints one JSON line (phase, device_kind, wall and compile
seconds, its checks).  The last line is {"ok": true, "device": {...}};
any failed check exits 1 before it.  Without a TPU, or outside a full
checkout, the script exits 2 and prints no result.  JAX's persistent
compilation cache lives where JAX_COMPILATION_CACHE_DIR says, else in
.jax_cache/ (repro.launch.cache).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"


@dataclasses.dataclass(frozen=True)
class Sizes:
    rhos: tuple = tuple(round(0.1 * i, 1) for i in range(1, 10))
    n_w2: int = 8
    arch: str = "gemma2-9b"
    arch_rhos: tuple = (0.3, 0.5, 0.7, 0.9)
    n_arch_w2: int = 4
    verify_n: int = 20_000  # arrivals per verify_backends trace
    grid_lanes: int = 64
    grid_slots: int = 1 << 20  # padded arrival slots per run_grid lane
    fleet_m: int = 8
    fleet_lanes: int = 16
    fleet_slots: int = 1 << 18  # MMPP2 sampler steps per fleet lane
    fleet_verify_n: int = 5_000  # arrivals per verify_fleet trace
    stream_n: int = 20_000  # arrivals of the FleetStream comparison


FULL = Sizes()
B_MAX = 32


def _bursty(rate: float):
    """MMPP2 of mean ``rate``: phases at 0.5x and 1.5x, each dwelling for
    200 mean inter-arrival gaps."""
    from repro.serving.arrivals import MMPP2

    dwell = 200.0 / rate
    return MMPP2(lam1=0.5 * rate, lam2=1.5 * rate, dwell1=dwell, dwell2=dwell)


class _CompileClock:
    """Seconds JAX spent compiling (or loading from its persistent cache)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Smoke:
    def __init__(self, sizes: Sizes = FULL):
        import jax

        self.sizes = sizes
        self.kind = jax.devices()[0].device_kind
        self.clock = _CompileClock()

    def phase(self, name, fn):
        t0 = time.perf_counter()
        c0, n0, h0 = self.clock.seconds, self.clock.count, self.clock.cache_hits
        checks, info = fn()
        line = {
            "phase": name,
            "device_kind": self.kind,
            "wall_s": time.perf_counter() - t0,
            "compile_s": self.clock.seconds - c0,
            "compiles": self.clock.count - n0,
            "cache_hits": self.clock.cache_hits - h0,
            "checks": {k: bool(v) for k, v in checks.items()},
            **info,
        }
        print(json.dumps(line), flush=True)
        bad = [k for k, v in checks.items() if not v]
        if bad:
            print(f"chip_smoke: phase {name} failed: {bad}", file=sys.stderr)
            sys.exit(1)

    # -- solve ------------------------------------------------------------

    def _solve_grid(self, specs, oracle_idx, with_pallas: bool):
        import jax
        import numpy as np

        from repro.core import solve, sweep_solve

        sink = []
        res = sweep_solve(specs, report_sink=sink)
        rep = sink[0]
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            oracle = [solve(specs[i]).policy for i in oracle_idx]
        checks = {
            "all_converged": all(r.rvi.converged for r in res),
            "all_healthy": bool(np.all(rep.healthy)),
            "no_ladder_rung": not rep.rungs,
            "none_quarantined": not rep.quarantined and not rep.failed,
            "tables_equal_cpu_f64_oracle": all(
                np.array_equal(res[i].policy, p)
                for i, p in zip(oracle_idx, oracle)
            ),
        }
        info = {
            "n_specs": len(specs),
            "oracle_specs": [
                [specs[i].rho, specs[i].w2] for i in oracle_idx
            ],
            "s_max_final": sorted({int(r.spec.s_max) for r in res}),
        }
        if with_pallas:
            from repro.kernels import bellman, ops

            sink_p = []
            res_p = sweep_solve(specs, backup="pallas", report_sink=sink_p)
            n, t, a, k = 2, 129, B_MAX + 1, 129
            hlo = (
                jax.jit(ops.bellman_backup_batched)
                .lower(
                    jax.ShapeDtypeStruct((n, t + k), np.float32),
                    jax.ShapeDtypeStruct((n, a, k), np.float32),
                    jax.ShapeDtypeStruct((n, t, a), np.float32),
                    jax.ShapeDtypeStruct((n,), np.float32),
                )
                .compile()
                .as_text()
            )
            checks.update(
                pallas_lowered=(
                    not bellman.auto_interpret(None)
                    and "tpu_custom_call" in hlo
                ),
                pallas_no_ladder_rung=not sink_p[0].rungs,
                pallas_tables_equal_banded=all(
                    np.array_equal(r.policy, q.policy)
                    for r, q in zip(res, res_p)
                ),
            )
        return checks, info

    def solve_paper(self):
        import numpy as np

        from repro.configs.googlenet_p4 import paper_spec

        s = self.sizes
        w2s = np.logspace(-1.0, 1.0, s.n_w2)
        specs = [paper_spec(rho=r, w2=float(w)) for r in s.rhos for w in w2s]
        nw = len(w2s)
        ri = {r: i for i, r in enumerate(s.rhos)}
        picks = [
            (s.rhos[0], 0),
            (s.rhos[len(s.rhos) // 2], nw // 2),
            (s.rhos[-2], nw - 1),
            (s.rhos[-1], 0),
            (s.rhos[-1], nw - 1),
        ]
        idx = sorted({ri[r] * nw + j for r, j in picks})
        return self._solve_grid(specs, idx, with_pallas=True)

    def solve_arch(self):
        import numpy as np

        from benchmarks.tpu_profile_scenario import arch_workload
        from repro.configs import get_config
        from repro.core import SMDPSpec
        from repro.core.profiles import tpu_service_model

        s = self.sizes
        svc, energy = tpu_service_model(arch_workload(get_config(s.arch), chips=8))
        w2s = np.logspace(-1.0, 1.0, s.n_arch_w2)
        specs = [
            SMDPSpec(
                lam=rho * B_MAX / float(svc.mean(B_MAX)), service=svc,
                energy=energy, b_min=1, b_max=B_MAX, w1=1.0, w2=float(w),
                s_max=128,
            )
            for rho in s.arch_rhos
            for w in w2s
        ]
        nw = len(w2s)
        idx = [0, len(specs) - nw, len(specs) - 1]
        checks, info = self._solve_grid(specs, idx, with_pallas=False)
        info["arch"] = s.arch
        return checks, info

    # -- serve ------------------------------------------------------------

    def _paper_serving(self):
        import numpy as np

        from repro.configs.googlenet_p4 import energy_table, paper_spec, service

        svc = service("det")
        means = np.array([0.0] + [float(svc.mean(b)) for b in range(1, B_MAX + 1)])
        lam = 0.7 * B_MAX / float(svc.mean(B_MAX))
        return svc, means, energy_table(), lam, paper_spec(rho=0.7, w2=1.0)

    def serve(self):
        import jax
        import numpy as np

        from repro.core import solve
        from repro.core.sweep import sweep_bank
        from repro.serving import pad_arrivals_batch, run_grid, verify_backends
        from repro.serving.arrivals import poisson_times_jax

        s = self.sizes
        svc, means, zeta, lam, spec = self._paper_serving()
        bank = sweep_bank(
            spec, [lam * f for f in (0.5 / 0.7, 1.0, 0.9 / 0.7)],
            w2s=(0.5, 1.0, 2.0),
        )
        table = bank.scheduler(lam=lam, w2=1.0).table
        rng = np.random.default_rng(0)
        poisson = np.cumsum(rng.exponential(1.0 / lam, s.verify_n))
        mmpp = _bursty(lam)
        bursty, _ = mmpp.sample_arrivals(s.verify_n / mmpp.mean_rate, rng)
        kw = dict(service=svc, energy_table=zeta, b_max=B_MAX)
        vb_p = verify_backends(table, poisson, **kw)
        vb_m = verify_backends(table, bursty, **kw)

        n = s.grid_slots - 4  # pad_arrivals' sentinel margin
        keys = jax.random.split(jax.random.PRNGKey(1), s.grid_lanes)
        arr = jax.jit(jax.vmap(lambda k: poisson_times_jax(k, lam, n)))(keys)
        arr = pad_arrivals_batch(np.asarray(arr))
        g = run_grid(table[None], arr, means=means, zeta=zeta, b_max=B_MAX)
        ev = solve(spec).eval
        w_sim = float(g["lat_sum"].sum() / g["n_served"].sum())
        p_sim = float(g["energy"].sum() / g["t_final"].sum())
        checks = {
            "verify_backends_poisson": vb_p["n_decisions"] > 0,
            "verify_backends_mmpp2": vb_m["n_decisions"] > 0,
            "grid_shape": g["n_served"].shape == (s.grid_lanes, 1),
            "grid_all_served": bool((g["n_served"] == n).all()),
            "grid_complete": not bool(g["incomplete"].any()),
            "grid_finite": bool(
                np.isfinite(g["w_mean"]).all() and np.isfinite(g["power"]).all()
            ),
            "grid_w_matches_model": abs(w_sim / ev.w_bar - 1.0) < 0.01,
            "grid_p_matches_model": abs(p_sim / ev.p_bar - 1.0) < 0.01,
        }
        info = {
            "verify_decisions": [vb_p["n_decisions"], vb_m["n_decisions"]],
            "verify_max_latency_err": [
                vb_p["max_latency_err"], vb_m["max_latency_err"]
            ],
            "grid": [s.grid_lanes, int(arr.shape[1])],
            "grid_events": int(g["events_total"]),
            "w_sim_vs_model": [w_sim, ev.w_bar],
            "p_sim_vs_model": [p_sim, ev.p_bar],
        }
        return checks, info

    # -- fleet ------------------------------------------------------------

    def _fleet_inputs(self):
        import jax
        import numpy as np

        from repro.core import solve
        from repro.serving import pad_arrivals_batch
        from repro.serving.arrivals import mmpp2_times_jax

        s = self.sizes
        svc, means, zeta, lam, spec = self._paper_serving()
        table = solve(spec).action_table()
        lam_f = s.fleet_m * lam
        mmpp = _bursty(lam_f)
        keys = jax.random.split(jax.random.PRNGKey(2), s.fleet_lanes)
        times, _ = jax.jit(
            jax.vmap(lambda k: mmpp2_times_jax(k, mmpp, s.fleet_slots - 4))
        )(keys)
        times = np.asarray(times)
        arr = pad_arrivals_batch([t[np.isfinite(t)] for t in times])
        kw = dict(
            routers=("jsq", "batch_aware"), n_replicas=s.fleet_m,
            means=means, zeta=zeta, b_max=B_MAX,
        )
        return svc, means, zeta, mmpp, table, arr, kw

    def fleet(self):
        import numpy as np

        from repro.serving import FleetStream, run_fleet_grid, simulate_fleet
        from repro.serving import verify_fleet

        s = self.sizes
        svc, means, zeta, mmpp, table, arr, kw = self._fleet_inputs()
        g = run_fleet_grid(table[None], arr, **kw)
        n_arr = np.isfinite(arr).sum(axis=1)
        tables = np.tile(table[None], (s.fleet_m, 1))
        rng = np.random.default_rng(3)
        cert = {}
        for router in kw["routers"]:
            tr, _ = mmpp.sample_arrivals(s.fleet_verify_n / mmpp.mean_rate, rng)
            cert[router] = verify_fleet(
                tables, tr, router=router, service=svc, energy_table=zeta,
                b_max=B_MAX,
            )
        tr, _ = mmpp.sample_arrivals(s.stream_n / mmpp.mean_rate, rng)
        one = simulate_fleet(
            tables, tr, router="jsq", means=means, zeta=zeta, b_max=B_MAX,
            slo=20.0,
        )
        st = FleetStream(
            tables, router="jsq", means=means, zeta=zeta, b_max=B_MAX,
            slo=20.0,
        )
        for chunk in np.array_split(tr, 4):
            st.push(chunk)
        st = st.finish()
        checks = {
            "grid_shape": g["n_served"].shape == (s.fleet_lanes, 1, 2),
            "grid_all_served": bool(
                (g["n_served"] == n_arr[:, None, None]).all()
            ),
            "grid_complete": not bool(g["incomplete"].any()),
            "grid_finite": bool(
                np.isfinite(g["w_mean"]).all() and np.isfinite(g["power"]).all()
            ),
            "verify_fleet": all(c["n_decisions"] > 0 for c in cert.values()),
            "stream_counts_equal": (
                st.n_served == one.n_served == len(tr)
                and st.n_batches == one.n_batches
                and st.n_epochs == one.n_epochs
                and st.slo_miss == one.slo_miss
                and st.t_final == one.t_final
                and np.array_equal(st.hist, one.hist)
            ),
            "stream_sums_equal": bool(
                np.isclose(st.lat_sum, one.lat_sum, rtol=1e-12)
                and np.isclose(st.energy, one.energy, rtol=1e-12)
            ),
        }
        info = {
            "grid": [s.fleet_lanes, int(arr.shape[1]), s.fleet_m],
            "grid_events": int(g["events_total"]),
            "w_mean_by_router": g["w_mean"].mean(axis=(0, 1)).tolist(),
            "verify_fleet_decisions": {
                r: c["n_decisions"] for r, c in cert.items()
            },
            "stream_arrivals": int(len(tr)),
        }
        return checks, info

    def fleet_sharded(self):
        import jax
        import numpy as np

        from repro.launch.mesh import make_sim_mesh
        from repro.serving import run_fleet_grid

        _, _, _, _, table, arr, kw = self._fleet_inputs()
        one = run_fleet_grid(table[None], arr, **kw)
        mesh = make_sim_mesh()
        sharded = run_fleet_grid(table[None], arr, mesh=mesh, **kw)
        exact, close = [], []
        for k, v in one.items():
            if np.issubdtype(np.asarray(v).dtype, np.floating):
                close.append(
                    np.allclose(v, sharded[k], rtol=1e-12, atol=0.0,
                                equal_nan=True)
                )
            else:
                exact.append(np.array_equal(v, sharded[k]))
        checks = {
            "mesh_devices": mesh.devices.size == jax.device_count(),
            "counts_equal": all(exact),
            "sums_equal": all(close),
        }
        return checks, {"grid": [arr.shape[0], int(arr.shape[1])],
                        "mesh": dict(mesh.shape)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: run only the fleet grid sharded over 4 chips vs one chip",
    )
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              "full checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [str(SRC), str(ROOT)]
    import jax

    from repro.launch.cache import enable_compile_cache

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0].platform is "
              f"{dev[0].platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    if len(dev) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(dev)} "
              "devices", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    import repro.core  # noqa: F401  (float64 on, before any computation)

    print(json.dumps({"compile_cache": cache, "sizes": dataclasses.asdict(FULL)}),
          flush=True)
    smoke = Smoke()
    if args.chips == 4:
        smoke.phase("fleet_sharded_4chips", smoke.fleet_sharded)
    else:
        smoke.phase("solve_paper", smoke.solve_paper)
        smoke.phase("solve_arch", smoke.solve_arch)
        smoke.phase("serve", smoke.serve)
        smoke.phase("fleet", smoke.fleet)
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
