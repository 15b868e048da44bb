"""Shared helpers of the cell tests: one CPU run of a cell at its
``cpu_test`` sizes, with the harness's look for a chip skipped."""
import dataclasses

import numpy as np

import common
import compare
import run

CELLS = [w["name"] for w in common.benchmark()["workloads"]]


def run_cpu(name, seed=20251016, trace=0, **kw):
    bench = common.benchmark()
    wl, cfg, mix = common.cell(bench, name)
    return run.run_cell(bench, wl, cfg, mix, seed, 0.2, trace, test=True,
                        cache=False, log=lambda s: None, **kw)


# -- planted faults: each replaces the program callable the window drives --


def _solve_fault(kind):
    from repro.core import build_smdp, evaluate_policy

    def consistent(r, policy):
        # the program's own evaluation of the broken table, as a fault in
        # the solver would hand it on
        ev = evaluate_policy(build_smdp(r.spec), policy)
        return dataclasses.replace(
            r, rvi=dataclasses.replace(r.rvi, policy=policy), eval=ev)

    def plant(entry):
        solve = entry.program["sweep_solve"]

        def broken(specs):
            res = solve(specs)
            if kind == "unchanged":
                # values never move from h = 0: act on the immediate cost
                # rate alone, which waits in every state
                return [consistent(r, np.zeros_like(r.rvi.policy)) for r in res]
            if kind == "half":
                half = res[: len(res) // 2]
                mean = dataclasses.replace(
                    half[0].eval,
                    **{k: float(np.mean([getattr(r.eval, k) for r in half]))
                       for k in ("w_bar", "p_bar", "g")})
                return half + [
                    dataclasses.replace(half[0], spec=r.spec, eval=mean)
                    for r in res[len(half):]]
            # altered: the first state that serves waits instead
            out = []
            for r in res:
                p = np.array(r.rvi.policy)
                p[np.argmax(p > 0)] = 0
                out.append(consistent(r, p))
            return out

        entry.program["sweep_solve"] = broken

    return plant


def _sim_fault(kind, key):
    def plant(entry):
        sim = entry.program[key]
        lat1 = float(entry.lat[1])

        def broken(*a, **k):
            out = {k2: np.array(v) for k2, v in sim(*a, **k).items()}
            keys = compare.INT_KEYS + compare.SUM_KEYS
            if kind == "unchanged":
                for k2 in keys:
                    out[k2] = np.zeros_like(out[k2])
            elif kind == "half":
                s = out["n_served"].shape[0] // 2
                for k2 in keys:
                    fill = out[k2][:s].mean(axis=0)
                    out[k2][s:] = np.round(fill) if k2 in compare.INT_KEYS else fill
            else:
                out["lat_sum"] = out["lat_sum"] + lat1
            return out

        entry.program[key] = broken

    return plant


def plant(name, kind):
    entry = common.cell(common.benchmark(), name)[2]["entry"]
    if entry == "sweep_solve":
        return _solve_fault(kind)
    return _sim_fault(kind, entry)
