"""sweep_solve: a (rho, w2) grid of one deployment to policy tables.

``table_s``: wall seconds from a ``sweep_solve`` call to tables on the
host, over the window's whole calls.  Every seed solves the same grid,
handed over in an order drawn from the seed.  The comparison solves each
operating point with the plain reference and judges a seeded sample of
the window's grids, and always the last.
"""
from __future__ import annotations

import numpy as np

import compare
import deploy
from common import BenchError
from entry import Entry, span


class SweepSolve(Entry):
    KEYS = {"rho": None, "w2": {"low": None, "high": None, "n": None},
            "accel": None, "check": {"grids": None}}
    # the banded and the Pallas backup both answer with the optimal
    # tables, to the solver's tolerance
    OPTIONS = {"backup": ("banded", "pallas")}
    CHECKS = ("table_shape_mismatch", "policy_gap", "eval_rel_err")

    def __init__(self, cfg, mix, seed, test=False):
        super().__init__(cfg, mix, seed, test)
        from repro.core import sweep_solve

        self.program = {"sweep_solve": sweep_solve}
        m = self.mix
        w2s = np.geomspace(m["w2"]["low"], m["w2"]["high"], m["w2"]["n"])
        grid = [(float(r), float(w)) for r in m["rho"] for w in w2s]
        # every seed solves the same grid, handed over in its own order:
        # the work, and so the time, does not depend on the seed
        order = np.random.default_rng(self.seed).permutation(len(grid))
        self.points_rw = [grid[i] for i in order]
        self.points = [
            (deploy.arrival_rate(cfg, r), w) for r, w in self.points_rw
        ]
        self.specs = [deploy.program_spec(cfg, r, w) for r, w in self.points_rw]
        self.grids = []

    def warm(self):
        from repro.core.rvi import ACCEL_RHO_THRESHOLD

        high = max(r for r, _ in self.points_rw) >= ACCEL_RHO_THRESHOLD
        accel = "mpi" if high else "none"
        if accel != self.mix["accel"]:
            raise BenchError(
                f"accel='auto' resolves to {accel!r}; the mix expects "
                f"{self.mix['accel']!r}")
        self.call(-1)
        self.grids.clear()

    def call(self, i):
        with span("bench.sweep_solve"):
            res = self.program["sweep_solve"](self.specs, **self.options)
        self.grids.append(res)
        return 1

    def e2e(self):
        return {"table_s": sum(s for s, _ in self.calls) / len(self.calls)}

    def counters(self):
        its = [r.rvi.iterations for g in self.grids for r in g]
        return {"backups_per_spec": float(np.mean(its)) if its else None}

    @staticmethod
    def answers(grid):
        return [
            None if r is None else dict(
                policy=np.asarray(r.rvi.policy), s_max=r.spec.s_max,
                w_bar=r.eval.w_bar, p_bar=r.eval.p_bar, g=r.eval.g)
            for r in grid
        ]

    def check(self, control=False):
        refs = compare.reference_tables(self.cfg, self.lat, self.zeta, self.points)
        if control:
            grids = [compare.reference_tables(
                self.cfg, self.lat, self.zeta, self.points, dtype=np.float32)]
        else:
            # every call solves the same grid: compare a sample of calls
            # drawn from the seed, and always the last
            n = len(self.grids)
            pick = np.random.default_rng([self.seed, 2]).choice(
                n, min(n, self.mix["check"]["grids"]), replace=False)
            grids = [self.answers(self.grids[i]) for i in sorted({*pick, n - 1})]
        out = {"table_shape_mismatch": 0, "policy_gap": 0.0, "eval_rel_err": 0.0}
        for answers in grids:
            nums = compare.compare_tables(
                self.cfg, self.lat, self.zeta, self.points, refs, answers)
            out = {k: max(out[k], v) for k, v in nums.items()}
        self._attempted = len(self.specs) * len(self.grids)
        self._failed = out["table_shape_mismatch"]
        return out


ENTRY = SweepSolve
