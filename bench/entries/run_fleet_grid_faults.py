"""run_fleet_grid with a fault schedule per lane: a fleet that loses a
replica under load.

Each call samples every lane's arrivals from the seed, as
``run_fleet_grid`` does, and with them, from the same lane key, the
lane's faults (the configuration's ``faults``): one replica, drawn, is
down for ``outage_ms`` from a uniform fraction in ``start_fraction`` of
the lane's expected horizon, and each replica's batch attempts straggle
by ``straggle_mult`` with probability ``p_straggle`` (``attempts`` per
replica, the last repeating).  The fleet grid then runs with ``faults=``
those schedules, and the checked lanes replay through
bench/reference/faults.py under the same schedules.

A lane is served short when its served and dropped requests do not add
up to its sampled ones.  ``crash_mismatch`` counts the checked lanes
(each router apart, lanes with a near tie left to ``tied_rel_err``) whose
crashed attempts or dropped requests differ from the reference's.
"""
from __future__ import annotations

import inspect

import numpy as np

import common
import compare
from common import BenchError
from entry import MMPP2, span
from reference.faults import simulate_fleet_faults

RunFleetGrid = common._load("entries", "run_fleet_grid").RunFleetGrid

FAULT_KEYS = ("n_crashes", "n_dropped")


class RunFleetGridFaults(RunFleetGrid):
    CHECKS = RunFleetGrid.CHECKS + ("crash_mismatch",)

    def __init__(self, cfg, mix, seed, test=False):
        from repro.serving import run_fleet_grid

        if "faults" not in inspect.signature(run_fleet_grid).parameters:
            raise BenchError("this program's run_fleet_grid takes no faults=")
        super().__init__(cfg, mix, seed, test)
        self.program["run_fleet_grid_faults"] = run_fleet_grid
        # two more epochs per boundary: one outage is two boundaries
        self.max_epochs += 4
        if isinstance(self.process, MMPP2):
            self.horizon = self.process.steps / (self.rate + 1.0 / self.process.dwell)
        else:
            self.horizon = self.process.n / self.rate

    def reset(self):
        super().reset()
        # per window call: crashed attempts, dropped requests, finite
        # boundaries, instances that left a boundary unreplayed, and
        # steps executed per arrival of the fullest lane
        self.per_call = {"crashes": [], "dropped": [], "fault_bounds": [],
                         "unreplayed": [], "steps_per_request": []}

    def schedules(self, keys):
        """One FaultSchedule per lane, drawn from the lane's key."""
        from repro.serving import FaultSchedule

        f = self.cfg["faults"]
        M = self.cfg["replicas"]
        lo, hi = f["start_fraction"]
        out = []
        for key in np.asarray(keys):
            rng = np.random.default_rng([int(w) for w in key])
            bounds = np.full((M, 2), np.inf)
            start = rng.uniform(lo, hi) * self.horizon
            bounds[rng.integers(M)] = (start, start + f["outage_ms"])
            mult = np.where(rng.random((M, f["attempts"])) < f["p_straggle"],
                            f["straggle_mult"], 1.0)
            out.append(FaultSchedule(bounds=bounds, mult=mult,
                                     max_retries=f["max_retries"]))
        return out

    def call(self, i):
        keys = self.keys(i)
        with span("bench.sample"):
            times = self.process.sample(keys)
            faults = self.schedules(keys)
        n_lane = np.array([np.isfinite(t).sum() for t in times])
        t_lane = np.array([np.where(np.isfinite(t), t, 0.0).max(initial=0.0)
                           for t in times])
        with span("bench.pad"):
            arr = self.program["pad"](times, size=self.slots)
        with span("bench.dispatch"):
            out = self.dispatch(arr, faults)
        padded = np.isfinite(arr).sum(axis=1)
        self.pad_faults += int((padded != n_lane).sum())

        def lanes(k):
            return np.asarray(out[k]).reshape(self.lanes, -1)

        short = np.abs(lanes("n_served") + lanes("n_dropped") - n_lane[:, None])
        self.short_requests += int(short.sum())
        self.lane_faults += int(
            (short != 0).sum() + np.asarray(out["incomplete"]).sum())
        self.n_lanes.append(n_lane)
        self.t_lanes.append(t_lane)
        bounds = np.array([np.isfinite(s.bounds).sum() for s in faults])
        replayed = np.asarray(out["fcur"]).sum(axis=-1).reshape(self.lanes, -1)
        calls = self.per_call
        calls["crashes"].append(int(lanes("n_crashes").sum()))
        calls["dropped"].append(int(lanes("n_dropped").sum()))
        calls["fault_bounds"].append(int(bounds.sum()))
        calls["unreplayed"].append(int((replayed < bounds[:, None]).sum()))
        calls["steps_per_request"].append(out["steps_executed"] / n_lane.max())
        want = self.mix["check"]["lanes"] - len(self.kept)
        if want > 0:
            for lane in self.pick.choice(self.lanes, min(want, self.mix["check"]["per_call"]), replace=False):
                aggs = [
                    {k: lanes(k)[lane, r].item()
                     for k in compare.INT_KEYS + compare.SUM_KEYS + FAULT_KEYS}
                    for r in range(len(self.routers()))
                ]
                self.kept.append(
                    (np.array(times[lane]), arr[lane].copy(), aggs, faults[lane]))
        return int(n_lane.sum())

    def dispatch(self, arr, faults):
        return self.program["run_fleet_grid_faults"](
            self.table[None], arr, routers=tuple(self.cfg["routers"]),
            n_replicas=self.cfg["replicas"], means=self.lat, zeta=self.zeta,
            b_max=self.cfg["b_max"], max_epochs=self.max_epochs,
            faults=faults, **self.options)

    def counters(self):
        out = super().counters()
        out.update(self.per_call)
        steps = self.per_call["steps_per_request"]
        out["steps_per_request"] = float(np.mean(steps)) if steps else None
        return out

    def reference(self, arrivals, router, sch, dtype=np.float64):
        return simulate_fleet_faults(
            arrivals, self.table, self.cfg["replicas"], router, self.lat,
            self.zeta, self.cfg["b_max"], sch.bounds, sch.mult,
            sch.max_retries, dtype)

    def check(self, control=False):
        pairs = []
        pad_bad = 0
        for times, padded, aggs, sch in self.kept:
            # the padded lane holds exactly the sampled arrivals, in order
            want = np.sort(times[np.isfinite(times)])
            got = padded[np.isfinite(padded)]
            pad_bad += int(got.shape != want.shape or not np.array_equal(got, want))
            for r, router in enumerate(self.routers()):
                ref = self.reference(want, router, sch)
                if control:
                    prog = self.reference(want, router, sch, np.float32)
                else:
                    prog = aggs[r]
                pairs.append((prog, ref))
        out = compare.compare_lanes(pairs)
        crash = 0
        for prog, ref in pairs:
            if ref["near_ties"]:
                out["tied_rel_err"] = max(
                    out["tied_rel_err"],
                    *(compare._rel(prog[k], ref[k]) for k in FAULT_KEYS))
            else:
                crash += any(int(prog[k]) != int(ref[k]) for k in FAULT_KEYS)
        out["crash_mismatch"] = crash
        n_lane = np.concatenate(self.n_lanes)
        t_lane = np.concatenate(self.t_lanes)
        out["served_mismatch"] = 0 if control else self.lane_faults
        out["pad_mismatch"] = 0 if control else self.pad_faults + pad_bad
        out["rate_z"] = compare.rate_z(
            int(n_lane.sum()), float(t_lane.sum()), self.rate, self.process.idc())
        out.update(self.process.checks(n_lane, t_lane))
        self._attempted = sum(w for _, w in self.calls) * len(self.routers())
        self._failed = 0 if control else self.short_requests
        return out


ENTRY = RunFleetGridFaults
