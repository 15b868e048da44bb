"""The comparisons that decide ``correct``, against the plain reference.

Each returns {name: value}; a run is correct when every value is at or
under its limit from the traffic file.  Solve cells compare the tables a
grid produced with the reference's optimal tables of the same operating
points; simulation cells compare a sample of lanes with the reference
simulators given the same arrival times, plus counts over every lane.
"""
from __future__ import annotations

import math

import numpy as np

from reference.sim import simulate_fleet, simulate_single
from reference.smdp import Chain, solve_point

INT_KEYS = ("n_served", "n_batches", "n_epochs")
SUM_KEYS = ("t_final", "energy", "lat_sum")


def _rel(a, b):
    a, b = float(a), float(b)
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-300)


# ---------------------------------------------------------------------------
# solve cells
# ---------------------------------------------------------------------------


def reference_tables(cfg, lat, zeta, points, dtype=np.float64):
    """The reference's solved operating points, one dict per (lam, w2)."""
    return [
        solve_point(lam, lat, zeta, cfg["b_min"], cfg["b_max"], cfg["w1"], w2,
                    cfg["s_max"], dtype=dtype)
        for lam, w2 in points
    ]


def compare_tables(cfg, lat, zeta, points, refs, tables):
    """Numbers of one grid's answers against the reference.

    ``tables`` holds per operating point a dict with policy, s_max, w_bar,
    p_bar and g (the objective with the grid's own c_o), or None where the
    grid gave no answer.

      table_shape_mismatch  answers missing or at another truncation level
      policy_gap            worst relative excess of the answer's cost rate
                            over the optimum, both on the reference chain
      eval_rel_err          worst relative error of the reported W_bar,
                            P_bar and g against the reference's evaluation
                            of the answer's own table
    """
    shape = 0
    gap = err = 0.0
    for (lam, w2), ref, ans in zip(points, refs, list(tables) + [None] * len(refs)):
        if ans is None or int(ans["s_max"]) != ref["s_max"] or (
            len(ans["policy"]) != ref["s_max"] + 2
        ):
            shape += 1
            continue
        chain = Chain(lam, lat, zeta, cfg["b_min"], cfg["b_max"], cfg["w1"],
                      w2, ref["s_max"], ref["c_o"])
        try:
            g, w_bar, p_bar = chain.evaluate(ans["policy"])[:3]
        except (ValueError, np.linalg.LinAlgError):
            gap = err = math.inf
            continue
        gap = max(gap, (g - ref["g"]) / abs(ref["g"]))
        err = max(err, _rel(ans["w_bar"], w_bar), _rel(ans["p_bar"], p_bar),
                  _rel(ans["g"], g))
    shape += max(0, len(tables) - len(refs))
    return {"table_shape_mismatch": shape, "policy_gap": gap,
            "eval_rel_err": err}


# ---------------------------------------------------------------------------
# simulation cells
# ---------------------------------------------------------------------------


def reference_lane(kind, arrivals, table, cfg, lat, zeta, router=None,
                   dtype=np.float64):
    if kind == "single":
        return simulate_single(arrivals, table, lat, zeta, cfg["b_max"], dtype)
    return simulate_fleet(arrivals, table, cfg["replicas"], router, lat, zeta,
                          cfg["b_max"], dtype)


def compare_lanes(pairs):
    """Numbers of sampled lanes: [(program aggregates, reference)].

      count_mismatch  lanes with no near tie whose served, batch or epoch
                      count differs from the reference
      sum_rel_err     worst relative error of t_final, energy and lat_sum
                      over those lanes
      tied_rel_err    worst relative error of every aggregate over lanes
                      where a completion fell within 1e-12 of an arrival
                      (their order rests on the clock's last bits)
    """
    count = 0
    sum_err = tied = 0.0
    for prog, ref in pairs:
        errs = [_rel(prog[k], ref[k]) for k in SUM_KEYS]
        if ref["near_ties"]:
            tied = max(tied, *errs, *(_rel(prog[k], ref[k]) for k in INT_KEYS))
            continue
        count += any(int(prog[k]) != int(ref[k]) for k in INT_KEYS)
        sum_err = max(sum_err, *errs)
    return {"count_mismatch": count, "sum_rel_err": sum_err,
            "tied_rel_err": tied}


def rate_z(n_total, t_total, lam, idc):
    """z-score of the pooled arrival rate: arrivals over the lanes' spans.

    ``idc`` is the asymptotic index of dispersion of the process's counts
    (1 for Poisson), so the rate's relative spread is sqrt(idc / n).
    """
    if n_total <= 0 or t_total <= 0:
        return math.inf
    return abs(n_total / t_total / lam - 1.0) / math.sqrt(idc / n_total)
