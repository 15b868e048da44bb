"""Plain reference of M routed replicas that fail: outages, crashed and
retried batches, dropped requests, stragglers.

It extends ``simulate_fleet`` of reference/sim.py, whose routers, action
rule, threshold gaps, near-tie rule and dtype discipline it imports, with
the degraded-mode semantics of a frozen fault schedule.  Python loops and
numpy alone; it imports nothing of the system under test.  With no
boundary and unit multipliers it is ``simulate_fleet`` step for step.

Schedule.  ``bounds[m]`` = [start, end, start, end, ...], sorted and
+inf-padded: replica m is down on each [start, end).  A cursor per
replica counts the boundaries replayed so far; an odd cursor is down.
``mult[m, j]`` multiplies the service time of replica m's j-th batch
attempt, crashed attempts counted, j clipped to the last column.
``max_retries`` bounds the consecutive crashes of one replica.

One step does the first of these:

  0. Replay one due boundary (at or before the clock), of the lowest-
     numbered replica that has one.  A down-start silences the replica's
     pending decision; if the replica holds a crashed batch (2), the
     batch's requests go back to the front of its queue, in order, and its
     count of consecutive crashes grows by one, unless that would pass
     ``max_retries``: then they are dropped (never served) and the count
     restarts.  A repair re-arms a replica that is idle with requests
     queued.
  1. Route the next due arrival.  Scores as in sim.py, where a replica
     holding a crashed batch counts as busy and the batch's requests as
     queued; a down replica adds 2^30, so every up replica wins and, with
     all down, the least-loaded one does.  The arrival wakes its replica
     if that is idle, up and holds no crashed batch.
  2. The lowest-numbered replica awaiting a decision decides, as in
     sim.py.  A batch of a > 0 takes svc = means[a] * mult[m, attempt]
     and would complete at t + svc.  If the replica's next down-start
     lies strictly before that, the batch is held as crashed: the replica
     stays idle, its requests leave the queue, and it burns zeta[a] (ds -
     t) / svc of energy.  Otherwise it is served: zeta[a] of energy, and
     the replica's count of consecutive crashes restarts.  Both are
     batch attempts; only the served are ``n_batches``.
  3. Advance the clock to the next arrival, else the earliest completion
     (the lowest-numbered replica on ties, waking it), else the earliest
     boundary of a replica with queued requests or a crashed batch.
     Boundaries of other replicas replay once the clock passes them.

Before each step, once every arrival is routed, replicas that are idle,
up, hold no crashed batch and have requests queued await a decision (the
tail drain).  These are the rules of the program's kernel where the
contract leaves them open: one boundary per step, replayed before any
admission or decision at the same clock; crashes decided when the batch
is dispatched, against the next down-start; completions before
boundaries at the same time; the attempt count as the straggler index.

Returns the keys of sim.py's ``simulate_fleet`` plus ``n_crashes`` (batch
attempts that crashed) and ``n_dropped`` (requests dropped).  A
completion within ``tie_rel`` of an arrival, or of the down-start it was
tested against, counts in ``near_ties``.
"""
from __future__ import annotations

import math
from collections import deque

import numpy as np

from reference.sim import (
    _GAP_SHIFT,
    _QCAP,
    INF,
    TIE_REL,
    _near_tie,
    _rounder,
    actions,
    threshold_gaps,
)

_DOWN = 1 << 30


def simulate_fleet_faults(arrivals, table, n_replicas, router, means, zeta,
                          b_max, bounds, mult, max_retries, dtype=np.float64,
                          tie_rel=TIE_REL):
    dt, rnd = _rounder(dtype)
    arr = np.asarray(arrivals, dtype=np.float64)
    arr = arr[np.isfinite(arr)].astype(dt)
    times = arr.tolist()
    n = len(times)
    M = int(n_replicas)
    L = len(table)
    qc = max(L, b_max + 1)
    act = actions(table, b_max, qc)
    thr = threshold_gaps(table)
    if router not in ("jsq", "batch_aware"):
        raise ValueError(f"no reference for router {router!r}")
    means_l = [rnd(float(x)) for x in means]
    zeta_l = [rnd(float(x)) for x in zeta]
    bnd = [[rnd(float(x)) for x in row if math.isfinite(x)]
           for row in np.asarray(bounds, dtype=np.float64).reshape(M, -1)]
    mul = [[rnd(float(x)) for x in row] for row in np.asarray(mult)]
    comp = np.empty(n, dtype=dt)
    served = np.zeros(n, dtype=bool)
    queues = [deque() for _ in range(M)]
    crashed = [[] for _ in range(M)]  # a batch held until its down-start
    busy = [INF] * M
    needs = [True] * M
    fcur = [0] * M
    rty = [0] * M
    nbat = [0] * M
    t = 0.0
    ia = n_srv = n_eps = n_bat = n_crash = n_drop = ties = 0
    energy = 0.0

    def next_bound(m):
        return bnd[m][fcur[m]] if fcur[m] < len(bnd[m]) else INF

    while True:
        dead = ia >= n
        if dead:
            for m in range(M):
                if (busy[m] == INF and queues[m] and fcur[m] % 2 == 0
                        and not crashed[m]):
                    needs[m] = True
        m_b = next((m for m in range(M) if next_bound(m) <= t), None)
        if m_b is not None:
            if fcur[m_b] % 2 == 0:
                if crashed[m_b]:
                    if rty[m_b] + 1 > max_retries:
                        n_drop += len(crashed[m_b])
                        rty[m_b] = 0
                    else:
                        queues[m_b].extendleft(reversed(crashed[m_b]))
                        rty[m_b] += 1
                    crashed[m_b] = []
                needs[m_b] = False
            elif queues[m_b] and busy[m_b] == INF and not crashed[m_b]:
                needs[m_b] = True
            fcur[m_b] += 1
            continue
        if not dead and times[ia] <= t:
            best = None
            for m in range(M):
                q = len(queues[m]) + len(crashed[m])
                bf = 0 if busy[m] == INF and not crashed[m] else 1
                score = 2 * min(q, _QCAP) + bf + _DOWN * (fcur[m] % 2)
                if router == "batch_aware":
                    gap = min(thr[min(q, L - 1)] + bf * min(q, _QCAP), _QCAP)
                    score += gap * _GAP_SHIFT
                if best is None or score < best:
                    best, m_r = score, m
            queues[m_r].append(ia)
            if busy[m_r] == INF and fcur[m_r] % 2 == 0 and not crashed[m_r]:
                needs[m_r] = True
            ia += 1
            continue
        if True in needs:
            m = needs.index(True)
            needs[m] = False
            n_eps += 1
            q = len(queues[m])
            a = act[min(q, qc)]
            if a == 0 and dead and q > 0:
                a = min(q, b_max)
            if a > 0:
                svc = rnd(means_l[a] * mul[m][min(nbat[m], len(mul[m]) - 1)])
                t_done = rnd(t + svc)
                nbat[m] += 1
                batch = [queues[m].popleft() for _ in range(a)]
                ds = next_bound(m)
                ties += abs(ds - t_done) <= tie_rel * t_done
                if ds < t_done:
                    crashed[m] = batch
                    n_crash += 1
                    energy = rnd(energy + rnd(rnd(zeta_l[a] * rnd(ds - t)) / svc))
                else:
                    busy[m] = t_done
                    comp[batch] = t_done
                    served[batch] = True
                    n_srv += a
                    n_bat += 1
                    rty[m] = 0
                    energy = rnd(energy + zeta_l[a])
                    ties += _near_tie(times, t_done, ia, tie_rel * t_done)
            continue
        t_c = min(busy)
        t_b = min((next_bound(m) for m in range(M) if queues[m] or crashed[m]),
                  default=INF)
        if not dead and times[ia] <= t_c and times[ia] <= t_b:
            t = times[ia]
        elif t_c < INF and t_c <= t_b:
            m_c = busy.index(t_c)
            t = t_c
            busy[m_c] = INF
            needs[m_c] = True
        elif t_b < INF:
            t = t_b
        else:
            break
    if n_srv + n_drop != n:
        raise RuntimeError(
            f"reference fleet served {n_srv} and dropped {n_drop} of {n}")
    lat = comp[served] - arr[served]
    return dict(n_served=n_srv, n_batches=n_bat, n_epochs=n_eps,
                t_final=float(t), energy=float(energy),
                lat_sum=float(np.sum(lat, dtype=dt)), near_ties=int(ties),
                n_crashes=n_crash, n_dropped=n_drop)
