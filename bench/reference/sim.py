"""Plain reference simulators: one batch server, and M routed replicas.

Written from the serving semantics, with Python loops and numpy alone; it
imports nothing of the system under test.

One server.  Requests join a FIFO queue.  A decision is taken whenever
the server is idle and no arrival is due: every arrival at or before the
clock is admitted first.  With q waiting, the action is
a = clip(table[min(q, L - 1)], 0, min(q, b_max)).  a = 0 waits for the
next arrival; once no arrival is left, a = 0 with q > 0 serves
min(q, b_max) (the tail drain) and with q = 0 ends the run.  A batch of a
runs for means[a] and uses zeta[a] of energy; each request's latency is
its batch's completion time less its arrival time.

M replicas.  One clock; each step does the first of: admit (route) the
next due arrival; let the lowest-numbered replica that awaits a decision
decide; advance the clock to the next arrival or completion (an arrival
wins a tie, the lowest-numbered replica wins a tie of completions).  A
replica awaits a decision at the start, when a request is routed to it
while idle, when its batch completes, and, once the stream is over, while
it is idle with requests queued.  Routers, by a score whose lowest value
wins (lowest index on ties), with busy = 1 while a batch runs:

  jsq          2 min(q, 16383) + busy
  batch_aware  gap * 2^15 + jsq, gap = min(thr(q) + busy * min(q, 16383),
               16383), where thr(q) counts the further arrivals a replica
               with q queued needs after this one before its table serves

Arithmetic runs in ``dtype``: float64 is the reference, float32 the
control.  A completion that falls within ``tie_rel`` (relative) of an
arrival time is counted in ``near_ties``: the order of such a pair rests
on the last bits of the clock, so a lane that has one is compared by a
looser rule.
"""
from __future__ import annotations

import math
from bisect import bisect_right

import numpy as np

INF = math.inf
_QCAP = (1 << 14) - 1
_GAP_SHIFT = 1 << 15
TIE_REL = 1e-12


def _rounder(dtype):
    dt = np.dtype(dtype)
    if dt == np.float64:
        return dt, (lambda x: x)
    return dt, (lambda x: float(dt.type(x)))


def actions(table, b_max, upto):
    """a(q) for q = 0..upto (q >= L repeats the table's last entry)."""
    table = [int(x) for x in table]
    L = len(table)
    return [max(0, min(table[min(q, L - 1)], q, b_max)) for q in range(upto + 1)]


def threshold_gaps(table):
    """thr(q): further arrivals, after the one joining q, before a serve."""
    row = [int(x) for x in table]
    L = len(row)
    out = []
    for q in range(L):
        tgt = q + 1
        if tgt >= L:
            out.append(0 if row[-1] > 0 else L)
            continue
        ns = next((s for s in range(tgt, L) if row[s] > 0), None)
        out.append(L if ns is None else ns - tgt)
    return out


def _near_tie(times, t, lo, tol):
    j = bisect_right(times, t, lo)
    return (j > 0 and t - times[j - 1] <= tol) or (
        j < len(times) and times[j] - t <= tol)


def simulate_single(arrivals, table, means, zeta, b_max, dtype=np.float64,
                    tie_rel=TIE_REL):
    dt, rnd = _rounder(dtype)
    arr = np.asarray(arrivals, dtype=np.float64)
    arr = arr[np.isfinite(arr)].astype(dt)
    times = arr.tolist()
    n = len(times)
    L = len(table)
    qc = max(L, b_max + 1)
    act = actions(table, b_max, qc)
    # next_serve[q]: the smallest q' >= q whose action serves (None: never)
    next_serve = [None] * (qc + 1)
    nxt = None
    for q in range(qc, -1, -1):
        if act[q] > 0:
            nxt = q
        next_serve[q] = nxt
    distinct = n < 2 or bool(np.all(np.diff(arr) > 0))
    means_l = [rnd(float(x)) for x in means]
    zeta_l = [rnd(float(x)) for x in zeta]
    comp = np.empty(n, dtype=dt)
    t = 0.0
    served = adm = n_eps = n_bat = ties = 0
    energy = 0.0
    while True:
        adm = bisect_right(times, t, adm)
        q = adm - served
        a = act[min(q, qc)]
        n_eps += 1
        if a == 0:
            if adm < n:
                if distinct:
                    # each further arrival is a wait decision of its own
                    # until the queue reaches a serving length
                    ns = next_serve[min(q + 1, qc)]
                    d = n - adm if ns is None else max(ns - q, 1)
                    d = min(d, n - adm)
                    n_eps += d - 1
                    t = times[adm + d - 1]
                else:
                    t = times[adm]
                continue
            if q == 0:
                break
            a = min(q, b_max)
        t_done = rnd(t + means_l[a])
        comp[served:served + a] = t_done
        served += a
        n_bat += 1
        energy = rnd(energy + zeta_l[a])
        ties += _near_tie(times, t_done, adm, tie_rel * t_done)
        t = t_done
    lat = comp[:served] - arr[:served]
    return dict(n_served=served, n_batches=n_bat, n_epochs=n_eps,
                t_final=float(t), energy=float(energy),
                lat_sum=float(np.sum(lat, dtype=dt)), near_ties=ties)


def simulate_fleet(arrivals, table, n_replicas, router, means, zeta, b_max,
                   dtype=np.float64, tie_rel=TIE_REL):
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
    comp = np.empty(n, dtype=dt)
    queues = [[] for _ in range(M)]
    heads = [0] * M
    qlen = [0] * M
    busy = [INF] * M
    needs = [True] * M
    t = 0.0
    ia = served = n_eps = n_bat = ties = 0
    energy = 0.0
    while True:
        dead = ia >= n
        if dead:
            for m in range(M):
                if busy[m] == INF and qlen[m] > 0:
                    needs[m] = True
        if not dead and times[ia] <= t:
            best = None
            for m in range(M):
                q = qlen[m]
                bf = 0 if busy[m] == INF else 1
                score = 2 * min(q, _QCAP) + bf
                if router == "batch_aware":
                    gap = min(thr[min(q, L - 1)] + bf * min(q, _QCAP), _QCAP)
                    score += gap * _GAP_SHIFT
                if best is None or score < best:
                    best, m_r = score, m
            queues[m_r].append(ia)
            qlen[m_r] += 1
            if busy[m_r] == INF:
                needs[m_r] = True
            ia += 1
            continue
        if True in needs:
            m = needs.index(True)
            needs[m] = False
            n_eps += 1
            q = qlen[m]
            a = act[min(q, qc)]
            if a == 0 and dead and q > 0:
                a = min(q, b_max)
            if a > 0:
                t_done = rnd(t + means_l[a])
                busy[m] = t_done
                h = heads[m]
                comp[queues[m][h:h + a]] = t_done
                heads[m] = h + a
                qlen[m] -= a
                served += a
                n_bat += 1
                energy = rnd(energy + zeta_l[a])
                ties += _near_tie(times, t_done, ia, tie_rel * t_done)
            continue
        t_c = min(busy)
        if not dead and times[ia] <= t_c:
            t = times[ia]
        elif t_c < INF:
            m_c = busy.index(t_c)
            t = t_c
            busy[m_c] = INF
            needs[m_c] = True
        else:
            break
    lat = comp[:n] - arr
    if served != n:
        raise RuntimeError(f"reference fleet served {served} of {n}")
    return dict(n_served=served, n_batches=n_bat, n_epochs=n_eps,
                t_final=float(t), energy=float(energy),
                lat_sum=float(np.sum(lat, dtype=dt)), near_ties=ties)
