"""Plain reference for the batching SMDP: truncated chain, optimal policy.

Written from the model's definition (arXiv 2501.02181, Sec. IV-V) with
numpy alone; it imports nothing of the system under test.

State s in {0, ..., s_max, S_o}: requests waiting (S_o stands for "more
than s_max" and counts as s_max).  Action a = 0 waits for the next
arrival; a in [b_min, min(s, b_max)] serves a batch of a.  Arrivals are
Poisson(lam) and service is deterministic with mean l(a), so the number of
arrivals during a service is Poisson(lam * l(a)).

  sojourn   y(s, 0) = 1 / lam            y(s, a) = l(a)
  holding   h(s, 0) = s / lam^2          h(s, a) = s l(a) / lam + l(a)^2 / 2
  energy    e(s, 0) = 0                  e(s, a) = zeta(a)
  cost      c = w1 h + w2 e, plus c_o * y at S_o (the abstract tail cost)
  moves     wait: s -> s + 1 (s_max -> S_o -> S_o);  serve a from s: the
            base t = s - a gains k arrivals, t + k > s_max lands in S_o

The optimal policy minimises the long-run cost rate g = sum(mu c) /
sum(mu y) over the embedded chain's stationary law mu; it is found by
policy iteration, exact to the working precision.  The abstract cost is
calibrated as c_o = max(100, 2 g_greedy) on the chain with c_o = 0, and
the truncation grows by 1.5x until the tail share
Delta = mu(S_o) c(S_o) / sum(mu y) falls under 1e-3.

``dtype`` is the working precision: float64 is the reference; float32
is the control that a sound program must beat.
"""
from __future__ import annotations

import math

import numpy as np

DELTA = 1e-3
GROW = 1.5
MAX_S_MAX = 4096
C_O_FLOOR = 100.0


class Chain:
    """The truncated SMDP of one operating point at one truncation level."""

    def __init__(self, lam, lat, zeta, b_min, b_max, w1, w2, s_max, c_o,
                 dtype=np.float64):
        self.dtype = np.dtype(dtype)
        self.lam = float(lam)
        self.s_max = int(s_max)
        self.b_min, self.b_max = int(b_min), int(b_max)
        S = self.s_max + 2
        A = self.b_max + 1
        self.S, self.A = S, A
        s_val = np.arange(S, dtype=np.float64)
        s_val[-1] = self.s_max
        acts = np.arange(A)
        lat = np.asarray(lat, dtype=np.float64)  # lat[a], a = 0..b_max
        zeta = np.asarray(zeta, dtype=np.float64)
        self.feasible = (acts[None, :] <= s_val[:, None]) & (acts >= self.b_min)
        self.feasible[:, 0] = True
        y = np.empty((S, A))
        y[:, 0] = 1.0 / lam
        y[:, 1:] = lat[None, 1:]
        hold = np.empty((S, A))
        hold[:, 0] = s_val / lam**2
        hold[:, 1:] = s_val[:, None] * lat[None, 1:] / lam + 0.5 * lat[None, 1:] ** 2
        energy = np.zeros((S, A))
        energy[:, 1:] = zeta[None, 1:]
        cost = w1 * hold + w2 * energy
        cost[-1, :] += c_o * y[-1, :]
        # arrivals during a service of a: Poisson(lam l(a)), k = 0..s_max
        ks = np.arange(self.s_max + 1, dtype=np.float64)
        lgam = np.array([math.lgamma(k + 1.0) for k in ks])
        pmf = np.zeros((A, self.s_max + 1))
        for a in range(1, A):
            m = lam * lat[a]
            pmf[a] = np.exp(ks * math.log(m) - m - lgam)
        self.y, self.hold, self.energy, self.cost = (
            x.astype(self.dtype) for x in (y, hold, energy, cost))
        self.pmf = pmf.astype(self.dtype)
        self.s_int = np.minimum(np.arange(S), self.s_max)

    def transitions(self, policy):
        """(S, S) transition matrix of the embedded chain under ``policy``."""
        S, s_max = self.S, self.s_max
        P = np.zeros((S, S), dtype=self.dtype)
        for s in range(S):
            a = int(policy[s])
            if a == 0:
                P[s, min(s + 1, S - 1)] = 1.0
                continue
            base = self.s_int[s] - a
            width = s_max - base + 1  # k = 0 .. s_max - base stay inside
            P[s, base:s_max + 1] = self.pmf[a, :width]
            P[s, S - 1] += max(0.0, 1.0 - float(self.pmf[a, :width].sum()))
        return P

    def evaluate(self, policy):
        """(g, W_bar, P_bar, Delta, h) of a policy, in the working dtype."""
        policy = np.asarray(policy, dtype=np.int64)
        rows = np.arange(self.S)
        if policy.shape != (self.S,) or not self.feasible[rows, policy].all():
            raise ValueError("policy shape or action infeasible")
        P = self.transitions(policy)
        c = self.cost[rows, policy]
        y = self.y[rows, policy]
        # stationary law: mu (I - P) = 0 with sum(mu) = 1
        M = (np.eye(self.S, dtype=self.dtype) - P).T
        M[-1, :] = 1.0
        rhs = np.zeros(self.S, dtype=self.dtype)
        rhs[-1] = 1.0
        mu = np.linalg.solve(M, rhs)
        denom = mu @ y
        g = (mu @ c) / denom
        w_bar = (mu @ self.hold[rows, policy]) / denom
        p_bar = (mu @ self.energy[rows, policy]) / denom
        delta = mu[-1] * c[-1] / denom
        # relative values: (I - P) h + g y = c with h(0) = 0
        B = np.eye(self.S, dtype=self.dtype) - P
        B[:, 0] = y
        x = np.linalg.solve(B, c)
        h = x.copy()
        h[0] = 0.0
        return float(g), float(w_bar), float(p_bar), float(delta), h, float(x[0])

    def q_values(self, h, g):
        """Q(s, a) = c - g y + E[h(next)], +inf where infeasible."""
        S, A, s_max = self.S, self.A, self.s_max
        T = s_max + 1
        # window[t, k] = h(t + k) for t + k <= s_max; the rest goes to S_o
        idx = np.arange(T)[:, None] + np.arange(T)[None, :]
        window = np.where(idx <= s_max, h[np.minimum(idx, s_max)], 0.0)
        inside = window @ self.pmf.T  # (T, A): sum_k p_a(k) h(t + k)
        csum = np.cumsum(self.pmf, axis=1)  # mass of k <= K
        tail = np.maximum(0.0, 1.0 - csum[:, ::-1].T)  # (T, A): from base t
        serve = inside + tail * h[-1]
        Q = np.full((S, A), np.inf, dtype=self.dtype)
        nxt = np.minimum(np.arange(S) + 1, S - 1)
        Q[:, 0] = self.cost[:, 0] - g * self.y[:, 0] + h[nxt]
        for a in range(max(1, self.b_min), A):
            ok = self.feasible[:, a]
            base = np.clip(self.s_int - a, 0, s_max)
            Q[ok, a] = (self.cost[ok, a] - g * self.y[ok, a]
                        + serve[base[ok], a])
        return Q

    def greedy(self):
        s = self.s_int
        act = np.maximum(np.minimum(s, self.b_max), self.b_min)
        return np.where(s >= self.b_min, act, 0).astype(np.int64)

    def optimal(self, max_rounds=200):
        """Policy iteration from the greedy policy; returns (policy, eval)."""
        policy = self.greedy()
        ev = self.evaluate(policy)
        for _ in range(max_rounds):
            g, h = ev[5], ev[4]
            Q = self.q_values(h, g)
            best = Q.min(axis=1)
            cur = Q[np.arange(self.S), policy]
            # keep the current action unless another is better beyond
            # rounding: policy iteration then cannot cycle
            tol = 64 * np.finfo(self.dtype).eps * np.maximum(1.0, np.abs(best))
            new = np.where(cur <= best + tol, policy, Q.argmin(axis=1))
            if np.array_equal(new, policy):
                break
            policy = new
            ev = self.evaluate(policy)
        return policy, ev


def solve_point(lam, lat, zeta, b_min, b_max, w1, w2, s_max, dtype=np.float64):
    """The optimal table of one operating point with the sweep's rules.

    Returns a dict: policy (length s_max + 2), s_max, c_o, g, w_bar, p_bar,
    delta.
    """
    kw = dict(lam=lam, lat=lat, zeta=zeta, b_min=b_min, b_max=b_max, w1=w1,
              w2=w2, dtype=dtype)
    probe = Chain(s_max=s_max, c_o=0.0, **kw)
    g_greedy = probe.evaluate(probe.greedy())[0]
    c_o = max(C_O_FLOOR, 2.0 * g_greedy)
    while True:
        chain = Chain(s_max=s_max, c_o=c_o, **kw)
        policy, ev = chain.optimal()
        if ev[3] < DELTA or s_max >= MAX_S_MAX:
            return dict(policy=policy, s_max=s_max, c_o=c_o, g=ev[0],
                        w_bar=ev[1], p_bar=ev[2], delta=ev[3])
        s_max = min(int(math.ceil(s_max * GROW)), MAX_S_MAX)
