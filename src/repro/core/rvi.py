"""Relative value iteration (Algorithm 1), accelerants, and App.-F baselines.

The discrete-time backup is

    J_{i+1}(s) = min_{a in A_s} { c~(s,a) + sum_j m~(j|s,a) H_i(j) }      (29)
    H_{i+1}(s) = J_{i+1}(s) - J_{i+1}(s*)

with span-based stopping.  Backup implementations:

  * dense  — einsum against the (S, A, S) transition tensor;
  * banded — exploits the transition structure m(j|s,a) = p^{[a]}_{j-s+a}:
             per action the backup is a windowed correlation of H with the
             arrival pmf, an O(A*S*K) computation instead of O(A*S^2).
             This is the form the Pallas TPU kernel (kernels/bellman.py)
             implements; here it doubles as its jnp oracle.
  * pallas — the same banded math with the windowed-matmul core on the
             Pallas kernel; the batched loop dispatches one spec-batched
             kernel launch per lockstep iteration (bellman_banded_batched).

Acceleration (``accel=`` on both RVI entry points)
--------------------------------------------------

At rho >= 0.7 the embedded chain mixes slowly and plain RVI needs many
hundreds of lockstep backups.  Classical fixes fail here in a specific
way: the iteration only converges *modulo constants* (H is a relative
value function, fixed up to an additive shift), so the natural metric is
the span seminorm  sp(x) = max(x) - min(x), under which the backup is
nonexpansive.  Momentum and textbook Anderson mixing form affine
combinations of past iterates whose *constant components* differ —
J_{i+1}(s*) drifts from step to step — so the extrapolated step picks up
an uncontrolled shift plus a secant direction fitted in a norm the
operator does not contract; the result is the divergence observed on
this repo's high-rho sweeps.  Two principled accelerants are provided:

  * accel="mpi" — batched modified policy iteration: every ``period``
    backups freeze the greedy policy and polish H by the *exact*
    gauge-fixed policy-evaluation linear solve (evaluate.
    policy_matrix_banded / policy_eval_linear, vmapped across the spec
    batch).  A polish is accepted per spec only if its one-step span
    residual shrinks (and the linear solve was finite — multichain
    degeneracies reject safely), so the iteration can never do worse
    than plain RVI.
  * accel="anderson" — span-seminorm-safe Anderson: the secant history
    is built from gauge-fixed iterates (H pinned to H(s*) = 0 before
    every difference), the least-squares step is Tikhonov-regularized,
    and each candidate is evaluated by one extra backup: it is taken
    only where its span residual does not exceed the plain backup's
    (rejection restarts the history).  Gauge-fixing removes the
    constant drift; rejection restores the monotone span decrease that
    makes plain RVI converge.

Both run float64 single-phase (they need tens of backups, so the f32
lockstep phase of the plain path buys nothing) and finish with an exact
linear-solve gain for the final greedy policy.  The scalar f64
``solve()`` path stays the untouched oracle these are tested against.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .evaluate import (
    policy_eval_linear,
    policy_matrix_banded,
    policy_matrix_banded_modulated,
)
from .smdp import SMDPSpec, TruncatedSMDP, build_smdp, phase_rho

#: rho at which the MPI polish starts paying for itself — below it plain
#: lockstep converges in ~100 backups and the polish machinery (anchor
#: accel solve, linear solves, extra jit phases) is pure overhead; above
#: it mixing slows exponentially and MPI wins big.  Shared by every
#: accel="auto" decision (sweep_solve and the modulated loops).
ACCEL_RHO_THRESHOLD = 0.5

#: every solver contraction runs at full precision: a TPU otherwise feeds
#: float32 matmuls to the MXU as bfloat16, which the f32 lockstep phase
#: cannot converge through
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class RVIResult:
    policy: np.ndarray  # (S,) batch-size action per truncated state
    g: float  # average expected cost per unit time (g~ = g^)
    h: np.ndarray  # (S,) relative value function of the DTMDP
    iterations: int
    span: float
    converged: bool
    wall_time_s: float


# ---------------------------------------------------------------------------
# Backups
# ---------------------------------------------------------------------------


def dense_backup(c_tilde: jnp.ndarray, m_tilde: jnp.ndarray, h: jnp.ndarray):
    """Q(s,a) = c~(s,a) + sum_j m~(j|s,a) h(j); infeasible entries are +inf."""
    return c_tilde + jnp.einsum("saj,j->sa", m_tilde, h, precision=_HI)


def banded_backup(
    c_tilde: jnp.ndarray,  # (S, A), +inf at infeasible
    pmfs: jnp.ndarray,  # (A, K+1) arrival pmfs (row 0 unused)
    tails: jnp.ndarray,  # (A, T) overflow mass per base state t
    scale: jnp.ndarray,  # (S, A) eta / y(s, a)
    s_max: int,
    h: jnp.ndarray,  # (S,) with h[-1] = h(S_o)
):
    """Structured backup; mathematically equal to dense_backup.

    For a != 0 and base t = s - a:
        (M^ h)(s) = sum_{k=0}^{s_max - t} p^{[a]}_k h(t + k) + tail(a,t) h(S_o)
    For a == 0: (M^ h)(s) = h(min(s+1, s_max -> S_o)); S_o self-loops.
    Discretized:  Q = c~ + scale * (M^ h) + (1 - scale) * h(s).
    """
    S = h.shape[0]
    A = pmfs.shape[0]
    T = s_max + 1  # base states 0..s_max
    K = pmfs.shape[1] - 1
    # windowed H matrix: Hwin[t, k] = h[t + k] masked to t + k <= s_max
    t_idx = jnp.arange(T)[:, None]
    k_idx = jnp.arange(K + 1)[None, :]
    j = t_idx + k_idx
    valid = j <= s_max
    hwin = jnp.where(valid, h[jnp.minimum(j, s_max)], 0.0)
    # G[t, a] = sum_k pmfs[a, k] hwin[t, k]  -> correlation as a matmul (MXU!)
    G = jnp.matmul(hwin, pmfs.T, precision=_HI)  # (T, A)
    G = G + tails.T * h[S - 1]  # overflow mass towards S_o
    # scatter to (S, A): for state s and action a, base t = s_val(s) - a
    s_val = jnp.minimum(jnp.arange(S), s_max)  # S_o behaves as s_max
    base = s_val[:, None] - jnp.arange(A)[None, :]  # (S, A); <0 -> infeasible
    base_c = jnp.clip(base, 0, s_max)
    mh_serve = G[base_c, jnp.arange(A)[None, :]]  # (S, A)
    # a == 0 column: next state s+1 (or S_o)
    nxt = jnp.where(jnp.arange(S) < s_max, jnp.arange(S) + 1, S - 1)
    mh_wait = h[nxt]
    mh = mh_serve.at[:, 0].set(mh_wait)
    q = c_tilde + scale * mh + (1.0 - scale) * h[:, None]
    return q


def pallas_backup(
    c_tilde, pmfs, tails, scale, s_max: int, h,
):
    """banded_backup with the windowed-matmul core on the Pallas TPU kernel.

    Identical math; the G[t,a] correlation runs in kernels/bellman.py
    (interpret mode on CPU).  Used by backup="pallas".
    """
    from repro.kernels import ops as kops

    S = h.shape[0]
    A = pmfs.shape[0]
    T = s_max + 1
    K = pmfs.shape[1]
    h_main = jnp.zeros(T + K, dtype=jnp.float32).at[:T].set(h[:T].astype(jnp.float32))
    G = kops.bellman_backup(h_main, pmfs, tails.T, h[S - 1])  # (T, A)
    G = G.astype(h.dtype)
    s_val = jnp.minimum(jnp.arange(S), s_max)
    base = s_val[:, None] - jnp.arange(A)[None, :]
    base_c = jnp.clip(base, 0, s_max)
    mh_serve = G[base_c, jnp.arange(A)[None, :]]
    nxt = jnp.where(jnp.arange(S) < s_max, jnp.arange(S) + 1, S - 1)
    mh = mh_serve.at[:, 0].set(h[nxt])
    return c_tilde + scale * mh + (1.0 - scale) * h[:, None]


def pallas_backup_batched(c_tilde, pmfs, tails, scale, s_max: int, h):
    """Spec-batched banded backup on the Pallas kernel (one launch per step).

    Identical math to vmap(banded_backup); the G[n,t,a] correlation runs in
    kernels/bellman.py::bellman_banded_batched with the spec axis as a grid
    dimension.  The kernel core is float32 — the batched driver keeps the
    exact final policy extraction on the float64 jnp path regardless.

    c_tilde/scale: (N, S, A); pmfs: (N, A, K); tails: (N, A, T); h: (N, S).
    """
    from repro.kernels import ops as kops

    N, S, A = c_tilde.shape
    T = s_max + 1
    K = pmfs.shape[2]
    h_main = jnp.zeros((N, T + K), dtype=jnp.float32)
    h_main = h_main.at[:, :T].set(h[:, :T].astype(jnp.float32))
    G = kops.bellman_backup_batched(
        h_main, pmfs, tails.transpose(0, 2, 1), h[:, S - 1]
    )  # (N, T, A)
    G = G.astype(h.dtype)
    s_val = jnp.minimum(jnp.arange(S), s_max)
    base = s_val[:, None] - jnp.arange(A)[None, :]
    base_c = jnp.clip(base, 0, s_max)
    mh_serve = G[:, base_c, jnp.arange(A)[None, :]]  # (N, S, A)
    nxt = jnp.where(jnp.arange(S) < s_max, jnp.arange(S) + 1, S - 1)
    mh = mh_serve.at[:, :, 0].set(h[:, nxt])
    return c_tilde + scale * mh + (1.0 - scale) * h[:, :, None]


def _batched_backup(backup_kind: str):
    """The (N, S, A) Q-backup for the batched loops (trace-time dispatch)."""
    if backup_kind == "pallas":
        return pallas_backup_batched
    return jax.vmap(banded_backup, in_axes=(0, 0, 0, 0, None, 0))


#: in-window pmf mass below this is dropped by the banded backups; the
#: overflow tails stay exact, so the induced backup error is O(BAND_TOL * |h|)
BAND_TOL = 1e-14


def trimmed_band(pm: np.ndarray, tol: float = BAND_TOL) -> int:
    """Width of the pmf band holding all but ``tol`` of every action's mass.

    ``pm`` is (..., A, K+1) with a zero row for a = 0.  The correlation in
    the banded backup is O(S * A * band), so trimming the vanishing tail of
    the arrival pmfs (their support is concentrated around lam * l(a))
    directly cuts every RVI iteration's work.
    """
    serve = pm[..., 1:, :]
    width = int((serve.cumsum(-1) < 1.0 - tol).sum(-1).max()) + 2
    return min(width, pm.shape[-1])


def make_banded_inputs(mdp: TruncatedSMDP):
    """Precompute (pmfs, tails, scale) for banded_backup from a built SMDP."""
    spec = mdp.spec
    # truncate pmf columns to k <= s_max (k larger always lands in S_o)
    pm = mdp.arrival_pmfs[:, : spec.s_max + 1].copy()
    # tails[a, t] = 1 - sum_{k <= s_max - t} p_k: reversed cumulative mass
    csum = np.cumsum(pm, axis=-1)
    tails = np.maximum(0.0, 1.0 - csum[:, ::-1])
    tails[0, :] = 0.0
    scale = mdp.eta / mdp.y
    return (
        jnp.asarray(pm, dtype=jnp.float64),
        jnp.asarray(tails, dtype=jnp.float64),
        jnp.asarray(scale, dtype=jnp.float64),
    )


# ---------------------------------------------------------------------------
# RVI driver
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("max_iter", "backup_kind", "s_max"))
def _rvi_loop(
    c_tilde,
    m_tilde,
    pmfs,
    tails,
    scale,
    eps: float,
    eps_rel: float,
    max_iter: int,
    backup_kind: str,
    s_max: int,
    ref_state: int = 0,
):
    S = c_tilde.shape[0]

    def backup(h):
        if backup_kind == "dense":
            return dense_backup(c_tilde, m_tilde, h)
        if backup_kind == "pallas":
            return pallas_backup(c_tilde, pmfs, tails, scale, s_max, h)
        return banded_backup(c_tilde, pmfs, tails, scale, s_max, h)

    def cond(carry):
        i, h, span, g = carry
        # relative criterion: costs scale with w2, so a purely absolute span
        # threshold stalls convergence detection for large weights
        thresh = jnp.maximum(eps, eps_rel * jnp.abs(g))
        return jnp.logical_and(i < max_iter, span >= thresh)

    def body(carry):
        i, h, _, _ = carry
        q = backup(h)
        j = jnp.min(q, axis=1)
        g = j[ref_state]
        h_new = j - g
        diff = h_new - h
        span = jnp.max(diff) - jnp.min(diff)
        return i + 1, h_new, span, g

    h0 = jnp.zeros(S, dtype=c_tilde.dtype)
    i, h, span, g = jax.lax.while_loop(cond, body, (0, h0, jnp.inf, 0.0))
    q = backup(h)
    policy = jnp.argmin(q, axis=1)
    return policy, g, h, i, span


def relative_value_iteration(
    mdp: TruncatedSMDP,
    eps: float = 1e-2,
    max_iter: int = 10_000,
    backup: str = "banded",
    eps_rel: float = 2e-4,
    accel: str = "none",
    accel_period: int = 6,
    accel_memory: int = 5,
    accel_safeguard: bool = True,
) -> RVIResult:
    """Solve the discretized MDP; the policy is eps-optimal for the SMDP.

    ``accel`` ("none" | "mpi" | "anderson") routes through the accelerated
    batched machinery with N = 1 (see relative_value_iteration_batched);
    the default stays the plain loop — the exact oracle path of solve().
    """
    t0 = time.perf_counter()
    if accel != "none":
        if backup == "dense":
            raise ValueError("accelerated RVI requires a banded backup")
        pmfs, tails, scale = make_banded_inputs(mdp)
        pm_full = np.asarray(pmfs)  # (A, s_max+1) f64
        pm_trim = pm_full[:, : trimmed_band(pm_full)]
        policies, g, h, span, it_conv, _, _ = _run_accel(
            jnp.asarray(mdp.c_tilde, jnp.float64)[None],
            jnp.asarray(pm_trim, jnp.float64)[None],
            jnp.asarray(tails, jnp.float64)[None],
            jnp.asarray(scale, jnp.float64)[None],
            mdp.spec.s_max,
            eps,
            eps_rel,
            max_iter,
            accel,
            backup,
            None,
            accel_period,
            accel_memory,
            accel_safeguard,
        )
        span_f = float(span[0])
        g_f = float(g[0])
        return RVIResult(
            policy=policies[0],
            g=g_f,
            h=h[0],
            iterations=int(it_conv[0]),
            span=span_f,
            converged=span_f < max(eps, eps_rel * abs(g_f)),
            wall_time_s=time.perf_counter() - t0,
        )
    c_tilde = jnp.asarray(mdp.c_tilde)
    if backup == "dense":
        m_tilde = jnp.asarray(mdp.m_tilde)
        pmfs = tails = scale = jnp.zeros((1, 1))
    else:
        m_tilde = jnp.zeros((1, 1, 1))
        pmfs, tails, scale = make_banded_inputs(mdp)
    policy, g, h, it, span = _rvi_loop(
        c_tilde,
        m_tilde,
        pmfs,
        tails,
        scale,
        eps,
        eps_rel,
        max_iter,
        backup,
        mdp.spec.s_max,
    )
    policy = np.asarray(policy)
    it = int(it)
    return RVIResult(
        policy=policy,
        g=float(g),
        h=np.asarray(h),
        iterations=it,
        span=float(span),
        converged=it < max_iter,
        wall_time_s=time.perf_counter() - t0,
    )


# ---------------------------------------------------------------------------
# Batched RVI: one jitted while_loop solves a whole spec sweep
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BatchedRVIResult:
    """Per-spec RVI outputs for a BatchedSMDP, leading axis = spec."""

    policies: np.ndarray  # (N, S)
    g: np.ndarray  # (N,)
    h: np.ndarray  # (N, S)
    iterations: np.ndarray  # (N,) backup count at which each spec converged
    span: np.ndarray  # (N,)
    converged: np.ndarray  # (N,) bool
    wall_time_s: float
    accel: str = "none"  # which accelerant produced this result
    accel_accepts: Optional[np.ndarray] = None  # (N,) accepted accel steps
    accel_rejects: Optional[np.ndarray] = None  # (N,) span-increasing steps
    #   (taken when safeguard is off, refused when it is on)
    report: Optional["SolveReport"] = None  # guard=True attaches certificates

    def unstack(self, i: int) -> RVIResult:
        return RVIResult(
            policy=self.policies[i],
            g=float(self.g[i]),
            h=self.h[i],
            iterations=int(self.iterations[i]),
            span=float(self.span[i]),
            converged=bool(self.converged[i]),
            wall_time_s=self.wall_time_s / len(self.g),
        )


# ---------------------------------------------------------------------------
# Guardrail ladder: per-spec NaN/Inf sentinels + divergence detection, with
# an automatic fallback ladder so one pathological spec degrades to a slower
# solve path (or a per-spec quarantine re-solve) instead of poisoning the
# whole vmapped batch.  Enabled with guard=True on both batched entry points;
# core.sweep turns it on by default.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SolveReport:
    """Residual certificates + guardrail record of one batched solve.

    ``span`` against ``eps`` (with the relative floor already folded into
    ``converged``) is the per-spec convergence certificate.  A spec is
    ``healthy`` when its g/h are finite AND it converged — a non-finite or
    still-growing span residual at the iteration cap is how divergence
    shows up, so the two sentinels together cover NaN/Inf poisoning and
    span-residual divergence alike.  ``rungs`` maps each fallback rung
    that fired to the spec rows it was applied to (in the order tried);
    ``quarantined`` rows were masked out of the batch and re-solved
    through the scalar float64 oracle path; ``failed`` rows stayed
    unhealthy after the entire ladder (their outputs carry NaN/Inf — the
    batch still completes, callers decide what to do with those rows).
    """

    eps: float
    span: np.ndarray  # (N,) final span residuals
    converged: np.ndarray  # (N,) bool
    healthy: np.ndarray  # (N,) bool — finite g/h and converged
    rungs: Dict[str, List[int]] = dataclasses.field(default_factory=dict)
    quarantined: List[int] = dataclasses.field(default_factory=list)
    failed: List[int] = dataclasses.field(default_factory=list)

    @property
    def any_fired(self) -> bool:
        return bool(self.rungs) or bool(self.quarantined)

    @staticmethod
    def merged(
        parts: Sequence[Tuple["SolveReport", Sequence[int]]],
        n: int,
        eps: float,
    ) -> "SolveReport":
        """Fold per-batch reports into one n-spec report (sweep rounds).

        ``parts`` pairs each report with the caller-level index of every
        batch row; later parts overwrite earlier ones per spec (a regrown
        spec's final solve wins), and a spec counts as failed only if its
        LAST solve left it unhealthy.
        """
        span = np.full(n, np.nan)
        converged = np.zeros(n, dtype=bool)
        healthy = np.zeros(n, dtype=bool)
        rungs: Dict[str, List[int]] = {}
        quarantined: List[int] = []
        ever_failed: set = set()
        for rep, rows in parts:
            rows = list(rows)
            span[rows] = rep.span
            converged[rows] = rep.converged
            healthy[rows] = rep.healthy
            for name, applied in rep.rungs.items():
                rungs.setdefault(name, []).extend(rows[i] for i in applied)
            quarantined.extend(rows[i] for i in rep.quarantined)
            ever_failed.update(rows[i] for i in rep.failed)
        return SolveReport(
            eps=eps,
            span=span,
            converged=converged,
            healthy=healthy,
            rungs=rungs,
            quarantined=sorted(set(quarantined)),
            failed=sorted(i for i in ever_failed if not healthy[i]),
        )


def _spec_health(res: BatchedRVIResult) -> np.ndarray:
    """(N,) bool NaN/Inf sentinel + divergence check per spec."""
    g = np.asarray(res.g, dtype=np.float64)
    h = np.asarray(res.h, dtype=np.float64).reshape(g.shape[0], -1)
    finite = np.isfinite(g) & np.isfinite(h).all(axis=-1)
    return finite & np.asarray(res.converged, dtype=bool)


def _writable(res: BatchedRVIResult) -> BatchedRVIResult:
    """Copy the per-spec arrays so ladder rungs can patch rows in place."""
    return dataclasses.replace(
        res,
        policies=np.array(res.policies),
        g=np.array(res.g, dtype=np.float64),
        h=np.array(res.h, dtype=np.float64),
        iterations=np.array(res.iterations),
        span=np.array(res.span, dtype=np.float64),
        converged=np.array(res.converged, dtype=bool),
    )


def _patch_rows(
    res: BatchedRVIResult, sub: BatchedRVIResult, dst: np.ndarray, src: np.ndarray
) -> None:
    res.policies[dst] = np.asarray(sub.policies)[src]
    res.g[dst] = np.asarray(sub.g)[src]
    res.h[dst] = np.asarray(sub.h)[src]
    res.iterations[dst] = np.asarray(sub.iterations)[src]
    res.span[dst] = np.asarray(sub.span)[src]
    res.converged[dst] = np.asarray(sub.converged)[src]


def _guarded_batched(
    batch,
    eps: float,
    max_iter: int,
    eps_rel: float,
    h0,
    mixed_precision: bool,
    accel: str,
    backup: str,
    accel_kw: dict,
) -> BatchedRVIResult:
    """Guardrail ladder around the batched RVI (see SolveReport).

    Rung order mirrors likely-culprit order: the Pallas kernel falls back
    to the jnp banded backup, the accelerant (and any caller-supplied warm
    start — a poisoned anchor h0 turns every row NaN) falls back to the
    plain lockstep loop, mixed precision falls back to single-phase
    float64, and rows that survive all of that are quarantined: masked out
    and re-solved one by one through the scalar float64 oracle path.  Only
    the unhealthy rows ride each rung, so a healthy batch pays one numpy
    health check and nothing else.
    """

    def run(b, h0_, mp, ac, bk):
        return relative_value_iteration_batched(
            b,
            eps=eps,
            max_iter=max_iter,
            eps_rel=eps_rel,
            h0=h0_,
            mixed_precision=mp,
            accel=ac,
            backup=bk,
            **accel_kw,
        )

    res = run(batch, h0, mixed_precision, accel, backup)
    healthy = _spec_health(res)
    rungs: Dict[str, List[int]] = {}
    quarantined: List[int] = []
    failed: List[int] = []
    if not healthy.all():
        res = _writable(res)
        bad = np.flatnonzero(~healthy)
        ladder = []
        bk = backup
        if bk == "pallas":
            ladder.append(
                ("backup_banded", dict(mp=mixed_precision, ac=accel, bk="banded", drop_h0=False))
            )
            bk = "banded"
        if accel != "none" or h0 is not None:
            ladder.append(
                ("plain_restart", dict(mp=mixed_precision, ac="none", bk=bk, drop_h0=True))
            )
        if mixed_precision:
            ladder.append(
                ("float64", dict(mp=False, ac="none", bk=bk, drop_h0=True))
            )
        for name, opt in ladder:
            if bad.size == 0:
                break
            sub = batch.take([int(i) for i in bad])
            sub_h0 = (
                None
                if (opt["drop_h0"] or h0 is None)
                else np.asarray(h0)[bad]
            )
            sub_res = run(sub, sub_h0, opt["mp"], opt["ac"], opt["bk"])
            ok = _spec_health(sub_res)
            rungs[name] = [int(i) for i in bad]
            if ok.any():
                _patch_rows(res, sub_res, bad[ok], np.flatnonzero(ok))
            bad = bad[~ok]
        if bad.size:
            rungs["quarantine"] = [int(i) for i in bad]
            for i in bad:
                i = int(i)
                quarantined.append(i)
                oracle = relative_value_iteration(
                    build_smdp(batch.specs[i]),
                    eps=eps,
                    max_iter=max_iter,
                    backup="banded",
                    eps_rel=eps_rel,
                    accel="none",
                )
                if (
                    np.isfinite(oracle.g)
                    and np.isfinite(oracle.h).all()
                    and oracle.converged
                ):
                    res.policies[i] = oracle.policy
                    res.g[i] = oracle.g
                    res.h[i] = oracle.h
                    res.iterations[i] = oracle.iterations
                    res.span[i] = oracle.span
                    res.converged[i] = True
                else:
                    failed.append(i)
        healthy = _spec_health(res)
    return dataclasses.replace(
        res,
        report=SolveReport(
            eps=eps,
            span=np.asarray(res.span),
            converged=np.asarray(res.converged),
            healthy=healthy,
            rungs=rungs,
            quarantined=quarantined,
            failed=failed,
        ),
    )


def _guarded_modulated(
    mbatch,
    eps: float,
    max_iter: int,
    eps_rel: float,
    h0,
    accel: str,
    accel_period: int,
) -> BatchedRVIResult:
    """Guardrail ladder for the modulated batched RVI.

    Same discipline as _guarded_batched with the rungs that apply to the
    product chain (always float64, no Pallas backup): the MPI accelerant
    and any caller h0 fall back to the plain lockstep loop, and rows still
    unhealthy are quarantined into single-spec plain-f64 re-solves — the
    oracle path the K = 1 bitwise tests pin the modulated solver against.
    """

    def run(b, h0_, ac):
        return relative_value_iteration_modulated(
            b,
            eps=eps,
            max_iter=max_iter,
            eps_rel=eps_rel,
            h0=h0_,
            accel=ac,
            accel_period=accel_period,
        )

    res = run(mbatch, h0, accel)
    healthy = _spec_health(res)
    rungs: Dict[str, List[int]] = {}
    quarantined: List[int] = []
    failed: List[int] = []
    if not healthy.all():
        res = _writable(res)
        bad = np.flatnonzero(~healthy)
        if accel != "none" or h0 is not None:
            sub_res = run(mbatch.take([int(i) for i in bad]), None, "none")
            ok = _spec_health(sub_res)
            rungs["plain_restart"] = [int(i) for i in bad]
            if ok.any():
                _patch_rows(res, sub_res, bad[ok], np.flatnonzero(ok))
            bad = bad[~ok]
        if bad.size:
            rungs["quarantine"] = [int(i) for i in bad]
            for i in bad:
                i = int(i)
                quarantined.append(i)
                oracle = run(mbatch.take([i]), None, "none")
                if _spec_health(oracle)[0]:
                    _patch_rows(res, oracle, np.array([i]), np.array([0]))
                else:
                    failed.append(i)
        healthy = _spec_health(res)
    return dataclasses.replace(
        res,
        report=SolveReport(
            eps=eps,
            span=np.asarray(res.span),
            converged=np.asarray(res.converged),
            healthy=healthy,
            rungs=rungs,
            quarantined=quarantined,
            failed=failed,
        ),
    )


@partial(jax.jit, static_argnames=("max_iter", "s_max", "backup_kind"))
def _rvi_loop_batched(
    c_tilde,  # (N, S, A)
    pmfs,  # (N, A, K+1)
    tails,  # (N, A, T)
    scale,  # (N, S, A)
    eps: float,
    eps_rel: float,
    max_iter: int,
    s_max: int,
    h0=None,  # (N, S) warm start; zeros when None
    ref_state: int = 0,
    backup_kind: str = "banded",
):
    """Vectorized Algorithm 1: every spec runs the banded backup in lockstep.

    The loop stops when EVERY spec's span is below its (relative) threshold;
    already-converged specs keep refining, which only tightens their h.
    """
    N, S, _ = c_tilde.shape
    backup = _batched_backup(backup_kind)

    def thresh(g):
        return jnp.maximum(eps, eps_rel * jnp.abs(g))

    def cond(carry):
        i, h, span, g, _ = carry
        return jnp.logical_and(i < max_iter, jnp.any(span >= thresh(g)))

    def body(carry):
        i, h, _, _, it_conv = carry
        q = backup(c_tilde, pmfs, tails, scale, s_max, h)  # (N, S, A)
        j = jnp.min(q, axis=-1)
        g = j[:, ref_state]
        h_new = j - g[:, None]
        diff = h_new - h
        span = jnp.max(diff, axis=-1) - jnp.min(diff, axis=-1)
        it_conv = jnp.where((span < thresh(g)) & (it_conv < 0), i + 1, it_conv)
        return i + 1, h_new, span, g, it_conv

    if h0 is None:
        h0 = jnp.zeros((N, S), dtype=c_tilde.dtype)
    init = (
        0,
        jnp.asarray(h0, dtype=c_tilde.dtype),
        jnp.full((N,), jnp.inf, dtype=c_tilde.dtype),
        jnp.zeros((N,), dtype=c_tilde.dtype),
        jnp.full((N,), -1, dtype=jnp.int32),
    )
    i, h, span, g, it_conv = jax.lax.while_loop(cond, body, init)
    q = backup(c_tilde, pmfs, tails, scale, s_max, h)
    policies = jnp.argmin(q, axis=-1)
    it_conv = jnp.where(it_conv < 0, i, it_conv)
    return policies, g, h, i, span, it_conv


# ---------------------------------------------------------------------------
# Accelerated batched loops (see module docstring): modified policy
# iteration with a banded linear-solve polish, and span-safe Anderson.
# Both count *backups* (the dominant cost) in ``nb`` and record per-spec
# acceptance/rejection of the accelerated steps.
# ---------------------------------------------------------------------------


def _span(diff):
    return jnp.max(diff, axis=-1) - jnp.min(diff, axis=-1)


def _solve_spd_small(a, b):
    """x with a x = b for tiny (..., M, M) symmetric positive-definite a.

    Unrolled Gauss-Jordan elimination (no pivoting needed for SPD): only
    elementwise ops, so a float64 system stays float64 on every backend —
    TPUs have no float64 LU — and a Tikhonov-regularized Anderson Gram
    matrix too ill-conditioned for a float32 factor still solves.
    """
    M = a.shape[-1]
    for k in range(M):
        f = a[..., :, k] / a[..., k, k][..., None]  # (..., M)
        f = f.at[..., k].set(0.0)  # row k stays as the pivot row
        a = a - f[..., :, None] * a[..., k, None, :]
        b = b - f * b[..., k, None]
    return b / jnp.diagonal(a, axis1=-2, axis2=-1)


@partial(jax.jit, static_argnames=("max_iter", "s_max", "backup_kind", "period"))
def _rvi_loop_batched_mpi(
    c_tilde,
    pmfs,
    tails,
    scale,
    eps: float,
    eps_rel: float,
    max_iter: int,
    s_max: int,
    backup_kind: str = "banded",
    period: int = 10,
    h0=None,
    ref_state: int = 0,
):
    """Batched modified policy iteration: RVI backups + periodic exact polish.

    Every ``period`` backups the greedy policy is frozen and h is replaced
    by its exact gauge-fixed policy evaluation (one vmapped banded linear
    solve), followed by one verification backup.  The polish is accepted
    per spec only where it is finite and shrinks the span residual, and
    never touches specs that already converged (per-spec masking) — so the
    loop is at worst plain RVI plus an amortized O(S^3/period) overhead.
    """
    N, S, A = c_tilde.shape
    backup = _batched_backup(backup_kind)
    mat = jax.vmap(policy_matrix_banded, in_axes=(0, 0, 0, None, 0))
    lin = jax.vmap(policy_eval_linear, in_axes=(0, 0, None))

    def bell(h):
        q = backup(c_tilde, pmfs, tails, scale, s_max, h)
        j = jnp.min(q, axis=-1)
        g = j[:, ref_state]
        return q, j - g[:, None], g

    def thresh(g):
        return jnp.maximum(eps, eps_rel * jnp.abs(g))

    def with_polish(args):
        q, hb, span, g, conv, nb, acc, rej = args
        pol = jnp.argmin(q, axis=-1)
        m_pi = mat(pmfs, tails, scale, s_max, pol)
        c_pi = jnp.take_along_axis(c_tilde, pol[..., None], axis=-1)[..., 0]
        g_pol, h_pol = lin(c_pi, m_pi, ref_state)
        _, hb2, g2 = bell(h_pol)
        span2 = _span(hb2 - h_pol)
        ok = (
            jnp.isfinite(g_pol)
            & jnp.all(jnp.isfinite(h_pol), axis=-1)
            & (span2 < span)
            & ~conv
        )
        h_out = jnp.where(ok[:, None], hb2, hb)
        return (
            h_out,
            jnp.where(ok, span2, span),
            jnp.where(ok, g2, g),
            nb + 1,
            acc + ok,
            rej + (~ok & ~conv),
        )

    def no_polish(args):
        _, hb, span, g, _, nb, acc, rej = args
        return hb, span, g, nb, acc, rej

    def cond(carry):
        it, _, _, span, g, _, _, _ = carry
        return jnp.logical_and(it < max_iter, jnp.any(span >= thresh(g)))

    def body(carry):
        it, nb, h, _, _, it_conv, acc, rej = carry
        q, hb, g = bell(h)
        nb = nb + 1
        span = _span(hb - h)
        conv = span < thresh(g)
        h_out, span_out, g_out, nb, acc, rej = jax.lax.cond(
            (it + 1) % period == 0,
            with_polish,
            no_polish,
            (q, hb, span, g, conv, nb, acc, rej),
        )
        it_conv = jnp.where(
            (span_out < thresh(g_out)) & (it_conv < 0), nb, it_conv
        )
        return it + 1, nb, h_out, span_out, g_out, it_conv, acc, rej

    if h0 is None:
        h0 = jnp.zeros((N, S), dtype=c_tilde.dtype)
    zi = jnp.zeros((N,), dtype=jnp.int32)
    init = (
        0,
        0,
        jnp.asarray(h0, dtype=c_tilde.dtype),
        jnp.full((N,), jnp.inf, dtype=c_tilde.dtype),
        jnp.zeros((N,), dtype=c_tilde.dtype),
        jnp.full((N,), -1, dtype=jnp.int32),
        zi,
        zi,
    )
    _, nb, h, span, g, it_conv, acc, rej = jax.lax.while_loop(cond, body, init)
    # exact final policy extraction always on the float64 jnp banded path
    q = _batched_backup("banded")(c_tilde, pmfs, tails, scale, s_max, h)
    policies = jnp.argmin(q, axis=-1)
    it_conv = jnp.where(it_conv < 0, nb, it_conv)
    return policies, g, h, nb, span, it_conv, acc, rej


@partial(
    jax.jit,
    static_argnames=("max_iter", "s_max", "backup_kind", "memory", "safeguard"),
)
def _rvi_loop_batched_anderson(
    c_tilde,
    pmfs,
    tails,
    scale,
    eps: float,
    eps_rel: float,
    max_iter: int,
    s_max: int,
    backup_kind: str = "banded",
    memory: int = 5,
    safeguard: bool = True,
    h0=None,
    ref_state: int = 0,
    reg: float = 1e-8,
):
    """Span-seminorm-safe Anderson acceleration of the batched RVI.

    Each iteration extrapolates a candidate from the last ``memory``
    gauge-fixed secant pairs (Tikhonov-regularized least squares), then
    evaluates it with one backup and accepts it per spec only where its
    span residual does not exceed the current one — the nonexpansiveness
    bound the plain backup satisfies by construction — so the safeguarded
    iteration is monotone in span and can never diverge.  Rejected specs
    fall back to the plain gauge-fixed backup step (one shared extra
    backup, paid only on iterations where some spec rejects) and restart
    their history.  With an empty history the candidate IS the plain step,
    so the scheme needs no warm-up special case.  ``safeguard=False``
    always takes the finite candidate: the known-divergent textbook
    variant, kept for the regression test.
    """
    N, S, A = c_tilde.shape
    M = memory
    backup = _batched_backup(backup_kind)

    def bell(h):
        q = backup(c_tilde, pmfs, tails, scale, s_max, h)
        j = jnp.min(q, axis=-1)
        g = j[:, ref_state]
        return j - g[:, None], g

    def thresh(g):
        return jnp.maximum(eps, eps_rel * jnp.abs(g))

    def cond(carry):
        it, _, _, _, g, span, _, _, _, _, _, _ = carry
        return jnp.logical_and(it < max_iter, jnp.any(span >= thresh(g)))

    def body(carry):
        it, nb, h, r, g, span, it_conv, dh, dr, valid, acc, rej = carry
        # plain step: h + r is the gauge-fixed backup of h (already computed)
        h_pl = h + r
        # Anderson candidate: regularized secant over gauge-fixed history
        # (empty history -> gamma = 0 -> the candidate is the plain step)
        vm = valid[..., None]
        rm = jnp.where(vm, dr, 0.0)  # (N, M, S)
        gram = jnp.einsum("nms,nks->nmk", rm, rm, precision=_HI)
        rhs = jnp.einsum("nms,ns->nm", rm, r, precision=_HI)
        tr = jnp.trace(gram, axis1=-2, axis2=-1)
        lam = (reg * tr / M + 1e-30)[:, None, None] * jnp.eye(
            M, dtype=c_tilde.dtype
        )
        gamma = _solve_spd_small(gram + lam, rhs)  # (N, M)
        h_cand = h_pl - jnp.einsum(
            "nm,nms->ns", gamma, jnp.where(vm, dh, 0.0) + rm, precision=_HI
        )
        h_cand = h_cand - h_cand[:, ref_state][:, None]  # pin the gauge
        hb_c, g_c = bell(h_cand)
        r_c = hb_c - h_cand
        span_c = _span(r_c)
        nb = nb + 1
        has_hist = valid.any(axis=-1)
        finite = jnp.all(jnp.isfinite(h_cand) & jnp.isfinite(r_c), axis=-1)
        worse = span_c > span  # the step the safeguard exists to refuse
        if safeguard:
            take = finite & ~worse
        else:
            take = finite & (has_hist | ~worse)
        rej = rej + (has_hist & finite & worse)
        acc = acc + (take & has_hist)

        def fallback(nb):
            # some spec refused its candidate: one shared plain backup
            hb_pl, g_pl = bell(h_pl)
            return hb_pl - h_pl, g_pl, nb + 1

        r_pl, g_pl, nb = jax.lax.cond(
            jnp.all(take),
            lambda nb: (r_c, g_c, nb),  # unused values; no extra backup
            fallback,
            nb,
        )
        h_new = jnp.where(take[:, None], h_cand, h_pl)
        r_new = jnp.where(take[:, None], r_c, r_pl)
        g_new = jnp.where(take, g_c, g_pl)
        span_new = jnp.where(take, span_c, _span(r_new))
        # history update: safe-mode rejection restarts the window
        reset = ~take if safeguard else jnp.zeros_like(take)
        valid = jnp.where(reset[:, None], False, valid)
        slot = it % M
        dh = dh.at[:, slot].set(h_new - h)
        dr = dr.at[:, slot].set(r_new - r)
        valid = valid.at[:, slot].set(True)
        it_conv = jnp.where(
            (span_new < thresh(g_new)) & (it_conv < 0), nb, it_conv
        )
        return it + 1, nb, h_new, r_new, g_new, span_new, it_conv, dh, dr, valid, acc, rej

    if h0 is None:
        h0 = jnp.zeros((N, S), dtype=c_tilde.dtype)
    h0 = jnp.asarray(h0, dtype=c_tilde.dtype)
    hb0, g0 = bell(h0)
    r0 = hb0 - h0
    zi = jnp.zeros((N,), dtype=jnp.int32)
    init = (
        0,
        1,
        h0,
        r0,
        g0,
        _span(r0),
        jnp.full((N,), -1, dtype=jnp.int32),
        jnp.zeros((N, M, S), dtype=c_tilde.dtype),
        jnp.zeros((N, M, S), dtype=c_tilde.dtype),
        jnp.zeros((N, M), dtype=bool),
        zi,
        zi,
    )
    out = jax.lax.while_loop(cond, body, init)
    _, nb, h, _, g, span, it_conv, _, _, _, acc, rej = out
    q = _batched_backup("banded")(c_tilde, pmfs, tails, scale, s_max, h)
    policies = jnp.argmin(q, axis=-1)
    it_conv = jnp.where(it_conv < 0, nb, it_conv)
    return policies, g, h, nb, span, it_conv, acc, rej


@partial(jax.jit, static_argnames=("s_max",))
def _exact_gain(c_tilde, pmfs, tails, scale, s_max, policies, ref_state=0):
    """Exact (linear-solve) gain + relative values of frozen greedy policies."""
    m_pi = jax.vmap(policy_matrix_banded, in_axes=(0, 0, 0, None, 0))(
        pmfs, tails, scale, s_max, policies
    )
    c_pi = jnp.take_along_axis(c_tilde, policies[..., None], axis=-1)[..., 0]
    return jax.vmap(policy_eval_linear, in_axes=(0, 0, None))(
        c_pi, m_pi, ref_state
    )


def _run_accel(
    c_tilde,  # (N, S, A) f64
    pmfs,  # (N, A, Kb) f64, band-trimmed
    tails,  # (N, A, T) f64
    scale,  # (N, S, A) f64
    s_max: int,
    eps: float,
    eps_rel: float,
    max_iter: int,
    accel: str,
    backup: str,
    h0,
    period: int,
    memory: int,
    safeguard: bool,
):
    """Shared driver for the accelerated loops + exact final gain.

    Returns (policies, g, h, span, it_conv, accepts, rejects) as numpy.
    ``g`` / ``h`` are the exact linear-solve evaluation of the final greedy
    policy wherever that solve is finite (it always is for the unichain
    policies RVI converges to); the loop's own fixed-point estimates back
    them up otherwise.
    """
    loop_args = (c_tilde, pmfs, tails, scale, eps, eps_rel, max_iter, s_max)
    if accel == "mpi":
        out = _rvi_loop_batched_mpi(
            *loop_args, backup_kind=backup, period=period, h0=h0
        )
    elif accel == "anderson":
        out = _rvi_loop_batched_anderson(
            *loop_args,
            backup_kind=backup,
            memory=memory,
            safeguard=safeguard,
            h0=h0,
        )
    else:
        raise ValueError(f"unknown accel {accel!r}")
    policies, g, h, _, span, it_conv, acc, rej = out
    g_exact, h_exact = _exact_gain(c_tilde, pmfs, tails, scale, s_max, policies)
    ok = np.isfinite(np.asarray(g_exact)) & np.isfinite(
        np.asarray(h_exact)
    ).all(axis=-1)
    g = np.where(ok, np.asarray(g_exact), np.asarray(g))
    h = np.where(ok[:, None], np.asarray(h_exact), np.asarray(h))
    return (
        np.asarray(policies),
        g,
        h,
        np.asarray(span),
        np.asarray(it_conv),
        np.asarray(acc),
        np.asarray(rej),
    )


def relative_value_iteration_batched(
    batch,  # BatchedSMDP
    eps: float = 1e-2,
    max_iter: int = 10_000,
    eps_rel: float = 2e-4,
    h0: Optional[np.ndarray] = None,
    mixed_precision: bool = True,
    accel: str = "none",
    backup: str = "banded",
    accel_period: int = 6,
    accel_memory: int = 5,
    accel_safeguard: bool = True,
    guard: bool = False,
) -> BatchedRVIResult:
    """Solve every spec of a BatchedSMDP with one jitted banded-RVI call.

    ``h0`` (N, S) warm-starts the relative values (any h0 converges to the
    same fixed point; a good one — e.g. interpolated from solved sweep
    anchors — just gets there in far fewer lockstep iterations).

    ``accel`` selects the solve path (see the module docstring):
      * "none"     — plain lockstep RVI.  With ``mixed_precision`` the bulk
        runs in float32 — halving the per-iteration memory traffic — and a
        float64 polish loop finishes from the float32 fixed point; the
        float32 stopping thresholds are floored above single-precision
        resolution so the first phase can never stall.
      * "mpi"      — modified policy iteration: every ``accel_period``
        backups, a vmapped exact policy-evaluation linear solve polishes h
        (per-spec safeguarded).  The high-rho default of the sweep engine.
      * "anderson" — span-safe restarted Anderson with ``accel_memory``
        secant pairs; ``accel_safeguard=False`` exposes the unsafeguarded
        (divergent) textbook variant for tests.
    Accelerated paths run float64 single-phase; ``iterations`` counts
    Bellman backups (including safeguard verification backups) so plain
    and accelerated counts are directly comparable.

    ``backup`` ("banded" | "pallas") picks the lockstep backup kernel; the
    final policy extraction and the float64 polish phase always use the
    float64 jnp banded path, so policies are bit-stable across backends.

    ``guard=True`` wraps the solve in the guardrail ladder (NaN/Inf
    sentinels, divergence detection, pallas->banded / accel->plain /
    f32->f64 fallbacks, per-spec quarantine re-solves) and attaches a
    SolveReport to the result; healthy batches return results identical
    to guard=False.
    """
    with TraceAnnotation("repro.rvi.solve", accel=accel):
        accel_kw = dict(
            accel_period=accel_period,
            accel_memory=accel_memory,
            accel_safeguard=accel_safeguard,
        )
        if guard:
            return _guarded_batched(
                batch,
                eps=eps,
                max_iter=max_iter,
                eps_rel=eps_rel,
                h0=h0,
                mixed_precision=mixed_precision,
                accel=accel,
                backup=backup,
                accel_kw=accel_kw,
            )
        return _solve_batched(
            batch, eps, max_iter, eps_rel, h0, mixed_precision, accel,
            backup, **accel_kw,
        )


def _solve_batched(
    batch,
    eps: float,
    max_iter: int,
    eps_rel: float,
    h0: Optional[np.ndarray],
    mixed_precision: bool,
    accel: str,
    backup: str,
    accel_period: int,
    accel_memory: int,
    accel_safeguard: bool,
) -> BatchedRVIResult:
    """relative_value_iteration_batched without the guard ladder.

    Each coarse float32 loop and each float64 finish runs in a host span
    of its own (``repro.rvi.f32`` / ``repro.rvi.f64``) that closes once
    the host holds the loop's result.
    """
    t0 = time.perf_counter()
    pm = batch.pmfs_banded
    arrs = (
        np.asarray(batch.c_tilde),
        np.asarray(pm[:, :, : trimmed_band(pm)]),
        np.asarray(batch.tails),
        np.asarray(batch.scale),
    )
    s_max = batch.specs[0].s_max
    if accel != "none":
        acc = rej = None
        it_accel = 0
        if mixed_precision:
            # accelerated f32 coarse phase on the narrow band: the floored
            # thresholds (see below) keep it from stalling, the per-spec
            # safeguards absorb any f32 conditioning loss in the polish
            with TraceAnnotation("repro.rvi.f32"):
                pm32 = pm[:, :, : trimmed_band(pm, tol=1e-8)]
                _, _, h32, span32, it_conv32, acc, rej = _run_accel(
                    jnp.asarray(arrs[0], jnp.float32),
                    jnp.asarray(pm32, jnp.float32),
                    jnp.asarray(arrs[2], jnp.float32),
                    jnp.asarray(arrs[3], jnp.float32),
                    s_max,
                    max(eps, 1e-4),
                    max(eps_rel, 1e-5),
                    max_iter,
                    accel,
                    backup,
                    None if h0 is None else jnp.asarray(h0, jnp.float32),
                    accel_period,
                    accel_memory,
                    accel_safeguard,
                )
            h0 = h32.astype(np.float64)
            it_accel = int(it_conv32.max())
            # float64 finish: plain lockstep from the f32 fixed point (a
            # handful of backups), exact gain from the final greedy policy
            with TraceAnnotation("repro.rvi.f64"):
                f64 = tuple(jnp.asarray(a, jnp.float64) for a in arrs)
                policies, g, h, _, span, it_conv = _rvi_loop_batched(
                    *f64, eps, eps_rel, max_iter, s_max, h0=jnp.asarray(h0)
                )
                g_exact, h_exact = _exact_gain(*f64[:4], s_max, policies)
                ok = np.isfinite(np.asarray(g_exact)) & np.isfinite(
                    np.asarray(h_exact)
                ).all(axis=-1)
                g = np.where(ok, np.asarray(g_exact), np.asarray(g))
                h = np.where(ok[:, None], np.asarray(h_exact), np.asarray(h))
                policies = np.asarray(policies)
                span = np.asarray(span)
                it_conv = np.asarray(it_conv) + it_accel
            acc, rej = np.asarray(acc), np.asarray(rej)
        else:
            with TraceAnnotation("repro.rvi.f64"):
                policies, g, h, span, it_conv, acc, rej = _run_accel(
                    *(jnp.asarray(a, jnp.float64) for a in arrs),
                    s_max,
                    eps,
                    eps_rel,
                    max_iter,
                    accel,
                    backup,
                    None if h0 is None else jnp.asarray(h0, jnp.float64),
                    accel_period,
                    accel_memory,
                    accel_safeguard,
                )
        return BatchedRVIResult(
            policies=policies,
            g=g,
            h=h,
            iterations=it_conv,
            span=span,
            converged=span < np.maximum(eps, eps_rel * np.abs(g)),
            wall_time_s=time.perf_counter() - t0,
            accel=accel,
            accel_accepts=acc,
            accel_rejects=rej,
        )
    if mixed_precision:
        # the float32 phase cannot resolve pmf mass below its epsilon anyway,
        # so it runs on a narrower band than the float64 polish
        with TraceAnnotation("repro.rvi.f32"):
            pm32 = pm[:, :, : trimmed_band(pm, tol=1e-8)]
            coarse = _rvi_loop_batched(
                jnp.asarray(arrs[0], jnp.float32),
                jnp.asarray(pm32, jnp.float32),
                jnp.asarray(arrs[2], jnp.float32),
                jnp.asarray(arrs[3], jnp.float32),
                max(eps, 1e-4),
                max(eps_rel, 1e-5),
                max_iter,
                s_max,
                h0=None if h0 is None else jnp.asarray(h0, jnp.float32),
                backup_kind=backup,
            )
            h0 = np.asarray(coarse[2], np.float64)
            it_coarse = int(coarse[3])
    else:
        it_coarse = 0
    with TraceAnnotation("repro.rvi.f64"):
        policies, g, h, it, span, it_conv = _rvi_loop_batched(
            *(jnp.asarray(a, jnp.float64) for a in arrs),
            eps,
            eps_rel,
            max_iter,
            s_max,
            h0=None if h0 is None else jnp.asarray(h0, jnp.float64),
        )
        policies = np.asarray(policies)
        g = np.asarray(g)
        h = np.asarray(h)
        span = np.asarray(span)
        it_conv = np.asarray(it_conv)
    return BatchedRVIResult(
        policies=policies,
        g=g,
        h=h,
        iterations=it_conv + it_coarse,
        span=span,
        converged=span < np.maximum(eps, eps_rel * np.abs(g)),
        wall_time_s=time.perf_counter() - t0,
    )


# ---------------------------------------------------------------------------
# Phase-modulated RVI: the same lockstep/MPI machinery on the (phase, queue)
# product chain.  h carries a (K, S) phase-blocked layout; the backup is the
# phase-coupled windowed correlation (one einsum against the K x K
# matrix-valued arrival pmfs), the wait column mixes phases through the
# arrival-phase matrix, and the MPI polish reuses policy_eval_linear on the
# (K*S, K*S) banded policy matrix.  Nothing is densified beyond that.
# ---------------------------------------------------------------------------


def banded_backup_modulated(
    c_tilde: jnp.ndarray,  # (K, S, A), +inf at infeasible
    pmfs: jnp.ndarray,  # (A, K, K, Kb) phase-coupled arrival pmfs
    tails: jnp.ndarray,  # (A, K, K, T) overflow mass per base state
    wait_m: jnp.ndarray,  # (K, K) arrival-phase matrix (a = 0)
    scale: jnp.ndarray,  # (K, S, A) eta / y
    s_max: int,
    h: jnp.ndarray,  # (K, S) with h[:, -1] = h(z, S_o)
):
    """Phase-blocked structured backup; K = 1 degenerates to banded_backup.

    For a != 0 and base t = s - a:
        (M^ h)(z, s) = sum_{w,k<=s_max-t} p^{[a]}_k[z,w] h(w, t+k)
                       + sum_w tail[a,z,w,t] h(w, S_o)
    For a == 0: (M^ h)(z, s) = sum_w wait_m[z,w] h(w, min(s+1 -> S_o)).
    Discretized:  Q = c~ + scale * (M^ h) + (1 - scale) * h(z, s).
    """
    K, S, A = c_tilde.shape
    T = s_max + 1
    Kb = pmfs.shape[-1]
    t_idx = jnp.arange(T)[:, None]
    k_idx = jnp.arange(Kb)[None, :]
    j = t_idx + k_idx
    valid = j <= s_max
    hwin = jnp.where(valid[None], h[:, jnp.minimum(j, s_max)], 0.0)  # (K,T,Kb)
    # G[z, t, a] = sum_{w, k} pmfs[a, z, w, k] hwin[w, t, k]  (phase-coupled
    # correlation; the K = 1 slice is exactly banded_backup's hwin @ pmfs.T)
    G = jnp.einsum("azwk,wtk->zta", pmfs, hwin, precision=_HI)
    G = G + jnp.einsum("azwt,w->zta", tails, h[:, S - 1], precision=_HI)
    s_val = jnp.minimum(jnp.arange(S), s_max)
    base = jnp.clip(s_val[:, None] - jnp.arange(A)[None, :], 0, s_max)  # (S,A)
    mh_serve = G[:, base, jnp.arange(A)[None, :]]  # (K, S, A)
    nxt = jnp.where(jnp.arange(S) < s_max, jnp.arange(S) + 1, S - 1)
    mh_wait = jnp.matmul(wait_m, h[:, nxt], precision=_HI)  # (K, S)
    mh = mh_serve.at[:, :, 0].set(mh_wait)
    return c_tilde + scale * mh + (1.0 - scale) * h[:, :, None]


def trimmed_band_modulated(pm: np.ndarray, tol: float = BAND_TOL) -> int:
    """Band width holding all but ``tol`` of every (action, phase) row.

    ``pm`` is (N, A, K, K, T); the row mass sums over end phases w.  The
    overflow tails stay full-width (exact), so trimming only drops in-band
    mass below ``tol`` — the same guarantee as trimmed_band.
    """
    row = pm[:, 1:].sum(axis=3)  # (N, A-1, K, T): mass per (a, z) over w
    tot = row.sum(axis=-1, keepdims=True)
    width = int((np.cumsum(row, axis=-1) < tot - tol).sum(-1).max()) + 2
    return min(width, pm.shape[-1])


def _span_flat(diff):
    d = diff.reshape(diff.shape[0], -1)
    return jnp.max(d, axis=-1) - jnp.min(d, axis=-1)


@partial(jax.jit, static_argnames=("max_iter", "s_max"))
def _rvi_loop_modulated(
    c_tilde,  # (N, K, S, A)
    pmfs,  # (N, A, K, K, Kb)
    tails,  # (N, A, K, K, T)
    wait_m,  # (N, K, K)
    scale,  # (N, K, S, A)
    eps: float,
    eps_rel: float,
    max_iter: int,
    s_max: int,
    h0=None,  # (N, K, S) warm start
):
    """Vectorized lockstep RVI on the product chain (gauge at (z=0, s=0))."""
    N, K, S, _ = c_tilde.shape
    backup = jax.vmap(banded_backup_modulated, in_axes=(0, 0, 0, 0, 0, None, 0))

    def thresh(g):
        return jnp.maximum(eps, eps_rel * jnp.abs(g))

    def cond(carry):
        i, h, span, g, _ = carry
        return jnp.logical_and(i < max_iter, jnp.any(span >= thresh(g)))

    def body(carry):
        i, h, _, _, it_conv = carry
        q = backup(c_tilde, pmfs, tails, wait_m, scale, s_max, h)
        j = jnp.min(q, axis=-1)  # (N, K, S)
        g = j[:, 0, 0]
        h_new = j - g[:, None, None]
        span = _span_flat(h_new - h)
        it_conv = jnp.where((span < thresh(g)) & (it_conv < 0), i + 1, it_conv)
        return i + 1, h_new, span, g, it_conv

    if h0 is None:
        h0 = jnp.zeros((N, K, S), dtype=c_tilde.dtype)
    init = (
        0,
        jnp.asarray(h0, dtype=c_tilde.dtype),
        jnp.full((N,), jnp.inf, dtype=c_tilde.dtype),
        jnp.zeros((N,), dtype=c_tilde.dtype),
        jnp.full((N,), -1, dtype=jnp.int32),
    )
    i, h, span, g, it_conv = jax.lax.while_loop(cond, body, init)
    q = backup(c_tilde, pmfs, tails, wait_m, scale, s_max, h)
    policies = jnp.argmin(q, axis=-1)
    it_conv = jnp.where(it_conv < 0, i, it_conv)
    return policies, g, h, i, span, it_conv


@partial(jax.jit, static_argnames=("max_iter", "s_max", "period"))
def _rvi_loop_modulated_mpi(
    c_tilde,
    pmfs,
    tails,
    wait_m,
    scale,
    eps: float,
    eps_rel: float,
    max_iter: int,
    s_max: int,
    period: int = 6,
    h0=None,
):
    """Modulated modified policy iteration: lockstep + periodic exact polish.

    The polish freezes the greedy (K, S) policy and replaces h by its exact
    gauge-fixed evaluation on the (K*S, K*S) banded policy matrix — same
    per-spec safeguard discipline as _rvi_loop_batched_mpi (accepted only
    where finite and span-shrinking), so it can never do worse than plain
    lockstep on the product chain.
    """
    N, K, S, A = c_tilde.shape
    backup = jax.vmap(banded_backup_modulated, in_axes=(0, 0, 0, 0, 0, None, 0))
    mat = jax.vmap(
        policy_matrix_banded_modulated, in_axes=(0, 0, 0, 0, None, 0)
    )
    lin = jax.vmap(policy_eval_linear, in_axes=(0, 0, None))

    def bell(h):
        q = backup(c_tilde, pmfs, tails, wait_m, scale, s_max, h)
        j = jnp.min(q, axis=-1)
        g = j[:, 0, 0]
        return q, j - g[:, None, None], g

    def thresh(g):
        return jnp.maximum(eps, eps_rel * jnp.abs(g))

    def with_polish(args):
        q, hb, span, g, conv, nb, acc, rej = args
        pol = jnp.argmin(q, axis=-1)  # (N, K, S)
        m_pi = mat(pmfs, tails, wait_m, scale, s_max, pol)
        c_pi = jnp.take_along_axis(c_tilde, pol[..., None], axis=-1)[
            ..., 0
        ].reshape(N, K * S)
        g_pol, h_pol_flat = lin(c_pi, m_pi, 0)
        h_pol = h_pol_flat.reshape(N, K, S)
        _, hb2, g2 = bell(h_pol)
        span2 = _span_flat(hb2 - h_pol)
        ok = (
            jnp.isfinite(g_pol)
            & jnp.all(jnp.isfinite(h_pol_flat), axis=-1)
            & (span2 < span)
            & ~conv
        )
        h_out = jnp.where(ok[:, None, None], hb2, hb)
        return (
            h_out,
            jnp.where(ok, span2, span),
            jnp.where(ok, g2, g),
            nb + 1,
            acc + ok,
            rej + (~ok & ~conv),
        )

    def no_polish(args):
        _, hb, span, g, _, nb, acc, rej = args
        return hb, span, g, nb, acc, rej

    def cond(carry):
        it, _, _, span, g, _, _, _ = carry
        return jnp.logical_and(it < max_iter, jnp.any(span >= thresh(g)))

    def body(carry):
        it, nb, h, _, _, it_conv, acc, rej = carry
        q, hb, g = bell(h)
        nb = nb + 1
        span = _span_flat(hb - h)
        conv = span < thresh(g)
        h_out, span_out, g_out, nb, acc, rej = jax.lax.cond(
            (it + 1) % period == 0,
            with_polish,
            no_polish,
            (q, hb, span, g, conv, nb, acc, rej),
        )
        it_conv = jnp.where(
            (span_out < thresh(g_out)) & (it_conv < 0), nb, it_conv
        )
        return it + 1, nb, h_out, span_out, g_out, it_conv, acc, rej

    if h0 is None:
        h0 = jnp.zeros((N, K, S), dtype=c_tilde.dtype)
    zi = jnp.zeros((N,), dtype=jnp.int32)
    init = (
        0,
        0,
        jnp.asarray(h0, dtype=c_tilde.dtype),
        jnp.full((N,), jnp.inf, dtype=c_tilde.dtype),
        jnp.zeros((N,), dtype=c_tilde.dtype),
        jnp.full((N,), -1, dtype=jnp.int32),
        zi,
        zi,
    )
    _, nb, h, span, g, it_conv, acc, rej = jax.lax.while_loop(cond, body, init)
    q = jax.vmap(banded_backup_modulated, in_axes=(0, 0, 0, 0, 0, None, 0))(
        c_tilde, pmfs, tails, wait_m, scale, s_max, h
    )
    policies = jnp.argmin(q, axis=-1)
    it_conv = jnp.where(it_conv < 0, nb, it_conv)
    return policies, g, h, nb, span, it_conv, acc, rej


@partial(jax.jit, static_argnames=("s_max",))
def _exact_gain_modulated(
    c_tilde, pmfs, tails, wait_m, scale, s_max, policies, ref_state=0
):
    """Exact linear-solve gain + relative values of frozen (K, S) policies."""
    N, K, S, _ = c_tilde.shape
    m_pi = jax.vmap(
        policy_matrix_banded_modulated, in_axes=(0, 0, 0, 0, None, 0)
    )(pmfs, tails, wait_m, scale, s_max, policies)
    c_pi = jnp.take_along_axis(c_tilde, policies[..., None], axis=-1)[
        ..., 0
    ].reshape(N, K * S)
    g, h = jax.vmap(policy_eval_linear, in_axes=(0, 0, None))(
        c_pi, m_pi, ref_state
    )
    return g, h.reshape(N, K, S)


def relative_value_iteration_modulated(
    mbatch,  # ModulatedBatchedSMDP
    eps: float = 1e-2,
    max_iter: int = 10_000,
    eps_rel: float = 2e-4,
    h0: Optional[np.ndarray] = None,
    accel: str = "auto",
    accel_period: int = 6,
    guard: bool = False,
) -> BatchedRVIResult:
    """Solve every spec of a ModulatedBatchedSMDP (one jitted call, f64).

    Returns a BatchedRVIResult whose per-spec policy/h carry the (K, S)
    phase-blocked layout.  ``accel`` in {"none", "mpi", "auto"}; "auto"
    routes through the MPI polish once any spec's *within-phase* traffic
    intensity reaches the sweep threshold (bursty phases mix slowly even
    when the mean rho is small — the burst phase sets the wall, so the
    decision keys on max_z rho_z, not on the mean).  Modulated solves run
    float64 single-phase: product chains are small (K*S states) and the
    mixed-precision coarse loop buys nothing at these sizes.  g/h are
    replaced by the exact linear-solve evaluation of the final greedy
    policy wherever that solve is finite, exactly like the accelerated
    scalar paths.  ``guard=True`` wraps the solve in the guardrail ladder
    (see relative_value_iteration_batched) and attaches a SolveReport.
    """
    if guard:
        return _guarded_modulated(
            mbatch,
            eps=eps,
            max_iter=max_iter,
            eps_rel=eps_rel,
            h0=h0,
            accel=accel,
            accel_period=accel_period,
        )
    t0 = time.perf_counter()
    pm = mbatch.pmfs_banded
    band = trimmed_band_modulated(pm)
    args = (
        jnp.asarray(mbatch.c_tilde, jnp.float64),
        jnp.asarray(pm[..., :band], jnp.float64),
        jnp.asarray(mbatch.tails, jnp.float64),
        jnp.asarray(mbatch.wait_m, jnp.float64),
        jnp.asarray(mbatch.scale, jnp.float64),
    )
    s_max = mbatch.s_max
    if accel == "auto":
        rho_z = max(
            phase_rho(sp, ph) for sp, ph in zip(mbatch.specs, mbatch.phases)
        )
        accel = "mpi" if rho_z >= ACCEL_RHO_THRESHOLD else "none"
    h0j = None if h0 is None else jnp.asarray(h0, jnp.float64)
    acc = rej = None
    if accel == "mpi":
        out = _rvi_loop_modulated_mpi(
            *args, eps, eps_rel, max_iter, s_max, period=accel_period, h0=h0j
        )
        policies, g, h, _, span, it_conv, acc, rej = out
        acc, rej = np.asarray(acc), np.asarray(rej)
    elif accel == "none":
        policies, g, h, _, span, it_conv = _rvi_loop_modulated(
            *args, eps, eps_rel, max_iter, s_max, h0=h0j
        )
    else:
        raise ValueError(f"unknown accel {accel!r} for modulated RVI")
    g_exact, h_exact = _exact_gain_modulated(*args, s_max, policies)
    ok = np.isfinite(np.asarray(g_exact)) & np.isfinite(
        np.asarray(h_exact).reshape(mbatch.n_specs, -1)
    ).all(axis=-1)
    g = np.where(ok, np.asarray(g_exact), np.asarray(g))
    h = np.where(ok[:, None, None], np.asarray(h_exact), np.asarray(h))
    span = np.asarray(span)
    return BatchedRVIResult(
        policies=np.asarray(policies),
        g=g,
        h=h,
        iterations=np.asarray(it_conv),
        span=span,
        converged=span < np.maximum(eps, eps_rel * np.abs(g)),
        wall_time_s=time.perf_counter() - t0,
        accel=accel,
        accel_accepts=acc,
        accel_rejects=rej,
    )


# ---------------------------------------------------------------------------
# Appendix-F baselines: approximate value / policy iteration on the
# *untruncated* associated DTMDP with an expanding state window.
# ---------------------------------------------------------------------------


def _untruncated_arrays(spec: SMDPSpec, n_states: int):
    """c~, p_k, y for states 0..n_states-1 of the untruncated DTMDP."""
    big = dataclasses.replace(spec, s_max=max(n_states - 2, spec.b_max), c_o=0.0)
    mdp = build_smdp(big)
    return mdp


def avi(
    spec: SMDPSpec,
    n_outer: int = 400,
    n0: int = 8,
    growth: int = 1,
    eval_s_max: int = 160,
) -> RVIResult:
    """Thomas–Stengos Scheme I: VI with an expanding state window.

    Iteration i backs up states {0..n0 + growth*i}; values outside the
    current window are taken as the boundary value (h of the largest known
    state), which mirrors the scheme's 'latter states see fewer backups'.
    """
    t0 = time.perf_counter()
    n_final = n0 + growth * n_outer + spec.b_max + 2
    mdp = _untruncated_arrays(spec, n_final + 2)
    n_states = mdp.n_states  # n_final + 2 (incl. S_o)
    c = np.where(mdp.feasible, mdp.c_tilde, np.inf)[: n_final + 1]
    m = mdp.m_tilde[: n_final + 1, :, :]  # (n_final+1, A, n_states)
    h = np.zeros(n_states)
    g = 0.0
    for i in range(n_outer):
        n_i = min(n0 + growth * i, n_final)
        q = c[: n_i + 1] + np.einsum("saj,j->sa", m[: n_i + 1, :, :], h)
        j = np.min(q, axis=1)
        g = j[0]
        h[: n_i + 1] = j - g
    q = c + np.einsum("saj,j->sa", m, h)
    policy = np.argmin(q, axis=1)
    pol = policy[: eval_s_max + 2].copy()
    pol[-1] = pol[eval_s_max]  # overflow state mirrors s_max
    return RVIResult(
        policy=pol,
        g=float(g),
        h=h[: eval_s_max + 2],
        iterations=n_outer,
        span=float("nan"),
        converged=True,
        wall_time_s=time.perf_counter() - t0,
    )


def api(
    spec: SMDPSpec,
    n_outer: int = 12,
    inner_per_outer: int = 20,
    n0: int = 8,
    growth: int = 1,
    eval_s_max: int = 160,
) -> RVIResult:
    """Thomas–Stengos Scheme IV: policy iteration with AVI inner evaluation."""
    t0 = time.perf_counter()
    max_inner = sum(inner_per_outer * (i + 1) for i in range(n_outer))
    n_final = n0 + growth * max_inner + spec.b_max + 2
    mdp = _untruncated_arrays(spec, n_final + 2)
    n_states = mdp.n_states
    c = np.where(mdp.feasible, mdp.c_tilde, np.inf)[: n_final + 1]
    m = mdp.m_tilde[: n_final + 1, :, :]
    policy = np.zeros(n_final + 1, dtype=np.int64)  # initial: always wait
    h = np.zeros(n_states)
    g = 0.0
    step = 0
    for outer in range(n_outer):
        # inner: approximate evaluation of `policy` with expanding window
        for _ in range(inner_per_outer * (outer + 1)):
            n_i = min(n0 + growth * step, n_final)
            step += 1
            rows = np.arange(n_i + 1)
            cp = c[rows, policy[: n_i + 1]]
            mp = m[rows, policy[: n_i + 1], :]
            j = cp + mp @ h
            g = j[0]
            h[: n_i + 1] = j - g
        # improvement
        q = c + np.einsum("saj,j->sa", m, h)
        policy = np.argmin(q, axis=1)
    pol = policy[: eval_s_max + 2].copy()
    pol[-1] = pol[eval_s_max]
    return RVIResult(
        policy=pol,
        g=float(g),
        h=h[: eval_s_max + 2],
        iterations=step,
        span=float("nan"),
        converged=True,
        wall_time_s=time.perf_counter() - t0,
    )
