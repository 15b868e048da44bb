"""Compiled serving simulator: ONE jitted `lax.scan` decision-epoch kernel.

The Python engine (serving.engine._run_events) walks the queue one event at
a time — perfect for wall-clock executors and stateful online controllers,
hopeless for replication sweeps: a multi-seed bank comparison is minutes of
interpreter time while the solver finishes in milliseconds.  This module is
the compiled backend: the SAME decision-epoch semantics as `_run_events`,
expressed as a single `jax.lax.scan` step and `vmap`-ped across
(seeds x scenarios) x policy tables so an entire bank comparison is one
device dispatch.

Key representation choices:

  * Arrivals are a pre-sorted, +inf-padded array.  Requests are served FIFO
    and admitted in time order, so the queue at any moment is a contiguous
    window ``arrivals[n_served : n_admitted]`` — no ring buffer, just two
    carried indices.  Every arrival mode reduces to this form: traces
    directly, Poisson / MMPP2 via the scan-compatible samplers in
    serving.arrivals (the MMPP2 phase chain lives in that sampler's carry)
    or via eager numpy pre-generation when draw-for-draw parity with the
    Python engine is wanted (ServingEngine.run(backend="compiled")).
  * Policy tables always carry a phase axis inside the kernel: a (K, L)
    stack indexed by the phase of the *last admitted arrival* (a
    ``phases`` array aligned with the arrivals — from the MMPP2 sampler
    carry, an oracle switch trace, or all-zeros for the plain K = 1
    lane).  That is exactly the Python engine's oracle-phase discipline
    (observe_arrival on admission), so phase-indexed SMDP policies —
    OraclePhaseScheduler stacks and exact modulated (K, S) policies alike
    — run decision-for-decision inside the jitted scan.
  * One *event* per scan step — an O(1) admission pointer increment or a
    decision epoch — and a scalars-only carry; per-request accounting
    (latencies, the fixed-bin log-spaced histogram sketch, SLO misses) is
    reconstructed vectorized after the scan, so `run_grid` returns O(bins)
    aggregates per lane no matter the horizon and `record=True` yields the
    full decision/latency record for the equivalence harness.
  * Service times are ``means[a] * unit_draws[k]`` — every ServiceModel
    family is a unit-scale draw times the batch-size mean, so a shared draw
    sequence makes the compiled and Python backends decision-for-decision
    identical (the equivalence harness in serving.engine).
  * Scan length and array sizes are bucketed to powers of two and the
    actual epoch budget is a traced scalar, so re-runs at nearby sizes hit
    the jit cache; finished lanes freeze via a `done` flag, and a lane that
    runs out of steps is re-dispatched at a doubled length.

Termination mirrors the Python kernel exactly: a wait decision with no
live arrival left either drains the queue in b_max-capped batches
(drain=True) or terminates; an epoch budget caps the run regardless.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.service_models import ServiceModel  # noqa: F401  (x64 on import)

#: default fixed-bin latency sketch resolution (log-spaced bins)
DEFAULT_N_BINS = 256


def default_hist_edges(
    means: np.ndarray, n_bins: int = DEFAULT_N_BINS,
    lo_scale: float = 0.25, hi_scale: float = 2000.0,
) -> np.ndarray:
    """Log-spaced latency bin edges from the service-mean scale.

    Latencies are bounded below by (a fraction of) the single-request
    service time and above by queueing delay; ~4%-wide log bins over
    [means[1]/4, 2000 * means[b_max]] keep the sketch quantile error well
    inside the tolerance band tested against np.percentile.
    """
    lo = max(float(means[1]) * lo_scale, 1e-9)
    hi = max(float(means[-1]) * hi_scale, lo * 10.0)
    return np.geomspace(lo, hi, n_bins + 1)


def _bucket(n: int, floor: int = 256) -> int:
    """Smallest size >= n from {2^k, 3*2^k} (jit-cache friendly shapes).

    The half-step sizes bound the padding waste at 33% instead of 100% —
    scan steps are the whole cost of a frozen lane, so the finer ladder is
    worth the few extra jit cache entries.
    """
    b = floor
    while b < n:
        h = (b * 3) // 2
        if h >= n:
            return h
        b <<= 1
    return b


#: scan lengths that completed, keyed by problem shape — repeat dispatches
#: (benchmark loops, warmed sweeps) skip the escalation ladder entirely
_NSTEPS_CACHE: dict = {}


def _initial_steps(key, n_arr: int, max_eps: int, cap: int) -> int:
    # a completed run caches its exact-fit size (from the kernel's step
    # counter), so repeat dispatches carry no padding slack beyond the
    # bucket; a fresh shape starts from the typical-count heuristic
    # (admissions run _ADMIT_W-wide, epochs ~0.5 per arrival) and the
    # escalation loop covers the rare policies that need more
    cached = _NSTEPS_CACHE.get(key)
    if cached is not None:
        return min(cached, cap)
    return min(
        _bucket(
            n_arr // _ADMIT_W + max(256, min(max_eps, n_arr) // 2 + 2)
        ),
        cap,
    )


#: arrivals admitted per scan step (a dynamic_slice window): bursts cost
#: ceil(m / _ADMIT_W) steps instead of m.  Padded arrays must end in at
#: least this many +inf sentinels so the slice never clamps into real data.
_ADMIT_W = 4

#: record=True materializes several per-step trace arrays of the scan
#: length; past this many slots simulate_compiled raises instead of
#: allocating toward OOM (serving.fleet.FleetStream streams the same
#: aggregates in O(chunk) memory for arbitrarily long horizons)
MAX_RECORD_SLOTS = 1 << 20


def pad_arrivals(
    times, deadlines=None, size: Optional[int] = None, *, phases=None
):
    """Sort + pad an arrival-time array with +inf to a bucketed size.

    Returns (arrivals, deadlines) float64 arrays of length ``size`` (or the
    next power-of-two above len(times) plus the kernel's sentinel margin).
    Padded deadlines are +inf (never miss).  With ``phases`` (per-arrival
    phase ints for the phase-indexed table lane) a co-sorted, zero-padded
    int array is returned as a third element.
    """
    t = np.asarray(times, dtype=np.float64)
    finite = np.isfinite(t)  # idempotent: +inf padding is re-derived
    d = p = None
    if deadlines is not None:
        d = np.asarray(deadlines, dtype=np.float64)
        if len(d) != len(t):
            raise ValueError("deadlines must align with times")
        d = d[finite]
    if phases is not None:
        p = np.asarray(phases, dtype=np.int64)
        if len(p) != len(t):
            raise ValueError("phases must align with times")
        p = p[finite]
    t = t[finite]
    order = np.argsort(t, kind="stable")
    t = t[order]
    n = len(t)
    size = _bucket(n + _ADMIT_W) if size is None else size
    if size < n + _ADMIT_W:
        raise ValueError(
            f"pad size {size} < n_arrivals + {_ADMIT_W} = {n + _ADMIT_W}"
        )
    arr = np.full(size, np.inf)
    arr[:n] = t
    dl = np.full(size, np.inf)
    if d is not None:
        dl[:n] = d[order]
    if p is None:
        return arr, dl
    ph = np.zeros(size, dtype=np.int64)
    ph[:n] = p[order]
    return arr, dl, ph


def pad_arrivals_batch(traces, size: Optional[int] = None):
    """Pad several traces to one shared bucketed size: the (S, N) array
    `run_grid` wants for its seeds/scenarios axis.

    Derives the common size (largest trace plus the kernel's sentinel
    margin, bucketed) so callers never touch the sizing internals.
    """
    traces = [np.asarray(t, dtype=np.float64) for t in traces]
    if not traces:
        raise ValueError("pad_arrivals_batch needs at least one trace")
    if size is None:
        size = _bucket(max(len(t) for t in traces) + _ADMIT_W)
    return np.stack([pad_arrivals(t, size=size)[0] for t in traces])


@dataclasses.dataclass
class CompiledResult:
    """Aggregates of one compiled run (arrays already on host)."""

    t_final: float
    n_served: int
    n_batches: int
    n_epochs: int
    n_admitted: int
    energy: float
    lat_sum: float
    slo_miss: int
    terminated: bool  # stream exhausted (vs epoch budget reached)
    hist: np.ndarray  # (n_bins + 2,) counts; [0]=underflow, [-1]=overflow
    hist_edges: np.ndarray  # (n_bins + 1,)
    # record=True only:
    actions: Optional[np.ndarray] = None  # (n_epochs,) batch size, 0 = wait
    serve: Optional[np.ndarray] = None  # (n_epochs,) bool
    latencies: Optional[np.ndarray] = None  # (n_served,) in service order
    # adaptive lane only: final controller carry (engine state sync)
    adaptive_state: Optional[dict] = None
    # managed-queue lane (buffer= / shed_expired=) only:
    n_shed: int = 0  # arrivals refused by the finite waiting room
    n_expired: int = 0  # queued requests shed past their deadline
    queue_slots: Optional[np.ndarray] = None  # surviving queue, slot idxs

    @property
    def batch_sizes(self) -> np.ndarray:
        if self.actions is None:
            raise ValueError("run with record=True for per-epoch decisions")
        return self.actions[self.serve]


@dataclasses.dataclass
class AdaptiveLane:
    """Host-side lowering of an `AdaptiveController` for the scan kernel.

    Everything the in-carry controller needs, precomputed once: the bank
    stacked in sorted-key order, the per-key lambda coordinate plus the
    *pinned*-dimension squared scaled offsets (so the kernel's distance is
    ``sqrt(((lam_i - est) / lam_scale)^2 + aux_sq_i)`` — the same scaled
    Euclidean metric as `SMDPSchedulerBank.distances` over the
    {lam, **fixed} coordinate set), the EWMA constants, and the initial
    carry state extracted from the live controller (so a mid-stream engine
    run resumes exactly).  Window-mode estimators have no O(1) carry and
    stay on the Python backend.
    """

    tables: np.ndarray  # (P, K, L) bank stack, sorted-key order
    lam_keys: np.ndarray  # (P,) lambda coordinate per key
    aux_sq: np.ndarray  # (P,) pinned-dims squared scaled distance
    inv_scale: float  # 1 / lambda-dimension scale
    ewma: float
    margin: float
    min_dwell: float
    min_gap: float
    init_est: float  # estimator rate before any gap (NaN if none)
    sel0: int  # initial bank entry (index into sorted keys)
    gap_bar0: float  # NaN when the estimator has no gap average yet
    have_gap_bar0: bool
    last0: float  # NaN when no arrival observed yet
    have_last0: bool
    last_switch0: float
    n_switches0: int

    @classmethod
    def from_controller(cls, ctrl) -> "AdaptiveLane":
        est = ctrl.estimator
        if getattr(est, "window", None) is not None:
            raise TypeError(
                "compiled adaptive lane needs an EWMA RateEstimator; "
                "window-mode estimators stay on the Python backend"
            )
        bank = ctrl.bank
        unknown = set(ctrl.fixed) - set(bank.key_names)
        if unknown:
            raise ValueError(
                f"unknown key dims {unknown}; have {bank.key_names}"
            )
        _, stacked = bank.stacked()
        if stacked.ndim == 2:
            stacked = stacked[:, None, :]
        i_lam = bank.key_names.index("lam")
        pts, scales = bank._pts, bank._scales
        aux = np.zeros(len(pts))
        for i, name in enumerate(bank.key_names):
            if i != i_lam and name in ctrl.fixed:
                aux += ((pts[:, i] - ctrl.fixed[name]) / scales[i]) ** 2
        gap_bar = est._gap_bar
        last = est._last
        return cls(
            tables=stacked,
            lam_keys=pts[:, i_lam].copy(),
            aux_sq=aux,
            inv_scale=1.0 / float(scales[i_lam]),
            ewma=float(est.ewma),
            margin=float(ctrl.margin),
            min_dwell=float(ctrl.min_dwell),
            min_gap=float(est.min_gap),
            init_est=(
                float(est._init_rate) if est._init_rate else float("nan")
            ),
            sel0=int(bank._key_index[ctrl.key]),
            gap_bar0=float("nan") if gap_bar is None else float(gap_bar),
            have_gap_bar0=gap_bar is not None,
            last0=float("nan") if last is None else float(last),
            have_last0=last is not None,
            last_switch0=float(ctrl._last_switch),
            n_switches0=int(ctrl.n_switches),
        )

    def lowered(self):
        """The ``adap`` pytree `_scan_core` consumes (constants + carry0)."""
        i64 = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
        state0 = (
            jnp.asarray(self.gap_bar0, dtype=jnp.float64),
            jnp.asarray(self.have_gap_bar0),
            jnp.asarray(self.last0, dtype=jnp.float64),
            jnp.asarray(self.have_last0),
            jnp.asarray(self.sel0, dtype=i64),
            jnp.asarray(self.last_switch0, dtype=jnp.float64),
            jnp.asarray(self.n_switches0, dtype=i64),
        )
        return (
            jnp.asarray(self.lam_keys, dtype=jnp.float64),
            jnp.asarray(self.aux_sq, dtype=jnp.float64),
            jnp.asarray(self.inv_scale, dtype=jnp.float64),
            jnp.asarray(self.ewma, dtype=jnp.float64),
            jnp.asarray(self.margin, dtype=jnp.float64),
            jnp.asarray(self.min_dwell, dtype=jnp.float64),
            jnp.asarray(self.min_gap, dtype=jnp.float64),
            jnp.asarray(self.init_est, dtype=jnp.float64),
            state0,
        )


def _admissible(arrivals, horizon):
    """The arrivals the kernel admits from: +inf from the horizon on."""
    return jnp.where(arrivals < horizon, arrivals, jnp.inf)


def _scan_kernel(
    table, arrivals, deadlines, phases, beliefs, draws, means, zeta, edges,
    t0, horizon, max_eps, drain, b_max, adap=None, buffer_cap=None,
    shed=None,
    *, record: bool, mix: bool = False, adaptive: bool = False,
    qman: bool = False, arr_adm=None,
):
    """The event kernel: one scan step == one admission OR one epoch.

    Returns its three pieces ``(carry0, step, finish)``: the carry before
    the first step, ``step(carry, _) -> (carry, out)`` (a `lax.scan`
    body), and ``finish(carry, outs)``, the per-request reconstruction and
    aggregates from the final carry and the stacked per-step outputs.
    `_scan_core` scans a fixed number of steps; `_lockstep_grid` stops
    once no instance of its grid is active.

    Pure jax function; shapes only (no jit here — callers jit/vmap it).
    `arrivals` must be sorted with at least one trailing +inf sentinel;
    ``arr_adm``, when given, is `_admissible` of them, computed once by a
    caller that builds the kernel inside a loop.
    ``table`` is a (K, L) phase-indexed stack (K = 1 for plain policies)
    and ``phases`` the per-arrival phase ints aligned with ``arrivals``;
    the active row is the phase of the last admitted arrival — the Python
    engine's oracle-phase discipline (phase updates on admission).

    Two static knobs widen the lane to *online* (non-oracle) policies:

      * ``mix=True`` — belief-mixture action rule: instead of one phase
        row, the decision is ``round(sum_k beliefs[last_adm, k] *
        table[k, min(q, L-1)])`` with ``beliefs`` the (size, K) posterior
        rows aligned with ``arrivals`` (arrivals.belief_forward_jax) —
        the compiled `BeliefPhaseScheduler(mode="mix")`.  (The argmax
        rule needs no kernel support: it is just ``phases =
        argmax(beliefs)`` through the oracle plumbing.)
      * ``adaptive=True`` — ``table`` grows a leading bank axis
        (P, K, L) and the carry gains the AdaptiveController state (EWMA
        gap estimate, selected entry, hysteresis clock).  Each admission
        folds its arrival into the estimate and may retune ``sel`` —
        guarded by the relative margin and min-dwell exactly as
        `scheduler.AdaptiveController._maybe_retune` — so the bank
        retunes live inside the scan.  ``adap`` packs the lowered
        constants + initial state (`AdaptiveLane.carry()`).
      * ``qman=True`` — managed-queue lane for admission shedding: the
        carry gains an explicit admitted-slot queue (an index array plus
        head/tail pointers) because refusals and expiry breaks the plain
        ``arrivals[n_served:n_admitted]`` window contiguity.  Arrivals
        beyond ``buffer_cap`` queued requests are refused at the door
        (never observed by the adaptive estimator — the Python engine's
        offered-vs-admitted discipline), and with ``shed`` set every
        decision is preceded by dropping the expired *prefix* of the
        queue (deadlines must be nondecreasing in arrival order, which
        ``deadline = arrival + slo`` guarantees; the wrapper checks).  A
        step sheds at most ``_ADMIT_W`` expired requests; if more remain
        the step is a pure shed step — no decision epoch, clock
        unchanged — and the next step continues, so the eventual decide
        sees the fully swept queue exactly as the Python loop does.

    Two throughput-critical choices:

      * One *event* per step, not one epoch: when the next arrival is due
        (<= the clock) the step admits it — a single O(1) gather — and only
        otherwise takes a decision epoch.  Batch-admission inside an epoch
        would need a binary search over the arrival array every step; the
        event formulation replaces it with pointer increments, the same
        trick that makes the Python loop O(1) per event.
      * The scan carry is scalars-only (clock, window indices, energy): all
        per-request accounting — latencies, the histogram sketch, SLO
        misses — is reconstructed *after* the scan in one vectorized pass,
        by mapping each request slot to the serve epoch that completed it
        (a searchsorted into the cumulative batch sizes).

    A lane that exhausts its steps before terminating or filling its epoch
    budget reports ``incomplete``; callers re-dispatch at a doubled step
    count (the scan is deterministic, so the prefix replays identically).
    An inactive step (``done``, or the epoch budget spent) changes no
    carry and serves nothing: ``a = 0``, and the resolved count stays.
    """
    L = table.shape[-1]
    size = arrivals.shape[0]
    n_bins = edges.shape[0] - 1
    if arr_adm is None:
        arr_adm = _admissible(arrivals, horizon)
    n_draws = draws.shape[0]
    i64 = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32

    if adaptive:
        (lam_keys, aux_sq, inv_scale, ad_ewma, ad_margin, ad_min_dwell,
         ad_min_gap, ad_init_est, ad_state0) = adap

    def step(carry, _):
        (t, n_srv, n_adm, n_bat, n_eps, n_used, done, energy), ad, qm = carry
        active = jnp.logical_not(done) & (n_eps < max_eps)
        # arrivals due by `now` are admitted before any decision is taken,
        # up to _ADMIT_W per step (they are a prefix of the sorted window;
        # the sentinel margin keeps the slice from clamping into real data)
        window = jax.lax.dynamic_slice(arr_adm, (n_adm,), (_ADMIT_W,))
        nxt = window[0]  # +inf once exhausted / beyond the horizon
        n_due = jnp.sum(window <= t).astype(i64)
        admit = active & (n_due > 0)
        dec = active & ~admit
        if qman:
            adm_idx, head, tail, last_adm, n_shd, n_exp = qm
            # door admission, one arrival at a time in time order: a
            # refusal checks the *running* queue length, exactly the
            # Python loop's per-arrival `len(queue) >= buffer`
            takes = []
            for j in range(_ADMIT_W):
                m = admit & (j < n_due)
                refuse = m & (tail - head >= buffer_cap)
                take = m & ~refuse
                adm_idx = adm_idx.at[jnp.where(take, tail, size)].set(
                    (n_adm + j).astype(jnp.int32), mode="drop"
                )
                tail = tail + take.astype(i64)
                n_shd = n_shd + refuse.astype(i64)
                last_adm = jnp.where(take, n_adm + j, last_adm)
                takes.append(take)
            q = tail - head
        else:
            q = n_adm - n_srv
        if adaptive:
            # fold each admitted arrival of this step into the controller
            # state, in time order — an unrolled masked pass over the
            # admission window, one EWMA update + hysteresis-guarded
            # retune per arrival, mirroring observe_arrival exactly
            gap_bar, have_gb, last_obs, have_last, sel, last_sw, n_sw = ad
            for j in range(_ADMIT_W):
                t_j = window[j]
                # refused arrivals are never observed (observe_arrival
                # runs on admission only in the Python engine)
                m = takes[j] if qman else admit & (j < n_due)
                gap = jnp.maximum(t_j - last_obs, ad_min_gap)
                upd = m & have_last
                gb_new = jnp.where(
                    have_gb, (1.0 - ad_ewma) * gap_bar + ad_ewma * gap, gap
                )
                gap_bar = jnp.where(upd, gb_new, gap_bar)
                have_gb = have_gb | upd
                last_obs = jnp.where(m, t_j, last_obs)
                have_last = have_last | m
                est = jnp.where(
                    have_gb,
                    1.0 / jnp.maximum(gap_bar, ad_min_gap),
                    ad_init_est,
                )
                dist = jnp.sqrt(((lam_keys - est) * inv_scale) ** 2 + aux_sq)
                cand = jnp.argmin(dist).astype(i64)
                switch = (
                    m
                    & (t_j - last_sw >= ad_min_dwell)
                    & jnp.isfinite(est)
                    & (cand != sel)
                    & (dist[cand] < (1.0 - ad_margin) * dist[sel])
                )
                n_sw = n_sw + switch.astype(i64)
                last_sw = jnp.where(switch, t_j, last_sw)
                sel = jnp.where(switch, cand, sel)
            ad = (gap_bar, have_gb, last_obs, have_last, sel, last_sw, n_sw)
            tab_kl = table[sel]  # the live bank entry, (K, L)
        else:
            tab_kl = table
        if qman:
            # expired-prefix sweep before the decision (deadlines are
            # nondecreasing in admission order, so expired requests are a
            # queue prefix); any shedding makes this a pure shed step —
            # the decision waits for the next step, clock unchanged
            e = jnp.asarray(0, dtype=i64)
            chain = dec & shed
            for j in range(_ADMIT_W):
                idx = adm_idx[jnp.clip(head + j, 0, size - 1)]
                chain = chain & (j < q) & (deadlines[idx] <= t)
                e = e + chain.astype(i64)
            dec_eff = dec & (e == 0)
        else:
            dec_eff = dec
        # phase of the last admitted arrival (before any admission this
        # reads the first arrival's phase; the queue is empty there, so
        # the decision is a forced wait whatever the row)
        if qman:
            last_i = jnp.clip(last_adm, 0, size - 1)
        else:
            last_i = jnp.clip(n_adm - 1, 0, size - 1)
        if mix:
            # belief-mixture action: posterior-weighted blend of the
            # per-phase actions, rounded — BeliefPhaseScheduler(mode="mix")
            a = jnp.round(
                jnp.sum(beliefs[last_i] * tab_kl[:, jnp.minimum(q, L - 1)])
            ).astype(i64)
        else:
            a = tab_kl[phases[last_i], jnp.minimum(q, L - 1)]
        a = jnp.clip(a, 0, jnp.minimum(q, b_max))
        live = jnp.isfinite(nxt)
        wait = dec_eff & (a == 0) & live
        term = dec_eff & (a == 0) & ~live & ((q == 0) | ~drain)
        a = jnp.where(
            dec_eff & (a == 0) & ~live & ~term, jnp.minimum(q, b_max), a
        )
        serve = dec_eff & ~wait & ~term
        a = a * serve
        svc = means[a] * draws[jnp.minimum(n_bat, n_draws - 1)]
        t_done = t + svc
        t_next = jnp.where(wait, nxt, jnp.where(serve, t_done, t))
        if qman:
            qm = (adm_idx, head + e + a, tail, last_adm, n_shd, n_exp + e)
            consumed = head + e + a  # queue items resolved so far
        else:
            consumed = n_srv + a  # request slots served so far
        carry = ((
            t_next,
            n_srv + a,
            n_adm + jnp.where(admit, n_due, 0),
            n_bat + serve.astype(i64),
            n_eps + dec_eff.astype(i64),
            n_used + active.astype(i64),
            done | term,
            # energy accrues in the carry, one serve at a time, so every
            # kernel (and the Python references) sums it in event order
            energy + zeta[a],  # zeta[0] forced to 0 by the wrappers
        ), ad, qm)
        # (a > 0) <=> serve, so the aggregate path only needs (a, t_done)
        # and the running resolved count; the decision flag is recorded
        # only for the equivalence harness
        a32 = a.astype(jnp.int32)
        c32 = consumed.astype(jnp.int32)
        return carry, (
            (a32, c32, dec_eff, t_done) if record else (a32, c32, t_done)
        )

    zero = jnp.asarray(0, dtype=i64)
    qm0 = (
        (
            jnp.zeros(size, dtype=jnp.int32),  # admitted-slot queue
            zero,  # head: served + expired
            zero,  # tail: admitted
            jnp.asarray(-1, dtype=i64),  # last admitted arrival slot
            zero,  # door refusals
            zero,  # expired sheds
        )
        if qman
        else None
    )
    carry0 = ((
        jnp.asarray(t0, dtype=jnp.float64),
        zero, zero, zero, zero, zero,
        jnp.asarray(False),
        jnp.asarray(0.0, dtype=jnp.float64),
    ), ad_state0 if adaptive else None, qm0)
    return carry0, step, partial(
        _finish, arrivals, deadlines, edges, max_eps,
        record=record, adaptive=adaptive, qman=qman,
    )


def _finish(arrivals, deadlines, edges, max_eps, carry, outs, *,
            record: bool, adaptive: bool, qman: bool):
    """`_scan_kernel`'s aggregates from its final carry and the (n_steps,)
    stacked step outputs."""
    size = arrivals.shape[0]
    n_bins = edges.shape[0] - 1
    i64 = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    a_seq, cum_seq, tdone_seq = (
        (outs[0], outs[1], outs[3]) if record else outs
    )
    n_steps = cum_seq.shape[0]
    scalars, ad_final, qm_final = carry
    t, n_srv, n_adm, n_bat, n_eps, n_used, done, energy = scalars

    # --- vectorized per-request reconstruction (one pass, no scan) -------
    # request slot j was completed by the step whose resolved interval
    # [cum - a, cum) contains j: the first step whose running resolved
    # count exceeds j.  The counts are nondecreasing over steps, so that is
    # one binary search per slot (a prefix sum or running max over the
    # slots would do the same in O(size), but compiles for minutes on TPU)
    slots = jnp.arange(size)
    step_of = jnp.minimum(
        jnp.searchsorted(cum_seq, slots.astype(jnp.int32), side="right"),
        n_steps - 1,
    )
    completion = tdone_seq[step_of]
    if qman:
        # managed-queue lane: the slot space is *admission order* (the
        # adm_idx queue), and steps consume a (served) + e (expired)
        # items from its head — a step does one or the other, so a done
        # slot's covering step tells served from expired apart
        adm_idx, head, tail, last_adm, n_shd, n_exp = qm_final
        arr_o = arrivals[adm_idx]
        dl_o = deadlines[adm_idx]
        valid = (slots < head) & (a_seq[step_of] > 0)  # done AND served
        lat = jnp.where(valid, completion - arr_o, 0.0)
        lat_sum = jnp.sum(lat)
        miss = jnp.sum(valid & (completion > dl_o))
    else:
        valid = slots < n_srv
        lat = jnp.where(valid, completion - arrivals, 0.0)
        lat_sum = jnp.sum(lat)
        miss = jnp.sum(valid & (completion > deadlines))
    bins = jnp.clip(jnp.searchsorted(edges, lat, side="right"), 0, n_bins + 1)
    hist = jnp.zeros(n_bins + 2, dtype=i64).at[
        jnp.where(valid, bins, 0)
    ].add(valid.astype(i64))

    agg = {
        "t_final": t, "n_served": n_srv, "n_admitted": n_adm,
        "n_batches": n_bat, "n_epochs": n_eps, "n_steps_used": n_used,
        "terminated": done,
        "incomplete": jnp.logical_not(done) & (n_eps < max_eps),
        "energy": energy, "lat_sum": lat_sum, "slo_miss": miss, "hist": hist,
    }
    if qman:
        # shed counters + final queue pointers (engine state sync: the
        # surviving queue is adm_idx[head:tail], in admission order)
        agg.update(
            n_shed=n_shd, n_expired=n_exp,
            qm_idx=adm_idx, qm_head=head, qm_tail=tail,
        )
    if adaptive:
        # final controller state (for the engine's post-run state sync)
        gap_bar, have_gb, last_obs, have_last, sel, last_sw, n_sw = ad_final
        agg.update(
            ad_gap_bar=gap_bar, ad_have_gap_bar=have_gb, ad_last=last_obs,
            ad_have_last=have_last, ad_sel=sel, ad_last_switch=last_sw,
            ad_n_switches=n_sw,
        )
    dec_seq = outs[2] if record else None
    return (agg, (a_seq, dec_seq, lat, valid)) if record else agg


def _scan_core(
    table, arrivals, deadlines, phases, beliefs, draws, means, zeta, edges,
    t0, horizon, max_eps, drain, b_max, adap=None, buffer_cap=None,
    shed=None,
    *, n_steps: int, record: bool, mix: bool = False, adaptive: bool = False,
    qman: bool = False,
):
    """`_scan_kernel` run for a fixed ``n_steps`` steps: its aggregates
    (and with ``record`` the per-step record).  Pure jax function."""
    carry0, step, finish = _scan_kernel(
        table, arrivals, deadlines, phases, beliefs, draws, means, zeta,
        edges, t0, horizon, max_eps, drain, b_max, adap, buffer_cap, shed,
        record=record, mix=mix, adaptive=adaptive, qman=qman,
    )
    carry, outs = jax.lax.scan(step, carry0, None, length=n_steps, unroll=4)
    return finish(carry, outs)


#: the phase_mode knob shared by simulate_compiled / run_grid / fleet:
#: "oracle" rows tables by the per-arrival true-phase ints, the belief
#: modes by the filtered posterior (argmax row / mixture action)
PHASE_MODES = ("oracle", "belief_argmax", "belief_mix")


def _check_phase_mode(phase_mode: str, beliefs, n_phases: int):
    """Validate the phase_mode / beliefs pairing; returns belief ndarray."""
    if phase_mode not in PHASE_MODES:
        raise ValueError(f"phase_mode must be one of {PHASE_MODES}")
    if phase_mode == "oracle":
        if beliefs is not None:
            raise ValueError('beliefs= needs phase_mode="belief_*"')
        return None
    if beliefs is None:
        raise ValueError(f'phase_mode="{phase_mode}" needs beliefs=')
    bel = np.asarray(beliefs, dtype=np.float64)
    if bel.shape[-1] != n_phases:
        raise ValueError(
            f"beliefs K={bel.shape[-1]} != table phase axis K={n_phases}"
        )
    return bel


def _coerce_adaptive(adaptive) -> Optional[AdaptiveLane]:
    if adaptive is None or isinstance(adaptive, AdaptiveLane):
        return adaptive
    return AdaptiveLane.from_controller(adaptive)


@partial(
    jax.jit,
    static_argnames=("n_steps", "record", "mix", "adaptive", "qman"),
)
def _simulate_jit(table, arrivals, deadlines, phases, beliefs, draws, means,
                  zeta, edges, t0, horizon, max_eps, drain, b_max, adap,
                  buffer_cap, shed, n_steps, record, mix, adaptive, qman):
    return _scan_core(
        table, arrivals, deadlines, phases, beliefs, draws, means, zeta,
        edges, t0, horizon, max_eps, drain, b_max, adap, buffer_cap, shed,
        n_steps=n_steps, record=record, mix=mix, adaptive=adaptive,
        qman=qman,
    )


def simulate_compiled(
    table,
    arrivals,
    *,
    means,
    zeta=None,
    draws=None,
    b_max: int,
    max_epochs: Optional[int] = None,
    t0: float = 0.0,
    horizon: Optional[float] = None,
    drain: bool = True,
    deadlines=None,
    phases=None,
    phase_mode: str = "oracle",
    beliefs=None,
    adaptive=None,
    buffer: Optional[int] = None,
    shed_expired: bool = False,
    hist_edges=None,
    record: bool = False,
    max_record_slots: Optional[int] = None,
) -> CompiledResult:
    """Run one policy table over one padded arrival trace, compiled.

    ``arrivals``/``deadlines`` may be raw times (padded internally) or
    already-padded arrays from `pad_arrivals`.  ``draws`` are unit-scale
    service draws (ones for deterministic service); service time of a batch
    of size a is ``means[a] * draws[n_batches_so_far]`` — exactly one draw
    consumed per serve epoch, matching the Python engine's rng discipline.

    ``table`` may be a (K, L) phase-indexed stack; who selects the row is
    the ``phase_mode`` knob:

      * ``"oracle"`` (default) — ``phases`` per-arrival true-phase ints
        (raw or pre-padded alongside ``arrivals``); the row is the phase
        of the last admitted arrival.
      * ``"belief_argmax"`` — ``beliefs`` (N, K) posterior rows aligned
        with ``arrivals`` (arrivals.belief_forward_jax); the argmax phase
        rows the stack: the compiled `BeliefPhaseScheduler`.
      * ``"belief_mix"`` — same ``beliefs``, but the action is the
        posterior-weighted mixture ``round(sum_k b_k table[k, q])``
        (`BeliefPhaseScheduler(mode="mix")`).

    ``adaptive`` (an `AdaptiveLane` or the `AdaptiveController` to lower)
    runs the bank-retuning controller *inside* the scan carry: ``table``
    may then be None (the lane's (P, K, L) bank stack is used) and the
    result carries ``adaptive_state`` — the final controller carry — for
    exact engine state sync.  Composes with any phase_mode (the phase axis
    rows each bank entry).

    ``buffer=B`` bounds the waiting room: arrivals finding B requests
    queued are refused at the door (counted in ``n_shed``, never observed
    by the adaptive estimator).  ``shed_expired=True`` drops queued
    requests whose deadline has passed before every decision epoch
    (``n_expired``); it requires deadlines nondecreasing in arrival order
    (``deadline = arrival + slo`` always is).  Either knob switches the
    kernel to the managed-queue lane (an explicit admitted-slot index
    queue in the carry) and the result gains ``queue_slots`` — the
    surviving queue as arrival-slot indices.  Belief lanes compose with
    ``shed_expired`` but not with ``buffer`` (the posterior folds admitted
    arrivals only, which a finite room makes decision-dependent).

    ``record=True`` materializes per-step trace buffers (actions,
    latencies) sized to the scan length.  That escalation is capped at
    ``max_record_slots`` (default `MAX_RECORD_SLOTS`): beyond it the call
    raises instead of silently allocating toward OOM — for longer
    horizons stream aggregates in O(chunk) memory with
    `serving.fleet.FleetStream` / `simulate_fleet_stream` instead.
    """
    lane = _coerce_adaptive(adaptive)
    if buffer is not None:
        if buffer < 0:
            raise ValueError(
                "buffer must be >= 0 (B = 0 sheds everything)"
            )
        if phase_mode != "oracle":
            raise ValueError(
                'buffer= composes with phase_mode="oracle" only: belief '
                "posteriors fold admitted arrivals, and admission under a "
                "finite waiting room is decision-dependent; run the "
                "Python backend"
            )
    qman = buffer is not None or bool(shed_expired)
    if lane is not None:
        table = lane.tables if table is None else np.asarray(
            table, dtype=np.int64
        )
        if table.ndim == 2:
            table = table[:, None, :]
        elif table.ndim != 3:
            raise ValueError(
                f"adaptive tables must be (P, L) or (P, K, L); "
                f"got {table.shape}"
            )
    else:
        table = np.asarray(table, dtype=np.int64)
        if table.ndim == 1:
            table = table[None]
        elif table.ndim != 2:
            raise ValueError(
                f"table must be (L,) or (K, L); got {table.shape}"
            )
    n_phases = table.shape[-2]
    bel = _check_phase_mode(phase_mode, beliefs, n_phases)
    if bel is not None:
        if phases is not None:
            raise ValueError("phases= and beliefs= are mutually exclusive")
        if bel.ndim != 2:
            raise ValueError(f"beliefs must be (N, K); got {bel.shape}")
    elif n_phases > 1 and phases is None and lane is None:
        raise ValueError("phase-indexed table needs phases= per arrival")
    arr = np.asarray(arrivals, dtype=np.float64)
    if bel is not None and len(bel) != len(arr):
        raise ValueError("beliefs must align with arrivals")
    if phase_mode == "belief_argmax":
        # the argmax rule is just an oracle-phase stream derived from the
        # posterior: reuse the whole phases plumbing, no kernel change
        phases = np.argmax(bel, axis=-1)
        bel = None
    mix = phase_mode == "belief_mix"
    if len(arr) < _ADMIT_W or not np.isinf(arr[-_ADMIT_W:]).all():
        raw = arr
        padded = pad_arrivals(raw, deadlines, phases=phases)
        if phases is None:
            arr, dl = padded
            ph = np.zeros(len(arr), dtype=np.int64)
        else:
            arr, dl, ph = padded
        if bel is not None:
            # co-sort/pad the posterior rows exactly like pad_arrivals
            finite = np.isfinite(raw)
            kept = bel[finite]
            order = np.argsort(raw[finite], kind="stable")
            bel = np.zeros((len(arr), bel.shape[1]))
            bel[: len(kept)] = kept[order]
    else:
        dl = (
            np.asarray(deadlines, dtype=np.float64)
            if deadlines is not None
            else np.full(len(arr), np.inf)
        )
        ph = (
            np.asarray(phases, dtype=np.int64)
            if phases is not None
            else np.zeros(len(arr), dtype=np.int64)
        )
        if len(ph) != len(arr):
            raise ValueError("padded phases must align with arrivals")
    if phases is not None and (ph.min() < 0 or ph.max() >= n_phases):
        raise ValueError(
            f"phases outside the table stack [0, {n_phases})"
        )
    n_arr = int(np.sum(np.isfinite(arr)))
    if shed_expired:
        # expired requests must form a queue *prefix* (the kernel sheds
        # from the head): deadlines nondecreasing in arrival order, which
        # deadline = arrival + slo satisfies by construction.  inf - inf
        # is NaN and NaN < 0 is False, so all-inf (no-deadline) runs pass.
        with np.errstate(invalid="ignore"):
            if np.any(np.diff(dl[:n_arr]) < 0):
                raise ValueError(
                    "shed_expired needs deadlines nondecreasing in arrival "
                    "order (deadline = arrival + slo always is); arbitrary "
                    "deadline orders run on the Python backend"
                )
    if max_epochs is None:
        max_eps = 2 * n_arr + 2
    else:
        max_eps = int(max_epochs)
    means = np.asarray(means, dtype=np.float64)
    zeta_a = (
        np.zeros(b_max + 1)
        if zeta is None
        else np.asarray(zeta, dtype=np.float64).copy()
    )
    zeta_a[0] = 0.0  # a = 0 never accounts energy (the kernel adds zeta[a])
    if draws is None:
        draws = np.ones(1)
    draws = np.asarray(draws, dtype=np.float64)
    edges = (
        default_hist_edges(means)
        if hist_edges is None
        else np.asarray(hist_edges, dtype=np.float64)
    )
    # one scan step per event: admissions + epochs.  Start from the typical
    # count and re-dispatch doubled if the lane ran out of steps (the cap
    # n_arr + max_eps + 1 is a hard upper bound: every step admits one of
    # n_arr arrivals or consumes one of max_eps epochs; the managed-queue
    # lane adds shed steps, each dropping >= 1 of at most n_arr requests).
    cap = _bucket((2 if qman else 1) * n_arr + max_eps + 1)
    ck = (
        "single", len(arr), table.shape, cap, mix, lane is not None,
        None if buffer is None else int(buffer), bool(shed_expired),
    )
    n_steps = _initial_steps(ck, n_arr, max_eps, cap)
    bel_j = (
        jnp.zeros((1, 1)) if bel is None else jnp.asarray(bel)
    )  # unused unless mix
    adap_j = None if lane is None else lane.lowered()
    if record:
        slots = (
            MAX_RECORD_SLOTS if max_record_slots is None
            else int(max_record_slots)
        )
        if n_steps > slots:
            raise ValueError(
                f"record=True needs at least {n_steps} trace slots for "
                f"{n_arr} arrivals, above max_record_slots={slots}; raise "
                "max_record_slots explicitly, or stream aggregates in "
                "O(chunk) memory with serving.fleet.FleetStream / "
                "simulate_fleet_stream"
            )
    # no buffer -> a cap the queue can never reach (the door never refuses)
    buf_cap = len(arr) + 1 if buffer is None else int(buffer)
    while True:
        out = _simulate_jit(
            jnp.asarray(table), jnp.asarray(arr), jnp.asarray(dl),
            jnp.asarray(ph), bel_j, jnp.asarray(draws), jnp.asarray(means),
            jnp.asarray(zeta_a), jnp.asarray(edges),
            float(t0), np.inf if horizon is None else float(horizon),
            max_eps, bool(drain), int(b_max), adap_j, buf_cap,
            bool(shed_expired), int(n_steps), bool(record), mix,
            lane is not None, qman,
        )
        agg = out[0] if record else out
        if n_steps >= cap or not bool(agg["incomplete"]):
            break
        nxt = min(2 * n_steps, cap)
        if record and nxt > slots:
            raise ValueError(
                f"record=True escalation wants {nxt} trace slots, above "
                f"max_record_slots={slots}; raise max_record_slots "
                "explicitly, or stream aggregates in O(chunk) memory with "
                "serving.fleet.FleetStream / simulate_fleet_stream"
            )
        n_steps = nxt
    _NSTEPS_CACHE[ck] = min(_bucket(int(agg["n_steps_used"]) + 1), cap)
    rec = out[1] if record else None
    agg = {k: np.asarray(v) for k, v in agg.items()}
    res = CompiledResult(
        t_final=float(agg["t_final"]),
        n_served=int(agg["n_served"]),
        n_batches=int(agg["n_batches"]),
        n_epochs=int(agg["n_epochs"]),
        n_admitted=int(agg["n_admitted"]),
        energy=float(agg["energy"]),
        lat_sum=float(agg["lat_sum"]),
        slo_miss=int(agg["slo_miss"]),
        terminated=bool(agg["terminated"]),
        hist=agg["hist"],
        hist_edges=edges,
    )
    if qman:
        res.n_shed = int(agg["n_shed"])
        res.n_expired = int(agg["n_expired"])
        res.queue_slots = np.asarray(agg["qm_idx"])[
            int(agg["qm_head"]): int(agg["qm_tail"])
        ].astype(np.int64)
    if lane is not None:
        res.adaptive_state = {
            "sel": int(agg["ad_sel"]),
            "gap_bar": float(agg["ad_gap_bar"]),
            "have_gap_bar": bool(agg["ad_have_gap_bar"]),
            "last": float(agg["ad_last"]),
            "have_last": bool(agg["ad_have_last"]),
            "last_switch": float(agg["ad_last_switch"]),
            "n_switches": int(agg["ad_n_switches"]),
        }
    if record:
        acts, dec, lat, valid = (np.asarray(x) for x in rec)
        res.actions = acts[dec].astype(np.int64)  # one entry per epoch
        res.serve = res.actions > 0
        res.latencies = lat[valid]  # arrival order == FIFO service order
    return res


#: steps per iteration of the grid's event loop (at most; it divides the
#: step count): the loop tests for active instances once per chunk
_GRID_CHUNK = 256

#: what the rows of the (a, consumed, t_done) step outputs that the grid's
#: loop never reaches hold: no serve, and a resolved count past every slot,
#: so each instance's counts stay nondecreasing for `_finish`'s search
_UNREACHED = (0, np.iinfo(np.int32).max, np.inf)


def _lockstep_grid(kernel_of, lanes, tables, *, n_steps: int, max_eps):
    """Every instance of a lanes [x tables] grid, run in lockstep until
    none is active.

    ``kernel_of(lane, table)`` builds one instance's `_scan_kernel` from
    its lane's arrays (``lanes``, a tuple of arrays with a leading lane
    axis) and its entry of ``tables`` (None: the grid has no table axis).
    The loop runs outside the vmap, a chunk of steps at a time, and stops
    at the first chunk boundary where no instance is active (an instance
    never becomes active again) or at ``n_steps``.  The rows it never
    reaches hold `_UNREACHED`: valid slots resolve to executed steps and
    the rest are masked, so the aggregates are bitwise those of a fixed
    ``n_steps``-step scan per instance (`_scan_core`).  Returns them and,
    as a (1,) array, the steps the loop ran.
    """
    def over_grid(f, in_axes=(), out_axes=0):
        """``f(kernel, *xs)`` on each instance, vmapped over the grid;
        each of ``xs`` carries the grid's axes at its entry of
        ``in_axes``, the results at ``out_axes``."""
        def per_lane(lane, *xs):
            if tables is None:
                return f(kernel_of(lane, None), *xs)
            return jax.vmap(
                lambda tab, *xs: f(kernel_of(lane, tab), *xs),
                (0, *in_axes), out_axes,
            )(tables, *xs)
        return lambda *xs: jax.vmap(per_lane, (0, *in_axes), out_axes)(
            lanes, *xs
        )

    chunk = math.gcd(n_steps, _GRID_CHUNK)
    carry0 = over_grid(lambda kernel: kernel[0])()
    run = over_grid(  # outs (chunk, S[, P])
        lambda kernel, carry: jax.lax.scan(
            kernel[1], carry, None, length=chunk, unroll=4
        ),
        (0,), (0, 1),
    )
    bufs0 = tuple(
        jnp.full((n_steps, *o.shape[1:]), fill, dtype=o.dtype)
        for o, fill in zip(jax.eval_shape(run, carry0)[1], _UNREACHED)
    )

    def any_active(state):
        i, carry, _ = state
        n_eps, done = carry[0][4], carry[0][6]
        return (i < n_steps) & jnp.any(~done & (n_eps < max_eps))

    def body(state):
        i, carry, bufs = state
        carry, outs = run(carry)
        bufs = tuple(
            jax.lax.dynamic_update_slice_in_dim(b, o, i, 0)
            for b, o in zip(bufs, outs)
        )
        return i + chunk, carry, bufs

    i, carry, bufs = jax.lax.while_loop(
        any_active, body, (jnp.asarray(0, dtype=jnp.int32), carry0, bufs0)
    )
    agg = over_grid(lambda kernel, c, o: kernel[2](c, o), (0, 1))(
        carry, bufs
    )
    return agg, i[None]


@partial(jax.jit, static_argnames=("n_steps", "mix"))
def _grid_jit(tables, arrivals, deadlines, phases, beliefs, draws, means,
              zeta, edges, t0, horizon, max_eps, drain, b_max, n_steps, mix):
    def kernel_of(lane, tab):
        arr, adm, dl, ph, bel, dr = lane
        return _scan_kernel(
            tab, arr, dl, ph, bel, dr, means, zeta, edges, t0, horizon,
            max_eps, drain, b_max, record=False, mix=mix, arr_adm=adm,
        )

    # masked once here: built in the loop's body, the (S, N) mask would be
    # recomputed every chunk
    lanes = (arrivals, _admissible(arrivals, horizon), deadlines, phases,
             beliefs, draws)
    return _lockstep_grid(
        kernel_of, lanes, tables, n_steps=n_steps, max_eps=max_eps
    )


@partial(jax.jit, static_argnames=("n_steps", "mix"))
def _grid_adaptive_jit(tables, arrivals, deadlines, phases, beliefs, draws,
                       means, zeta, edges, t0, horizon, max_eps, drain,
                       b_max, adap, n_steps, mix):
    # the bank stack is the whole policy axis here (the controller selects
    # among its P entries live), so the grid has trace lanes only
    def kernel_of(lane, _):
        arr, adm, dl, ph, bel, dr = lane
        return _scan_kernel(
            tables, arr, dl, ph, bel, dr, means, zeta, edges, t0, horizon,
            max_eps, drain, b_max, adap, record=False, mix=mix,
            adaptive=True, arr_adm=adm,
        )

    lanes = (arrivals, _admissible(arrivals, horizon), deadlines, phases,
             beliefs, draws)
    return _lockstep_grid(
        kernel_of, lanes, None, n_steps=n_steps, max_eps=max_eps
    )


def run_grid(
    tables,
    arrivals,
    *,
    means,
    zeta=None,
    draws=None,
    b_max: int,
    max_epochs: Optional[int] = None,
    t0: float = 0.0,
    horizon: Optional[float] = None,
    drain: bool = True,
    deadlines=None,
    phases=None,
    phase_mode: str = "oracle",
    beliefs=None,
    hist_edges=None,
):
    """The vmapped sweep: (seeds x scenarios) traces x policy tables.

    ``tables``  — (P, L) stacked action tables (SMDPSchedulerBank.stacked()
    or scheduler.as_action_table per contender), or (P, K, L) phase-indexed
    stacks with ``phases`` = (S, N) per-arrival phase ints (pad_arrivals
    phases=, or the mmpp2_times_jax(with_phases=True) sampler carry);
    ``arrivals`` — (S, N) padded sorted traces (pad_arrivals per trace,
    common N); ``draws`` — (S, D) unit service draws per trace lane (ones
    for det service).

    ``phase_mode`` selects who rows the phase axis: ``"oracle"`` (the
    ``phases`` ints), or the belief lanes with ``beliefs`` = (S, N, K)
    posterior rows per trace (arrivals.belief_forward_jax over the padded
    batch) — ``"belief_argmax"`` rows by the MAP phase, ``"belief_mix"``
    blends the per-phase actions by the posterior.  This is the deployable
    (non-oracle) policy sweep at the same compiled throughput.

    One jitted dispatch returns dict of (S, P) aggregate arrays plus the
    (S, P, n_bins + 2) histogram sketch: everything a bank comparison needs
    (mean latency, power, weighted cost, sketch quantiles) without ever
    materializing per-request data.  ``steps_executed`` is the number of
    event steps the kernel ran: it stops once every lane and table is
    done, short of the step-count bucket it was compiled for.
    """
    with TraceAnnotation("repro.grid.prepare", lanes=len(arrivals)):
        tables = np.asarray(tables, dtype=np.int64)
        arr = np.asarray(arrivals, dtype=np.float64)
        if tables.ndim == 2:
            tables = tables[:, None, :]
        elif tables.ndim != 3:
            raise ValueError(
                f"tables must be (P, L) or (P, K, L); got {tables.shape}"
            )
        if arr.ndim != 2:
            raise ValueError("run_grid wants (S, N) arrivals")
        if arr.shape[1] < _ADMIT_W or not np.isinf(arr[:, -_ADMIT_W:]).all():
            raise ValueError("pad each trace with pad_arrivals first")
        bel = _check_phase_mode(phase_mode, beliefs, tables.shape[1])
        if bel is not None:
            if phases is not None:
                raise ValueError("phases= and beliefs= are mutually exclusive")
            if bel.ndim != 3 or bel.shape[:2] != arr.shape:
                raise ValueError(
                    f"beliefs must be (S, N, K) aligned with arrivals "
                    f"{arr.shape}; got {bel.shape}"
                )
            if phase_mode == "belief_argmax":
                phases = np.argmax(bel, axis=-1)
                bel = None
        elif tables.shape[1] > 1 and phases is None:
            raise ValueError("phase-indexed tables need phases= (S, N) ints")
        mix = phase_mode == "belief_mix"
        dl = (
            np.asarray(deadlines, dtype=np.float64)
            if deadlines is not None
            else np.full_like(arr, np.inf)
        )
        if phases is not None:
            ph = np.asarray(phases, dtype=np.int64)
            if ph.shape != arr.shape:
                raise ValueError(
                    f"phases shape {ph.shape} != arrivals {arr.shape}"
                )
            if ph.min() < 0 or ph.max() >= tables.shape[1]:
                raise ValueError(
                    f"phases outside the table stack [0, {tables.shape[1]})"
                )
        else:
            ph = np.zeros(arr.shape, dtype=np.int64)
        means = np.asarray(means, dtype=np.float64)
        zeta_a = (
            np.zeros(b_max + 1)
            if zeta is None
            else np.asarray(zeta, dtype=np.float64).copy()
        )
        # a = 0 never accounts energy (the kernel adds zeta[a])
        zeta_a[0] = 0.0
        if draws is None:
            draws = np.ones((arr.shape[0], 1))
        draws = np.asarray(draws, dtype=np.float64)
        n_arr_max = int(np.isfinite(arr).sum(axis=1).max())
        max_eps = 2 * n_arr_max + 2 if max_epochs is None else int(max_epochs)
        edges = (
            default_hist_edges(means)
            if hist_edges is None
            else np.asarray(hist_edges, dtype=np.float64)
        )
        cap = _bucket(n_arr_max + max_eps + 1)
        ck = ("grid", arr.shape, tables.shape, cap, mix)
        n_steps = _initial_steps(ck, n_arr_max, max_eps, cap)
        bel_j = (  # unused unless mix
            jnp.zeros((arr.shape[0], 1, 1))
            if bel is None
            else jnp.asarray(bel)
        )
        # uploaded once: an escalated dispatch reuses the device arrays
        dev = (
            jnp.asarray(tables), jnp.asarray(arr), jnp.asarray(dl),
            jnp.asarray(ph), bel_j, jnp.asarray(draws), jnp.asarray(means),
            jnp.asarray(zeta_a), jnp.asarray(edges),
        )
    while True:
        with TraceAnnotation("repro.grid.run", steps_run=n_steps):
            out, ran = _grid_jit(
                *dev,
                float(t0), np.inf if horizon is None else float(horizon),
                max_eps, bool(drain), int(b_max), int(n_steps), mix,
            )
            done = n_steps >= cap or not bool(
                np.asarray(out["incomplete"]).any()
            )
        if done:
            break
        n_steps = min(2 * n_steps, cap)
    with TraceAnnotation("repro.grid.post", steps_run=n_steps) as post:
        # the vmapped loop runs every lane and table in lockstep until the
        # last is done, a chunk at a time
        used = int(np.asarray(out["n_steps_used"]).max())
        executed = int(np.asarray(ran)[0])
        post.set_metadata(steps_used=used, steps_executed=executed)
        _NSTEPS_CACHE[ck] = min(_bucket(used + 1), cap)
        return _grid_post(out, executed, edges, t0, zeta is not None)


def _grid_post(out, executed, edges, t0, have_energy):
    """Host-side aggregate post-processing shared by the grid entries."""
    out = {k: np.asarray(v) for k, v in out.items()}
    out["steps_executed"] = executed
    out["hist_edges"] = edges
    with np.errstate(invalid="ignore", divide="ignore"):
        span = out["t_final"] - t0
        # starved lane (n_served == 0) -> NaN mean latency, not 0.0: a
        # zero would win every frontier argmin and poison plots silently
        out["w_mean"] = np.where(
            out["n_served"] > 0,
            out["lat_sum"] / np.maximum(out["n_served"], 1),
            np.nan,
        )
        # same convention as the engine's have_energy flag: a lane with no
        # energy source or no served batch reports NaN power, not 0
        out["power"] = np.where(
            have_energy & (out["n_batches"] > 0) & (span > 0),
            out["energy"] / span,
            np.nan,
        )
        # served requests + decision epochs: the event count a throughput
        # figure divides by (same definition as the BENCH_serving series)
        out["events_total"] = int(
            out["n_served"].sum() + out["n_epochs"].sum()
        )
    return out


def run_grid_adaptive(
    arrivals,
    *,
    adaptive,
    means,
    zeta=None,
    draws=None,
    b_max: int,
    max_epochs: Optional[int] = None,
    t0: float = 0.0,
    horizon: Optional[float] = None,
    drain: bool = True,
    deadlines=None,
    phases=None,
    phase_mode: str = "oracle",
    beliefs=None,
    hist_edges=None,
):
    """Seeds-vmapped adaptive dispatch: one controller config, S traces.

    The adaptive analogue of `run_grid`: every trace lane runs the
    in-carry `AdaptiveController` (``adaptive`` — an `AdaptiveLane` or the
    controller to lower) over the *whole* bank stack, retuning live, so
    the policy axis collapses into the carry and the vmap covers trace
    lanes only.  Each lane starts from the controller's current state —
    fresh controllers per seed, the replication-sweep semantics.  Returns
    the same dict as `run_grid` with (S,) aggregates plus the final
    per-lane controller state (``ad_*`` keys).  ``phase_mode`` /
    ``beliefs`` / ``phases`` row the bank entries' phase axis exactly as
    in `run_grid` (e.g. a belief-tracked phase row on top of bank
    retuning = AdaptiveController(phase_filter=...)).
    """
    lane = _coerce_adaptive(adaptive)
    tables = lane.tables
    arr = np.asarray(arrivals, dtype=np.float64)
    if arr.ndim != 2:
        raise ValueError("run_grid_adaptive wants (S, N) arrivals")
    if arr.shape[1] < _ADMIT_W or not np.isinf(arr[:, -_ADMIT_W:]).all():
        raise ValueError("pad each trace with pad_arrivals first")
    bel = _check_phase_mode(phase_mode, beliefs, tables.shape[1])
    if bel is not None:
        if phases is not None:
            raise ValueError("phases= and beliefs= are mutually exclusive")
        if bel.ndim != 3 or bel.shape[:2] != arr.shape:
            raise ValueError(
                f"beliefs must be (S, N, K) aligned with arrivals "
                f"{arr.shape}; got {bel.shape}"
            )
        if phase_mode == "belief_argmax":
            phases = np.argmax(bel, axis=-1)
            bel = None
    mix = phase_mode == "belief_mix"
    dl = (
        np.asarray(deadlines, dtype=np.float64)
        if deadlines is not None
        else np.full_like(arr, np.inf)
    )
    if phases is not None:
        ph = np.asarray(phases, dtype=np.int64)
        if ph.shape != arr.shape:
            raise ValueError(f"phases shape {ph.shape} != arrivals {arr.shape}")
        if ph.min() < 0 or ph.max() >= tables.shape[1]:
            raise ValueError(
                f"phases outside the table stack [0, {tables.shape[1]})"
            )
    else:
        ph = np.zeros(arr.shape, dtype=np.int64)
    means = np.asarray(means, dtype=np.float64)
    zeta_a = (
        np.zeros(b_max + 1)
        if zeta is None
        else np.asarray(zeta, dtype=np.float64).copy()
    )
    zeta_a[0] = 0.0
    if draws is None:
        draws = np.ones((arr.shape[0], 1))
    draws = np.asarray(draws, dtype=np.float64)
    n_arr_max = int(np.isfinite(arr).sum(axis=1).max())
    max_eps = 2 * n_arr_max + 2 if max_epochs is None else int(max_epochs)
    edges = (
        default_hist_edges(means)
        if hist_edges is None
        else np.asarray(hist_edges, dtype=np.float64)
    )
    cap = _bucket(n_arr_max + max_eps + 1)
    ck = ("grid_adaptive", arr.shape, tables.shape, cap, mix)
    n_steps = _initial_steps(ck, n_arr_max, max_eps, cap)
    bel_j = (
        jnp.zeros((arr.shape[0], 1, 1)) if bel is None else jnp.asarray(bel)
    )
    adap_j = lane.lowered()
    while True:
        out, ran = _grid_adaptive_jit(
            jnp.asarray(tables), jnp.asarray(arr), jnp.asarray(dl),
            jnp.asarray(ph), bel_j, jnp.asarray(draws), jnp.asarray(means),
            jnp.asarray(zeta_a), jnp.asarray(edges),
            float(t0), np.inf if horizon is None else float(horizon),
            max_eps, bool(drain), int(b_max), adap_j, int(n_steps), mix,
        )
        if n_steps >= cap or not bool(np.asarray(out["incomplete"]).any()):
            break
        n_steps = min(2 * n_steps, cap)
    _NSTEPS_CACHE[ck] = min(
        _bucket(int(np.asarray(out["n_steps_used"]).max()) + 1), cap
    )
    return _grid_post(
        out, int(np.asarray(ran)[0]), edges, t0, zeta is not None
    )
