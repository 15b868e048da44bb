"""Fleet serving simulator: M routed replicas, ONE jitted `lax.scan` kernel.

`serving.compiled` simulates the paper's single batch-service queue; real
deployments put M replicas behind a router.  This module extends the same
event-kernel discipline to a fleet: one scan step is one *event* — an
arrival admission (routed to a replica), a decision epoch on one replica,
or a clock advance to the next arrival/completion — with a scalars-plus-
(M,)-vectors carry, so the whole fleet is still a single `lax.scan` that
vmaps over (seeds x scenarios) x policies x routers and shards across
devices via `shard_map` (through distributed.meshcompat + launch.mesh).

Routers (the `router_id` is a traced scalar, so the router axis vmaps):

  * ``rr``          round-robin — arrival i goes to server (i + rr0) % M.
  * ``jsq``         join-shortest-queue on ``2*qlen + busy`` (a busy server
                    with the same backlog loses ties to an idle one; index
                    order breaks exact ties).
  * ``pow2``        power-of-two-choices: two candidates from pre-drawn
                    uniforms (shared with the Python reference so both
                    backends route identically), better JSQ score wins.
  * ``batch_aware`` targets the server whose queue is *closest to its SMDP
                    table's next admission threshold*: the request that
                    completes a batch ships immediately, so send arrivals
                    where they unblock a serve first (threshold_gaps
                    precomputes the distance per (server, phase, queue)).

Each replica runs its own (optionally heterogeneous) policy table — a
(M, K, L) stack, phase row selected by the phase of the *last admitted
arrival* fleet-wide, the same oracle-phase discipline as the single-server
kernel.  Decision-epoch semantics per replica are exactly
`serving.compiled._scan_core`'s: admit-all-due-then-decide, wait jumps,
b_max-capped tail drain, epoch budgets.  An M=1 fleet is decision-for-
decision identical to the single-server kernel (`verify_fleet` asserts it,
and the Python reference `PythonFleet` replays every router tie-break).

Chunked streaming (`FleetStream` / `simulate_fleet_stream`): the record
path materializes O(horizon) per-request buffers; the streaming path scans
the arrival stream in fixed-size chunks, carries the per-server leftover
queues and busy clocks across chunk boundaries, and folds each chunk's
latencies / SLO misses / energy into the O(1)-memory aggregates
(`ServingMetrics` P² quantiles + the fixed-bin histogram sketch), so
billion-event horizons run in O(chunk) memory.  Completions later than the
chunk's last arrival are deferred to the next chunk (a later chunk's
arrival may precede them); latencies are accounted at serve start, when
the completion time is already known, so in-flight batches across a
boundary are never double- or under-counted.  Belief row-selection
streams too: `FleetStream(phase_mode="belief_argmax" | "belief_mix",
belief_filter=...)` forwards the MMPP posterior chunk by chunk
(`belief_forward_jax` resumed from the carried filter state), so the
non-oracle lanes reach the same O(chunk)-memory horizons.

Degraded mode (`serving.faults`): a frozen `FaultSchedule` threads
replica outage boundaries and per-attempt straggler multipliers through
the kernel.  Routers mask DOWN replicas (rr scans forward for the first
UP slot; score routers add a penalty term), a down-start strictly before
an in-flight batch's completion crashes it — the requests requeue to the
FRONT with bounded retries, then drop — crashed attempts burn prorated
energy, and ``buffer=B`` bounds each replica's waiting room (overflow
arrivals shed at admission).  All of it runs identically in the compiled
kernel, `PythonFleet`, and `FleetStream` (fault cursors, retry counters
and in-flight requeues carry across chunks), and in `run_fleet_grid`,
whose lanes each take their own schedule; `verify_faults` certifies the
contract per router and arrival family.
"""
from __future__ import annotations

import dataclasses
import json
import math
from functools import partial
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.service_models import ServiceModel  # noqa: F401  (x64 on import)

from .arrivals import belief_forward_jax
from .compiled import (
    _ADMIT_W,
    _bucket,
    _check_phase_mode,
    default_hist_edges,
    pad_arrivals,
)
from .metrics import P2Quantile, histogram_quantiles

#: router name -> kernel id (a traced scalar inside the scan)
ROUTERS: Dict[str, int] = {"rr": 0, "jsq": 1, "pow2": 2, "batch_aware": 3}

#: JSQ score = 2*min(qlen, _SCORE_QCAP) + busy_flag; the cap keeps the
#: batch-aware combined score (gap * _GAP_SHIFT + jsq) inside int32
_SCORE_QCAP = (1 << 14) - 1
_GAP_SHIFT = 1 << 15
#: additive int32 routing penalty for DOWN replicas: combined healthy
#: scores stay < 2^30, so one penalty pushes every DOWN replica behind
#: every UP one while preserving the among-down relative order
_DOWN_PENALTY = 1 << 30
#: buf_cap sentinel for "no finite waiting room" (queues never reach it)
_NO_BUFFER = 1 << 30


def router_id(router) -> int:
    """Resolve a router name (or already-an-id) to its kernel id."""
    if isinstance(router, str):
        try:
            return ROUTERS[router]
        except KeyError:
            raise ValueError(
                f"unknown router {router!r}; one of {sorted(ROUTERS)}"
            ) from None
    rid = int(router)
    if rid not in ROUTERS.values():
        raise ValueError(f"router id {rid} not in {sorted(ROUTERS.values())}")
    return rid


def _jsq_score(qlen: int, busy: bool) -> int:
    return 2 * min(int(qlen), _SCORE_QCAP) + int(busy)


def _belief_phases(phase_mode, beliefs, phases, n_phases):
    """Resolve the fleet's phase stream from a belief posterior.

    Returns ``(phases, bel)``.  The fleet kernel selects one phase row
    fleet-wide (the last admitted arrival's); the belief-argmax rule is
    therefore just a derived phase stream — ``argmax(beliefs)`` through
    the existing phases plumbing, exactly `simulate_compiled`'s lowering
    (``bel`` comes back None).  The belief-*mixture* rule keeps the
    posterior rows (``bel`` is the (N, K) array the kernel's mix lane
    consumes) AND derives the same argmax phase stream — decisions blend
    the per-phase actions, while the batch-aware router's threshold gaps
    (a per-phase integer lookup) follow the MAP phase.
    """
    bel = _check_phase_mode(phase_mode, beliefs, n_phases)
    if bel is None:
        return phases, None
    if phases is not None:
        raise ValueError("phases= and beliefs= are mutually exclusive")
    if bel.ndim not in (2, 3):  # (N, K) per-lane or (S, N, K) grids
        raise ValueError(f"beliefs must be (N, K) or (S, N, K); got {bel.shape}")
    phases = np.argmax(bel, axis=-1)
    return phases, (bel if phase_mode == "belief_mix" else None)


def threshold_gaps(tables: np.ndarray) -> np.ndarray:
    """Distance-to-next-admission-threshold per (server, phase, queue).

    ``gaps[m, k, q]`` is how many arrivals *beyond the incoming one* server
    m (in phase k, with q currently queued) still needs before its table
    first serves: 0 means this arrival lands in a queue state whose action
    is a serve — the request ships immediately.  States past the table end
    follow the eq.-30 extension (the last column repeats), and a row that
    never serves gets the max gap L (routed last).  The batch-aware router
    scores ``gap * _GAP_SHIFT + jsq_score`` so equal-gap servers fall back
    to join-shortest-queue.
    """
    tables = np.asarray(tables, dtype=np.int64)
    if tables.ndim == 2:
        tables = tables[:, None, :]
    if tables.ndim != 3:
        raise ValueError(f"tables must be (M, L) or (M, K, L); got {tables.shape}")
    M, K, L = tables.shape
    gaps = np.empty((M, K, L), dtype=np.int64)
    for m in range(M):
        for k in range(K):
            row = tables[m, k]
            # nxt[s] = smallest serving state >= s (within the table; the
            # eq.-30 extension makes every state >= L serve iff row[-1] > 0)
            nxt = np.full(L, L + 1, dtype=np.int64)  # L+1 == "never"
            nn = L if row[L - 1] > 0 else L + 1  # first serve state past the end
            for s in range(L - 1, -1, -1):
                if row[s] > 0:
                    nn = s
                nxt[s] = nn
            for q in range(L):
                tgt = q + 1  # queue length after this arrival joins
                if tgt >= L:
                    g = 0 if row[L - 1] > 0 else L
                else:
                    ns = nxt[tgt]
                    if ns <= L:
                        g = min(ns, L) - tgt if ns > tgt else 0
                    else:
                        g = L  # never serves: max gap, routed last
                gaps[m, k, q] = min(g, L)
    return gaps


@dataclasses.dataclass
class FleetResult:
    """Aggregates of one fleet run (arrays already on host)."""

    t_final: float
    n_served: int  # total over replicas (carried q0 + this run's arrivals)
    n_batches: int
    n_epochs: int
    n_admitted: int
    energy: float
    lat_sum: float
    slo_miss: int
    terminated: bool  # stream exhausted and every replica drained/stopped
    hist: np.ndarray  # (n_bins + 2,) counts; [0]=underflow, [-1]=overflow
    hist_edges: np.ndarray
    # degraded-mode counters (zero on fault-free, unbuffered runs)
    n_crashes: int = 0  # batch attempts killed by a replica down-start
    n_dropped: int = 0  # requests dropped after max_retries crashes
    n_shed: int = 0  # arrivals rejected by the finite waiting room
    # per-replica state (all (M,)): final queue lengths, busy clocks,
    # per-replica routed/served counts — conservation checks + stream carry
    qlen: np.ndarray = dataclasses.field(default_factory=lambda: np.zeros(0))
    busy: np.ndarray = dataclasses.field(default_factory=lambda: np.zeros(0))
    n_routed: np.ndarray = dataclasses.field(default_factory=lambda: np.zeros(0))
    n_served_m: np.ndarray = dataclasses.field(default_factory=lambda: np.zeros(0))
    # record=True only:
    actions: Optional[np.ndarray] = None  # (n_epochs,) batch size, 0 = wait
    servers: Optional[np.ndarray] = None  # (n_epochs,) deciding replica
    latencies: Optional[np.ndarray] = None  # (n,) arrival-indexed (NaN unserved)
    served: Optional[np.ndarray] = None  # (n,) bool, arrival served this run
    arr_server: Optional[np.ndarray] = None  # (n,) replica each arrival joined
    dropped: Optional[np.ndarray] = None  # (n,) bool, crash-dropped this run
    shed: Optional[np.ndarray] = None  # (n,) bool, rejected at admission

    @property
    def batch_sizes(self) -> np.ndarray:
        if self.actions is None:
            raise ValueError("run with record=True for per-epoch decisions")
        return self.actions[self.actions > 0]

    @property
    def w_mean(self) -> float:
        return self.lat_sum / self.n_served if self.n_served else float("nan")


# ---------------------------------------------------------------------------
# The compiled kernel
# ---------------------------------------------------------------------------


def _fleet_kernel(
    tables, thr_gap, arrivals, deadlines, phases, beliefs, bel0, router_u,
    q0_times, q0_dl, draws, means, zeta, edges, fb, fmult,
    rid, t0, horizon, max_eps, drain, b_max, buf_cap, max_retries,
    rr0, ph0, busy0, nbat0, needs0, fcur0, rty0, infl0, more_coming, t_last,
    *, mix: bool,
):
    """The fleet event kernel: one step == one admission, one decision
    epoch on one replica, one fault boundary, or one clock advance.

    Returns its three pieces ``(carry0, step, finish)``: the carry before
    the first step, ``step(carry, _) -> (carry, out)`` (a `lax.scan`
    body), and ``finish(carry, outs, *, record)``, the per-request
    reconstruction and aggregates from the final carry and the stacked
    per-step outputs.  `_fleet_jit` scans a fixed number of steps;
    `_fleet_grid_core` stops once no instance of its grid is active.

    An inactive step (``done``, or the epoch budget spent) changes no
    carry: every update but the drain wake is gated on ``active``, and the
    wake finds nothing new, since an instance turns inactive only after a
    decision step, whose own wake saw the same stream state and whose
    decision leaves its replica busy, crashed or empty.  (An instance
    inactive from the start may wake replicas of a carried queue once; the
    grid carries none.)  Its outputs are fixed: no dispatch, no admission,
    and a sort key past every (replica, position) that ``finish`` reads.

    Pure jax function (callers jit/vmap).  ``tables`` is (M, K, L);
    ``thr_gap`` the matching threshold_gaps array; ``arrivals`` sorted with
    trailing +inf sentinels; ``router_u`` (size, 2) pre-drawn uniforms for
    pow2 (aligned with arrivals); ``q0_times``/``q0_dl`` (M, Q0) +inf-padded
    per-replica leftover queues carried in from a previous chunk (Q0 = 0
    for a fresh run); ``busy0``/``nbat0``/``rr0``/``ph0`` the carried
    replica clocks / draw cursors / router + phase state.

    Degraded-mode extensions (serving.faults semantics contract):

      * ``fb`` (M, >=1) is the +inf-padded per-replica down-boundary array
        (FaultSchedule.bounds, parity of the carried cursor ``fcur0`` =
        availability) and ``fmult`` (M, >=1) the per-attempt service
        multipliers.  Boundaries replay as their own steps, before any
        admission/decision at the same clock, so routing masks always see
        fresh parity.  A dispatched batch crashes iff the replica's next
        down interval starts strictly before its would-be completion; the
        crashed requests requeue to the FRONT (they keep their substream
        positions) and after ``max_retries`` consecutive crashes the batch
        is dropped (counted, never served).  Crashed-attempt energy is
        prorated, zeta(a) * elapsed / service.
      * ``buf_cap`` is the finite waiting room B (pass _NO_BUFFER to turn
        it off): a routed arrival finding B requests already waiting
        (queued + crashed-in-flight) is shed — it consumes its router
        slot but never queues.
      * ``mix=True`` — the belief-mixture action rule of the single-server
        kernel: ``round(sum_k beliefs[last_adm, k] * table[m, k, q])``
        with ``beliefs`` (size, K) posterior rows aligned with arrivals
        and ``bel0`` the carried posterior row standing in before this
        chunk's first admission.

    With ``fb`` all-+inf, ``fmult`` all-ones, ``buf_cap`` = _NO_BUFFER and
    ``mix=False`` every expression reduces bitwise to the fault-free
    kernel (verify_fleet's rail).

    Streaming contract: with ``more_coming`` true, completions (and fault
    boundaries) strictly later than ``t_last`` (the chunk's last arrival)
    are deferred — the next chunk's arrivals may precede them — and
    replicas park instead of terminating.  Latency/SLO/energy are
    accounted at serve start (the completion time is known then), so a
    batch in flight across the chunk boundary is accounted exactly once,
    in the chunk that launched it.

    Step priority, chosen so an M=1 fleet replays the single-server kernel
    decision-for-decision: (0) a due fault boundary replays (lowest index
    first, one per step); (1) else a due arrival is admitted (routed, one
    per step) before any decision; (2) else the lowest-index replica with
    a pending decision flag decides — wait / serve / terminate, exactly
    `compiled._scan_core`'s rules per replica; (3) else the clock advances
    to the next arrival, completion, or relevant fault boundary, arrivals
    winning time ties (the single-server kernel admits all due arrivals
    before deciding), completions winning over boundaries (a batch whose
    down interval starts exactly at its completion time finishes first).
    """
    M, K, L = tables.shape
    size = arrivals.shape[0]
    Q0 = q0_times.shape[1]
    n_bins = edges.shape[0] - 1
    n_draws = draws.shape[0]
    nfb = fb.shape[1]
    n_mult = fmult.shape[1]
    arr_adm = jnp.where(arrivals < horizon, arrivals, jnp.inf)
    i64 = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    midx = jnp.arange(M)
    # a Python-bool more_coming would make `~more_coming` the int -1 and
    # silently promote the needs/done bool carries to int
    more_coming = jnp.asarray(more_coming, dtype=bool)
    drain = jnp.asarray(drain, dtype=bool)
    t_last = jnp.asarray(t_last, dtype=jnp.float64)
    c0 = jnp.sum(jnp.isfinite(q0_times), axis=1).astype(i64)  # carried queue
    P_sub = Q0 + size  # max substream length per replica
    key_t = jnp.int32 if (M + 1) * (P_sub + 1) < 2**31 else i64
    fcur0 = jnp.asarray(fcur0, dtype=i64)
    infl0 = jnp.asarray(infl0, dtype=i64)

    def step(carry, _):
        (t, n_adm, rr, ph, neps, nuse, done,
         busy, qlen, n_route, n_srv, nbat, needs,
         fcur, rty, infl, ndrop, nshed, energy) = carry
        idle = jnp.isinf(busy)
        down = (fcur % 2) == 1  # odd cursor parity == inside a down interval
        ia = jnp.minimum(n_adm, size - 1)
        nxt = arr_adm[ia]
        stream_dead = jnp.isinf(nxt) & ~more_coming
        # wake idle parked replicas for the b_max-capped tail drain (UP
        # replicas with no crashed batch pending; a DOWN replica wakes at
        # its repair boundary instead)
        needs = needs | (
            stream_dead & idle & (qlen > 0) & drain & ~done
            & ~down & (infl == 0)
        )
        active = ~done & (neps < max_eps)
        # next unreplayed fault boundary per replica (+inf past the end)
        nb = jnp.where(
            fcur < nfb, fb[midx, jnp.minimum(fcur, nfb - 1)], jnp.inf
        )
        bnd_pend = nb <= t
        any_bnd = jnp.any(bnd_pend)
        bstep = active & any_bnd
        due = active & ~any_bnd & (nxt <= t)
        any_pend = jnp.any(needs)
        dec_step = active & ~any_bnd & ~due & any_pend
        adv = active & ~any_bnd & ~due & ~any_pend

        # ---- (0) fault boundary: replay the lowest-index due one -----
        m_b = jnp.argmax(bnd_pend).astype(i64)
        one_b = midx == m_b
        is_start = (fcur[m_b] % 2) == 0  # even cursor -> a down-start
        crash_b = bstep & is_start & (infl[m_b] > 0)
        give_up = crash_b & (rty[m_b] + 1 > max_retries)
        requeue = crash_b & ~give_up
        # the crashed batch's positions start where it was dispatched
        # (nothing on this replica resolved since: no serves while a
        # crashed batch is pending)
        dbase = (n_srv[m_b] + ndrop[m_b]).astype(jnp.int32)
        ndrop = ndrop + jnp.where(give_up & one_b, infl, 0)
        qlen = qlen + jnp.where(requeue & one_b, infl, 0)
        rty = jnp.where(
            crash_b & one_b, jnp.where(give_up, 0, rty + 1), rty
        )
        infl = jnp.where(crash_b & one_b, 0, infl)
        # a down-start silences any pending decision; the matching repair
        # re-arms the replica if work queued up while it was down
        needs = needs & ~(bstep & is_start & one_b)
        needs = needs | (
            bstep & ~is_start & one_b & (qlen > 0) & idle & (infl == 0)
        )
        fcur = fcur + jnp.where(bstep & one_b, 1, 0)

        # ---- (1) admission: route one due arrival --------------------
        qeff = qlen + infl  # crashed in-flight requests still hold the room
        busy_flag = (~idle | (infl > 0)).astype(jnp.int32)
        base = (
            2 * jnp.minimum(qeff, _SCORE_QCAP).astype(jnp.int32) + busy_flag
        )
        # DOWN replicas lose to every UP one: rr scans forward from its
        # slot for the first UP replica (all down -> its own slot); score
        # routers add a +2^30 penalty (scores stay < 2^30, so int32 is
        # safe and the among-down relative order is preserved)
        pen = down.astype(jnp.int32) * _DOWN_PENALTY
        ph_arr = phases[ia]
        rr_idx = (rr + midx) % M
        m_rr = rr_idx[
            jnp.argmin(down[rr_idx].astype(jnp.int32))
        ].astype(i64)
        m_jsq = jnp.argmin(base + pen).astype(i64)
        u = router_u[ia]
        cand1 = jnp.minimum((u[0] * M).astype(i64), M - 1)
        cand2 = jnp.minimum((u[1] * M).astype(i64), M - 1)
        m_p2 = jnp.where(
            base[cand1] + pen[cand1] <= base[cand2] + pen[cand2],
            cand1, cand2,
        )
        # batch-aware: distance to the next admission threshold, with a
        # busy replica's gap penalized by its backlog — an over-threshold
        # queue reports gap 0 while its server is mid-batch, and without
        # the penalty it would absorb the whole stream (equal gaps fall
        # back to the JSQ score)
        gaps = thr_gap[midx, ph_arr, jnp.clip(qeff, 0, L - 1)].astype(
            jnp.int32
        )
        gaps = jnp.minimum(
            gaps + busy_flag * jnp.minimum(qeff, _SCORE_QCAP).astype(
                jnp.int32
            ),
            _SCORE_QCAP,
        )
        m_ba = jnp.argmin(gaps * _GAP_SHIFT + base + pen).astype(i64)
        m_r = jnp.select(
            [rid == 0, rid == 1, rid == 2], [m_rr, m_jsq, m_p2], m_ba
        )
        one_r = midx == m_r
        # finite waiting room: a routed arrival finding B requests already
        # waiting is shed — it consumes its router slot (rr advances, the
        # phase updates) but never occupies a substream position
        shed = due & (qeff[m_r] >= buf_cap)
        admit = due & ~shed
        pos_out = jnp.where(admit, n_route[m_r], 0).astype(jnp.int32)
        adm_idx = jnp.where(due, n_adm, size).astype(jnp.int32)
        qlen = qlen + jnp.where(admit & one_r, 1, 0)
        n_route = n_route + jnp.where(admit & one_r, 1, 0)
        nshed = nshed + jnp.where(shed & one_r, 1, 0)
        needs = needs | (admit & one_r & idle & ~down & (infl == 0))
        ph = jnp.where(due, ph_arr, ph)
        rr = rr + due.astype(i64)
        n_adm = n_adm + due.astype(i64)

        # ---- (2) decision epoch on the first pending replica ---------
        m_d = jnp.argmax(needs).astype(i64)  # lowest-index True
        q_d = qlen[m_d]
        if mix:
            # belief-mixture action rule (compiled._scan_core's mix lane):
            # posterior-weighted blend of the per-phase actions, rounded.
            # Before this chunk's first admission the carried posterior
            # row bel0 stands in for "the last admitted arrival's belief"
            bi = jnp.clip(n_adm - 1, 0, size - 1)
            bel_row = jnp.where(n_adm > 0, beliefs[bi], bel0)
            a = jnp.round(
                jnp.sum(bel_row * tables[m_d, :, jnp.minimum(q_d, L - 1)])
            ).astype(i64)
        else:
            a = tables[m_d, ph, jnp.minimum(q_d, L - 1)]
        a = jnp.clip(a, 0, jnp.minimum(q_d, b_max))
        live = ~stream_dead  # arrivals may still come (this chunk or later)
        force = dec_step & (a == 0) & ~live & (q_d > 0) & drain
        a = jnp.where(force, jnp.minimum(q_d, b_max), a)
        dispatch = dec_step & (a > 0)
        a = jnp.where(dispatch, a, 0)
        svc = (
            means[a]
            * draws[jnp.minimum(nbat[m_d], n_draws - 1)]
            * fmult[m_d, jnp.minimum(nbat[m_d], n_mult - 1)]
        )
        t_done = t + svc
        # crash pre-resolution: the batch fails iff the replica's next
        # down interval starts strictly before the would-be completion
        # (a boundary exactly at t_done completes first).  The deciding
        # replica is UP, so nb[m_d] is its next down-START and > t
        ds_d = nb[m_d]
        will_crash = dispatch & (ds_d < t_done)
        serve = dispatch & ~will_crash
        one_d = midx == m_d
        sel = serve & one_d
        busy = jnp.where(sel, t_done, busy)
        qlen = qlen - jnp.where(dispatch & one_d, a, 0)
        start = (n_srv[m_d] + ndrop[m_d]).astype(jnp.int32)
        n_srv = n_srv + jnp.where(sel, a, 0)
        infl = infl + jnp.where(will_crash & one_d, a, 0)
        rty = jnp.where(sel, 0, rty)
        nbat = nbat + jnp.where(dispatch & one_d, 1, 0)
        neps = neps + dec_step.astype(i64)
        needs = needs & ~(dec_step & one_d)
        m_dec = jnp.where(dec_step, m_d, M).astype(jnp.int32)
        # energy: full zeta on success; a crashed attempt burns prorated
        # energy for the time it actually ran before the down-start
        e_out = jnp.where(serve, zeta[a], 0.0) + jnp.where(
            will_crash, zeta[a] * (ds_d - t) / svc, 0.0
        )

        # ---- (3) advance: arrival, completion, or fault boundary -----
        # streaming deferral: once this chunk's arrivals are exhausted,
        # only completions STRICTLY before the last arrival may process —
        # the next chunk may open with an arrival at that exact time, and
        # arrivals win completion ties (the one-shot kernel's tie-break)
        comp_ok = jnp.isfinite(nxt) | stream_dead | (busy < t_last)
        busy_eff = jnp.where(comp_ok, busy, jnp.inf)
        m_c = jnp.argmin(busy_eff).astype(i64)
        t_c = busy_eff[m_c]
        # boundaries only matter to replicas with queued or crashed work
        # (the repair wakes them); an empty idle replica's boundaries
        # replay lazily once some other event moves the clock past them.
        # The same streaming deferral as completions applies
        bnd_ok = jnp.isfinite(nxt) | stream_dead | (nb < t_last)
        nb_eff = jnp.where(((qlen > 0) | (infl > 0)) & bnd_ok, nb, jnp.inf)
        t_b = jnp.min(nb_eff)
        adv_arr = adv & jnp.isfinite(nxt) & (nxt <= t_c) & (nxt <= t_b)
        adv_cmp = adv & ~adv_arr & jnp.isfinite(t_c) & (t_c <= t_b)
        adv_bnd = adv & ~adv_arr & ~adv_cmp & jnp.isfinite(t_b)
        stuck = adv & ~adv_arr & ~adv_cmp & ~adv_bnd  # drained or deferred
        t = jnp.where(
            adv_arr, nxt,
            jnp.where(adv_cmp, t_c, jnp.where(adv_bnd, t_b, t)),
        )
        one_c = midx == m_c
        busy = jnp.where(adv_cmp & one_c, jnp.inf, busy)
        needs = needs | (adv_cmp & one_c)
        done = done | stuck

        carry = (
            t, n_adm, rr, ph, neps, nuse + active.astype(i64), done,
            busy, qlen, n_route, n_srv, nbat, needs,
            fcur, rty, infl, ndrop, nshed,
            # summed in event order, as compiled._scan_core sums it
            energy + e_out,
        )
        a32 = jnp.where(dispatch, a, 0).astype(jnp.int32)
        # the (replica, first position) of the substream interval this step
        # resolves — a serve or a crash drop, at most one per step — as one
        # sort key; other steps key past every (replica, position)
        mark_m = jnp.where(serve, m_d, jnp.where(give_up, m_b, M))
        mark_pos = jnp.where(serve, start, jnp.where(give_up, dbase, 0))
        key = (mark_m * (P_sub + 1) + mark_pos).astype(key_t)
        out = (a32, m_dec, key, serve, t_done, adm_idx,
               jnp.where(due, m_r, M).astype(jnp.int32), pos_out, shed)
        return carry, out

    zero = jnp.asarray(0, dtype=i64)
    zv = jnp.zeros(M, dtype=i64)
    down_init = (fcur0 % 2) == 1
    carry0 = (
        jnp.asarray(t0, dtype=jnp.float64), zero,
        jnp.asarray(rr0, dtype=i64), jnp.asarray(ph0, dtype=i64),
        zero, zero, jnp.asarray(False),
        jnp.asarray(busy0, dtype=jnp.float64),
        c0 - infl0, c0, zv,
        jnp.asarray(nbat0, dtype=i64),
        # chunk carries hand in the exact pending-decision flags; fresh
        # runs arm every idle healthy replica (the t0 decision round)
        jnp.asarray(needs0, dtype=bool)
        & jnp.isinf(busy0) & (infl0 == 0) & ~down_init,
        fcur0, jnp.asarray(rty0, dtype=i64), infl0, zv, zv,
        jnp.asarray(0.0, dtype=jnp.float64),
    )

    def finish(carry, outs, *, record: bool):
        (a_seq, mdec_seq, key_seq, srv_seq, tdone_seq,
         adm_seq, mr_seq, pos_seq, shed_seq) = outs
        n_steps = key_seq.shape[0]
        (t, n_adm, rr, ph, neps, nuse, done,
         busy, qlen, n_route, n_srv, nbat, needs,
         fcur, rty, infl, ndrop, nshed, energy) = carry

        # --- vectorized per-request reconstruction ----------------------
        # Substream positions are per replica: request p on replica m resolves
        # at the serve (or drop) whose interval [base, base + a) contains p.
        # Sorting the steps by their (replica, base) key lines each replica's
        # intervals up in position order, so the resolving step of (m, p) is
        # the last key <= (m, p): one binary search per request.  (A running
        # max over a positions grid would do it in O(size), but compiles for
        # minutes on TPU.)  The resolving step's serve flag says served vs
        # crash-dropped; positions at or past the replica's resolved count stay
        # unresolved (a budget-cut or drain=False run leaves a queued tail).
        # Carried q0 requests occupy positions [0, c0), this chunk's routed
        # arrivals [c0, n_route).
        keys, key_step = jax.lax.sort(
            (key_seq, jnp.arange(n_steps, dtype=jnp.int32)), num_keys=1
        )
        n_res = n_srv + ndrop

        def resolve(m, p):
            """(served, dropped, completion time) of positions p on
            replicas m."""
            q = (m * (P_sub + 1) + p).astype(key_t)
            last = jnp.searchsorted(keys, q, side="right") - 1
            step = key_step[jnp.clip(last, 0)]
            done = p < n_res[m]
            return done & srv_seq[step], done & ~srv_seq[step], tdone_seq[step]

        # carried-queue part: positions [0, Q0) of each replica's substream
        q0_fin = jnp.isfinite(q0_times)
        q0_served, q0_dropped, q0_comp = resolve(
            midx[:, None], jnp.arange(Q0)[None, :]
        )
        q0_served = q0_served & q0_fin
        q0_dropped = q0_dropped & q0_fin
        q0_lat = jnp.where(q0_served, q0_comp - q0_times, 0.0)
        q0_miss = jnp.sum(q0_served & (q0_comp > q0_dl))

        # arrival part: scatter each routed arrival's (replica, position);
        # shed arrivals record their would-be replica but hold no position
        arr_server = jnp.full(size, M, dtype=jnp.int32).at[adm_seq].set(
            mr_seq, mode="drop"
        )
        arr_pos = jnp.zeros(size, dtype=jnp.int32).at[adm_seq].set(
            pos_seq, mode="drop"
        )
        arr_shed = jnp.zeros(size, dtype=bool).at[adm_seq].set(
            shed_seq, mode="drop"
        )
        admitted = (arr_server < M) & ~arr_shed
        arr_served, arr_dropped, arr_comp = resolve(
            jnp.clip(arr_server, 0, M - 1), arr_pos
        )
        arr_served = admitted & arr_served
        arr_dropped = admitted & arr_dropped
        arr_lat = jnp.where(arr_served, arr_comp - arrivals, 0.0)
        arr_miss = jnp.sum(arr_served & (arr_comp > deadlines))

        lat_sum = jnp.sum(q0_lat) + jnp.sum(arr_lat)
        n_served = jnp.sum(n_srv)
        all_lat = jnp.concatenate([q0_lat.reshape(-1), arr_lat])
        all_ok = jnp.concatenate([q0_served.reshape(-1), arr_served])
        bins = jnp.clip(
            jnp.searchsorted(edges, all_lat, side="right"), 0, n_bins + 1
        )
        hist = jnp.zeros(n_bins + 2, dtype=i64).at[
            jnp.where(all_ok, bins, 0)
        ].add(all_ok.astype(i64))

        n_batches = jnp.sum(srv_seq.astype(i64))  # successful serves
        n_attempts = jnp.sum(nbat) - jnp.sum(jnp.asarray(nbat0))
        agg = {
            "t_final": t, "n_admitted": n_adm, "n_served": n_served,
            "n_batches": n_batches,
            # crashes are counted at dispatch (the chunk that launched the
            # attempt), matching the serve-start accounting discipline
            "n_crashes": n_attempts - n_batches,
            "n_dropped": jnp.sum(ndrop), "n_shed": jnp.sum(nshed),
            "n_epochs": neps, "n_steps_used": nuse,
            "terminated": done & ~more_coming,
            "parked": done & more_coming,
            "incomplete": ~done & (neps < max_eps),
            "energy": energy, "lat_sum": lat_sum,
            "slo_miss": q0_miss + arr_miss, "hist": hist,
            # per-replica state (stream carry + conservation checks)
            "qlen": qlen, "busy": busy, "n_route": n_route, "n_srv": n_srv,
            "nbat": nbat, "rr": rr, "ph": ph, "needs": needs,
            "fcur": fcur, "rty": rty, "infl": infl,
            "ndrop_m": ndrop, "nshed_m": nshed,
        }
        if not record:
            return agg
        rec = (a_seq, mdec_seq, arr_lat, arr_served, arr_dropped, arr_shed,
               arr_server, arr_pos, q0_lat, q0_served, q0_dropped)
        return agg, rec

    return carry0, step, finish


@partial(jax.jit, static_argnames=("n_steps", "record", "mix"))
def _fleet_jit(tables, thr_gap, arrivals, deadlines, phases, beliefs, bel0,
               router_u, q0_times, q0_dl, draws, means, zeta, edges,
               fb, fmult, rid, t0, horizon, max_eps, drain, b_max,
               buf_cap, max_retries,
               rr0, ph0, busy0, nbat0, needs0, fcur0, rty0, infl0,
               more_coming, t_last, n_steps, record, mix):
    carry0, step, finish = _fleet_kernel(
        tables, thr_gap, arrivals, deadlines, phases, beliefs, bel0,
        router_u, q0_times, q0_dl, draws, means, zeta, edges, fb, fmult,
        rid, t0, horizon, max_eps, drain, b_max, buf_cap, max_retries,
        rr0, ph0, busy0, nbat0, needs0, fcur0, rty0, infl0,
        more_coming, t_last, mix=mix,
    )
    carry, outs = jax.lax.scan(step, carry0, None, length=n_steps)
    return finish(carry, outs, record=record)


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------


def _norm_tables(tables, *, want_m: Optional[int] = None) -> np.ndarray:
    """(L,) / (M, L) / (M, K, L) -> (M, K, L) int64."""
    t = np.asarray(tables, dtype=np.int64)
    if t.ndim == 1:
        t = t[None, None, :]
    elif t.ndim == 2:
        t = t[:, None, :]
    elif t.ndim != 3:
        raise ValueError(
            f"tables must be (L,), (M, L) or (M, K, L); got {t.shape}"
        )
    if want_m is not None and t.shape[0] != want_m:
        raise ValueError(f"expected {want_m} replica tables, got {t.shape[0]}")
    return t


def _prep_faults(faults, M: int):
    """FaultSchedule | None -> (fb, fmult, max_retries) kernel arrays.

    ``fb`` always ships >= 1 column (all-+inf when fault-free) so the
    kernel's boundary gather never indexes an empty axis.
    """
    if faults is None:
        return np.full((M, 1), np.inf), np.ones((M, 1)), 0
    from .faults import stack_schedules

    fb, fmult, max_retries = stack_schedules([faults], M)
    return fb[0], fmult[0], max_retries


def _prep_inputs(
    tables, arrivals, *, means, zeta, draws, b_max, deadlines, phases,
    slo, hist_edges, router_u, router_seed, bel=None,
):
    """Shared normalization for simulate_fleet / FleetStream / the grid."""
    tables = _norm_tables(tables)
    M, K, L = tables.shape
    arr = np.asarray(arrivals, dtype=np.float64)
    if slo is not None:
        if deadlines is not None:
            raise ValueError("pass slo= or deadlines=, not both")
        deadlines = np.where(np.isfinite(arr), arr + slo, np.inf)
    if len(arr) < _ADMIT_W or not np.isinf(arr[-_ADMIT_W:]).all():
        raw = arr
        padded = pad_arrivals(
            arr, deadlines,
            phases=phases if phases is not None else None,
        )
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
            bel_p = np.zeros((len(arr), bel.shape[1]))
            bel_p[: len(kept)] = kept[order]
            bel = bel_p
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
    if len(dl) != len(arr) or len(ph) != len(arr):
        raise ValueError("padded deadlines/phases must align with arrivals")
    if bel is not None and len(bel) != len(arr):
        raise ValueError("padded beliefs must align with arrivals")
    if phases is not None and K > 1 and (ph.min() < 0 or ph.max() >= K):
        raise ValueError(f"phases outside the table stack [0, {K})")
    if K > 1 and phases is None:
        raise ValueError("phase-indexed (M, K, L) tables need phases=")
    if router_u is None:
        router_u = np.random.default_rng(router_seed).random((len(arr), 2))
    router_u = np.asarray(router_u, dtype=np.float64)
    if router_u.shape != (len(arr), 2):
        # raw (n, 2) uniforms are padded alongside the arrivals (padded
        # slots are never admitted, so their draws are never consumed)
        ru = np.full((len(arr), 2), 0.5)
        ru[: len(router_u)] = router_u
        router_u = ru
    means = np.asarray(means, dtype=np.float64)
    zeta_a = (
        np.zeros(b_max + 1)
        if zeta is None
        else np.asarray(zeta, dtype=np.float64).copy()
    )
    zeta_a[0] = 0.0  # a = 0 never accounts energy
    if draws is None:
        draws = np.ones(1)
    draws = np.asarray(draws, dtype=np.float64)
    edges = (
        default_hist_edges(means)
        if hist_edges is None
        else np.asarray(hist_edges, dtype=np.float64)
    )
    return tables, arr, dl, ph, bel, router_u, means, zeta_a, draws, edges


def simulate_fleet(
    tables,
    arrivals,
    *,
    router="jsq",
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
    slo: Optional[float] = None,
    hist_edges=None,
    record: bool = False,
    router_u=None,
    router_seed: int = 0,
    faults=None,
    buffer: Optional[int] = None,
) -> FleetResult:
    """Run M replica policy tables over one routed arrival trace, compiled.

    ``tables`` is (M, L) — one action table per replica, heterogeneous
    allowed — or (M, K, L) phase-indexed stacks with ``phases`` per arrival
    (the phase of the last admitted arrival selects the row fleet-wide,
    the single-server kernel's oracle-phase discipline).  Non-oracle row
    selection: ``phase_mode="belief_argmax"`` with ``beliefs`` (n, K)
    posterior rows (`arrivals.belief_forward_jax`) derives the phase
    stream from the filter posterior instead of an oracle switch trace;
    ``"belief_mix"`` keeps the posterior rows and blends the per-phase
    actions per decision (the single-server mix rule; the batch-aware
    router's threshold gaps follow the MAP phase).  ``router`` is one
    of ``rr | jsq | pow2 | batch_aware``; pow2 consumes ``router_u``
    ((n, 2) uniforms, drawn from ``router_seed`` when absent) so the
    compiled lane and the PythonFleet reference route identically.

    Degraded-mode knobs: ``faults`` is a `serving.faults.FaultSchedule`
    (routers mask DOWN replicas; a mid-service down-start crashes the
    in-flight batch, which requeues to the front and — after the
    schedule's ``max_retries`` consecutive crashes — is dropped);
    ``buffer`` a finite waiting room B (a routed arrival finding B
    requests waiting is shed).  Both default off and are then bitwise
    no-ops on the kernel.

    Service/energy conventions are `simulate_compiled`'s: service time of a
    batch of a is ``means[a] * draws[k]`` with one draw consumed per serve
    *per replica* (draw cursor = that replica's batch count), energy
    ``zeta[a]`` summed over serves.  An M=1 fleet is decision-for-decision
    identical to the single-server kernel.

    ``record=True`` additionally returns the per-epoch decision log
    (action + deciding replica), arrival-indexed latencies, and the
    per-arrival dropped/shed flags — O(n) buffers; for long horizons use
    `FleetStream` / `simulate_fleet_stream` which fold chunks into O(1)
    aggregates instead.
    """
    rid = router_id(router)
    bel = None
    if phase_mode != "oracle" or beliefs is not None:
        if beliefs is not None and (
            np.asarray(beliefs).ndim != 2
            or len(np.asarray(beliefs)) != len(np.asarray(arrivals))
        ):
            raise ValueError("beliefs must be (n, K) aligned with arrivals")
        phases, bel = _belief_phases(
            phase_mode, beliefs, phases, _norm_tables(tables).shape[1]
        )
    (tables, arr, dl, ph, bel, router_u, means, zeta_a, draws, edges) = (
        _prep_inputs(
            tables, arrivals, means=means, zeta=zeta, draws=draws,
            b_max=b_max, deadlines=deadlines, phases=phases, slo=slo,
            hist_edges=hist_edges, router_u=router_u,
            router_seed=router_seed, bel=bel,
        )
    )
    M = tables.shape[0]
    thr = threshold_gaps(tables)
    fb, fmult, max_retries = _prep_faults(faults, M)
    n_bnd = int(np.isfinite(fb).sum())
    if buffer is not None and int(buffer) < 0:
        raise ValueError("buffer must be >= 0")
    buf_cap = _NO_BUFFER if buffer is None else int(buffer)
    mix = bel is not None
    bel_j = jnp.asarray(bel) if mix else jnp.zeros((1, 1))
    bel0_j = bel_j[0]
    n_arr = int(np.sum(np.isfinite(arr)))
    # crashes re-serve their batch and repairs wake queued replicas —
    # at most two extra epochs per finite fault boundary
    max_eps = (
        (2 * n_arr + M + 4 + 2 * n_bnd)
        if max_epochs is None
        else int(max_epochs)
    )
    q0_t = np.full((M, 1), np.inf)
    q0_d = np.full((M, 1), np.inf)
    busy0 = np.full(M, np.inf)
    nbat0 = np.zeros(M, dtype=np.int64)
    zm = np.zeros(M, dtype=np.int64)
    # one step per admission, epoch, boundary, or advance; each of those
    # is preceded by at most one advance, so 2x is a hard cap
    cap = _bucket(2 * (n_arr + max_eps + n_bnd) + 2 * M + 8)
    # every arrival costs a clock advance plus its admission step, so 2n
    # steps is a floor; decisions and completions add the rest
    n_steps = min(_bucket(max(256, (5 * n_arr) // 2 + 2 * M + 8)), cap)
    while True:
        out = _fleet_jit(
            jnp.asarray(tables), jnp.asarray(thr), jnp.asarray(arr),
            jnp.asarray(dl), jnp.asarray(ph), bel_j, bel0_j,
            jnp.asarray(router_u),
            jnp.asarray(q0_t), jnp.asarray(q0_d), jnp.asarray(draws),
            jnp.asarray(means), jnp.asarray(zeta_a), jnp.asarray(edges),
            jnp.asarray(fb), jnp.asarray(fmult),
            int(rid), float(t0),
            np.inf if horizon is None else float(horizon),
            max_eps, bool(drain), int(b_max),
            int(buf_cap), int(max_retries),
            0, 0, jnp.asarray(busy0), jnp.asarray(nbat0),
            jnp.ones(M, dtype=bool),
            jnp.asarray(zm), jnp.asarray(zm), jnp.asarray(zm),
            False, np.inf, int(n_steps), bool(record), mix,
        )
        agg = out[0] if record else out
        if n_steps >= cap or not bool(agg["incomplete"]):
            break
        n_steps = min(2 * n_steps, cap)
    rec = out[1] if record else None
    agg = {k: np.asarray(v) for k, v in agg.items()}
    res = FleetResult(
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
        n_crashes=int(agg["n_crashes"]),
        n_dropped=int(agg["n_dropped"]),
        n_shed=int(agg["n_shed"]),
        qlen=agg["qlen"],
        busy=agg["busy"],
        n_routed=agg["n_route"],
        n_served_m=agg["n_srv"],
    )
    if record:
        (a_seq, mdec_seq, arr_lat, arr_served, arr_dropped, arr_shed,
         arr_server) = (np.asarray(x) for x in rec[:7])
        dec = mdec_seq < M
        res.actions = a_seq[dec].astype(np.int64)
        res.servers = mdec_seq[dec].astype(np.int64)
        n = len(np.asarray(arrivals))
        res.served = arr_served[:n]
        res.latencies = np.where(res.served, arr_lat[:n], np.nan)
        res.arr_server = np.where(
            arr_server[:n] < M, arr_server[:n], -1
        ).astype(np.int64)
        res.dropped = arr_dropped[:n]
        res.shed = arr_shed[:n]
    return res


# ---------------------------------------------------------------------------
# Python reference router loop (the equivalence side of verify_fleet)
# ---------------------------------------------------------------------------


class PythonFleet:
    """Reference M-replica router loop, event-for-event the compiled kernel.

    Same step priority (admit due arrival -> decide lowest-index pending
    replica -> advance the clock, arrivals winning ties), same router
    tie-breaks (shared ``router_u`` uniforms for pow2), same draw cursor
    discipline (one unit draw per serve per replica, indexed by that
    replica's batch count).  Interpreter-speed — it exists to certify the
    compiled lane (`verify_fleet`) and to test snapshot()/restore()
    through the router state.
    """

    def __init__(
        self,
        tables,
        arrivals,
        *,
        router="jsq",
        means,
        zeta=None,
        draws=None,
        b_max: int,
        t0: float = 0.0,
        horizon: Optional[float] = None,
        drain: bool = True,
        deadlines=None,
        phases=None,
        phase_mode: str = "oracle",
        beliefs=None,
        slo: Optional[float] = None,
        router_u=None,
        router_seed: int = 0,
        faults=None,
        buffer: Optional[int] = None,
    ):
        self.tables = _norm_tables(tables)
        self.M, self.K, self.L = self.tables.shape
        self.rid = router_id(router)
        self.thr = threshold_gaps(self.tables)
        bel = None
        if phase_mode != "oracle" or beliefs is not None:
            phases, bel = _belief_phases(phase_mode, beliefs, phases, self.K)
        times = np.asarray(arrivals, dtype=np.float64)
        finite = np.isfinite(times)
        times = times[finite]
        order = np.argsort(times, kind="stable")
        self.times = times[order]
        if slo is not None and deadlines is not None:
            raise ValueError("pass slo= or deadlines=, not both")
        if deadlines is not None:
            d = np.asarray(deadlines, dtype=np.float64)[finite][order]
        elif slo is not None:
            d = self.times + slo
        else:
            d = np.full(len(self.times), np.inf)
        self.deadlines = d
        if phases is not None:
            self.phases = np.asarray(phases, dtype=np.int64)[finite][order]
        else:
            self.phases = np.zeros(len(self.times), dtype=np.int64)
        self.bel = None if bel is None else bel[finite][order]
        if self.K > 1 and phases is None:
            raise ValueError("phase-indexed (M, K, L) tables need phases=")
        if horizon is not None:
            keep = self.times < horizon
            self.times, self.deadlines = self.times[keep], self.deadlines[keep]
            self.phases = self.phases[keep]
            if self.bel is not None:
                self.bel = self.bel[keep]
        self.n = len(self.times)
        if router_u is None:
            router_u = np.random.default_rng(router_seed).random((self.n, 2))
        self.router_u = np.asarray(router_u, dtype=np.float64)
        self.means = np.asarray(means, dtype=np.float64)
        zeta_a = (
            np.zeros(b_max + 1)
            if zeta is None
            else np.asarray(zeta, dtype=np.float64).copy()
        )
        zeta_a[0] = 0.0
        self.zeta = zeta_a
        self.draws = (
            np.ones(1) if draws is None else np.asarray(draws, np.float64)
        )
        self.b_max = int(b_max)
        self.drain = bool(drain)
        self.fb, self.fmult, self.max_retries = _prep_faults(faults, self.M)
        if buffer is not None and int(buffer) < 0:
            raise ValueError("buffer must be >= 0")
        self.buf_cap = _NO_BUFFER if buffer is None else int(buffer)
        # --- mutable run state -----------------------------------------
        self.t = float(t0)
        self.i = 0  # arrival cursor
        self.rr = 0
        self.ph = 0
        self.busy = [float("inf")] * self.M
        self.queues: List[List[int]] = [[] for _ in range(self.M)]
        self.needs = [True] * self.M  # initial decision round, like t0 wait
        self.nbat = [0] * self.M
        self.n_srv = [0] * self.M
        self.neps = 0
        self.done = False
        # degraded-mode state: boundary cursor (odd parity = DOWN),
        # consecutive-crash counter, the crashed in-flight batch
        self.fcur = [0] * self.M
        self.rty = [0] * self.M
        self.infl_req: List[List[int]] = [[] for _ in range(self.M)]
        self.ndrop = [0] * self.M
        self.nshed = [0] * self.M
        # --- outputs ---------------------------------------------------
        self.decisions: List[tuple] = []  # (replica, action) incl. waits
        self.latencies = np.full(self.n, np.nan)
        self.served = np.zeros(self.n, dtype=bool)
        self.dropped = np.zeros(self.n, dtype=bool)
        self.shed = np.zeros(self.n, dtype=bool)
        self.arr_server = np.full(self.n, -1, dtype=np.int64)
        self.energy = 0.0
        self.slo_miss = 0
        self.n_crashes = 0

    # --- fault helpers ---------------------------------------------------
    def _down(self, m: int) -> bool:
        return self.fcur[m] % 2 == 1

    def _next_bound(self, m: int) -> float:
        if self.fcur[m] >= self.fb.shape[1]:
            return float("inf")
        return float(self.fb[m, self.fcur[m]])

    # --- router ---------------------------------------------------------
    def _route(self, i: int) -> int:
        qeff = [
            len(self.queues[m]) + len(self.infl_req[m])
            for m in range(self.M)
        ]
        base = [
            _jsq_score(
                qeff[m],
                np.isfinite(self.busy[m]) or bool(self.infl_req[m]),
            )
            for m in range(self.M)
        ]
        pen = [
            _DOWN_PENALTY if self._down(m) else 0 for m in range(self.M)
        ]
        if self.rid == 0:
            # rr scans forward from its slot for the first UP replica;
            # with every replica down it falls back to its own slot
            for k in range(self.M):
                c = (self.rr + k) % self.M
                if not self._down(c):
                    return c
            return self.rr % self.M
        if self.rid == 1:
            return int(np.argmin([base[m] + pen[m] for m in range(self.M)]))
        if self.rid == 2:
            u = self.router_u[i]
            c1 = min(int(u[0] * self.M), self.M - 1)
            c2 = min(int(u[1] * self.M), self.M - 1)
            return c1 if base[c1] + pen[c1] <= base[c2] + pen[c2] else c2
        ph_arr = int(self.phases[i])
        score = []
        for m in range(self.M):
            q = qeff[m]
            gap = int(self.thr[m, ph_arr, min(q, self.L - 1)])
            if np.isfinite(self.busy[m]) or self.infl_req[m]:
                gap += min(q, _SCORE_QCAP)  # mid-batch: backlog penalty
            score.append(
                min(gap, _SCORE_QCAP) * _GAP_SHIFT + base[m] + pen[m]
            )
        return int(np.argmin(score))

    # --- snapshot / restore (router state round-trips exactly) ----------
    def snapshot(self) -> dict:
        return {
            "t": self.t, "i": self.i, "rr": self.rr, "ph": self.ph,
            "busy": list(self.busy),
            "queues": [list(q) for q in self.queues],
            "needs": list(self.needs), "nbat": list(self.nbat),
            "n_srv": list(self.n_srv), "neps": self.neps,
            "done": self.done, "decisions": list(self.decisions),
            "latencies": self.latencies.copy(),
            "served": self.served.copy(),
            "dropped": self.dropped.copy(),
            "shed": self.shed.copy(),
            "arr_server": self.arr_server.copy(),
            "energy": self.energy, "slo_miss": self.slo_miss,
            "fcur": list(self.fcur), "rty": list(self.rty),
            "infl_req": [list(q) for q in self.infl_req],
            "ndrop": list(self.ndrop), "nshed": list(self.nshed),
            "n_crashes": self.n_crashes,
        }

    def restore(self, snap: dict) -> None:
        self.t, self.i = snap["t"], snap["i"]
        self.rr, self.ph = snap["rr"], snap["ph"]
        self.busy = list(snap["busy"])
        self.queues = [list(q) for q in snap["queues"]]
        self.needs = list(snap["needs"])
        self.nbat = list(snap["nbat"])
        self.n_srv = list(snap["n_srv"])
        self.neps, self.done = snap["neps"], snap["done"]
        self.decisions = list(snap["decisions"])
        self.latencies = snap["latencies"].copy()
        self.served = snap["served"].copy()
        self.dropped = snap["dropped"].copy()
        self.shed = snap["shed"].copy()
        self.arr_server = snap["arr_server"].copy()
        self.energy, self.slo_miss = snap["energy"], snap["slo_miss"]
        self.fcur = list(snap["fcur"])
        self.rty = list(snap["rty"])
        self.infl_req = [list(q) for q in snap["infl_req"]]
        self.ndrop = list(snap["ndrop"])
        self.nshed = list(snap["nshed"])
        self.n_crashes = snap["n_crashes"]

    # --- the loop --------------------------------------------------------
    def step(self, max_epochs: Optional[int] = None) -> bool:
        """One event; returns False once the run is finished."""
        if self.done or (max_epochs is not None and self.neps >= max_epochs):
            return False
        nxt = self.times[self.i] if self.i < self.n else float("inf")
        live = self.i < self.n
        # (0) replay the lowest-index due fault boundary (before any
        # admission or decision at the same clock: routing masks and the
        # crash bookkeeping always see fresh parity)
        nb = [self._next_bound(m) for m in range(self.M)]
        for m in range(self.M):
            if nb[m] <= self.t:
                is_start = self.fcur[m] % 2 == 0
                if is_start and self.infl_req[m]:
                    # the down-start catches a crashed in-flight batch
                    if self.rty[m] + 1 > self.max_retries:
                        for j in self.infl_req[m]:
                            self.dropped[j] = True
                        self.ndrop[m] += len(self.infl_req[m])
                        self.rty[m] = 0
                    else:  # requeue to the FRONT, keeping positions
                        self.queues[m] = self.infl_req[m] + self.queues[m]
                        self.rty[m] += 1
                    self.infl_req[m] = []
                if is_start:
                    self.needs[m] = False  # silence any pending decision
                elif (
                    self.queues[m]
                    and np.isinf(self.busy[m])
                    and not self.infl_req[m]
                ):
                    self.needs[m] = True  # repair re-arms queued work
                self.fcur[m] += 1
                return True
        # (1) admit one due arrival (shed if the waiting room is full)
        if nxt <= self.t:
            m = self._route(self.i)
            self.arr_server[self.i] = m
            qeff = len(self.queues[m]) + len(self.infl_req[m])
            if qeff >= self.buf_cap:
                self.shed[self.i] = True
                self.nshed[m] += 1
            else:
                self.queues[m].append(self.i)
                if (
                    np.isinf(self.busy[m])
                    and not self._down(m)
                    and not self.infl_req[m]
                ):
                    self.needs[m] = True
            self.ph = int(self.phases[self.i])
            self.rr += 1
            self.i += 1
            return True
        # wake idle parked UP replicas for the tail drain
        if not live and self.drain:
            for m in range(self.M):
                if (
                    np.isinf(self.busy[m])
                    and self.queues[m]
                    and not self._down(m)
                    and not self.infl_req[m]
                ):
                    self.needs[m] = True
        # (2) decision epoch on the lowest-index pending replica
        if any(self.needs):
            m = self.needs.index(True)
            self.needs[m] = False
            q = len(self.queues[m])
            if self.bel is not None:
                # belief-mixture rule: blend the per-phase actions under
                # the last admitted arrival's posterior row
                row = self.bel[min(max(self.i - 1, 0), self.n - 1)]
                a = int(np.round(np.sum(
                    row * self.tables[m, :, min(q, self.L - 1)]
                )))
            else:
                a = int(self.tables[m, self.ph, min(q, self.L - 1)])
            a = max(0, min(a, q, self.b_max))
            if a == 0 and not live and q > 0 and self.drain:
                a = min(q, self.b_max)  # capped tail drain
            self.neps += 1
            if a == 0:
                self.decisions.append((m, 0))
                return True  # wait (or terminal no-op)
            svc = (
                self.means[a]
                * self.draws[min(self.nbat[m], len(self.draws) - 1)]
                * self.fmult[m, min(self.nbat[m], self.fmult.shape[1] - 1)]
            )
            done_t = self.t + svc
            batch, self.queues[m] = self.queues[m][:a], self.queues[m][a:]
            self.nbat[m] += 1
            self.decisions.append((m, a))
            # crash pre-resolution: the batch fails iff the replica's next
            # down interval starts strictly before its completion
            ds = self._next_bound(m)
            if ds < done_t:
                self.infl_req[m] = batch
                self.energy += float(self.zeta[a] * (ds - self.t) / svc)
                self.n_crashes += 1
                return True
            for j in batch:
                self.latencies[j] = done_t - self.times[j]
                self.served[j] = True
                if done_t > self.deadlines[j]:
                    self.slo_miss += 1
            self.busy[m] = done_t
            self.n_srv[m] += a
            self.rty[m] = 0
            self.energy += float(self.zeta[a])
            return True
        # (3) advance the clock: arrival > completion > fault boundary.
        # A boundary only matters to a replica with queued or crashed
        # work (its repair must wake it / resolve the crash); empty idle
        # replicas' boundaries replay lazily when the clock passes them
        t_c = min(self.busy)
        m_c = int(np.argmin(self.busy))
        t_b = min(
            (
                nb[m]
                for m in range(self.M)
                if self.queues[m] or self.infl_req[m]
            ),
            default=float("inf"),
        )
        if live and nxt <= t_c and nxt <= t_b:
            self.t = nxt
            return True
        if np.isfinite(t_c) and t_c <= t_b:
            self.t = t_c
            self.busy[m_c] = float("inf")
            self.needs[m_c] = True
            return True
        if np.isfinite(t_b):
            self.t = t_b  # the boundary itself replays next step
            return True
        self.done = True  # drained: nothing due, pending, or in flight
        return False

    def run(self, max_epochs: Optional[int] = None) -> "PythonFleet":
        while self.step(max_epochs):
            pass
        return self

    @property
    def qlen(self) -> np.ndarray:
        return np.asarray([len(q) for q in self.queues], dtype=np.int64)


def verify_fleet(
    tables,
    trace,
    *,
    router="jsq",
    service: ServiceModel,
    energy_table=None,
    b_max: int,
    n_epochs: Optional[int] = None,
    horizon: Optional[float] = None,
    drain: bool = True,
    slo: Optional[float] = None,
    phases=None,
    phase_mode: str = "oracle",
    beliefs=None,
    faults=None,
    buffer: Optional[int] = None,
    seed: int = 0,
    atol: float = 1e-9,
) -> Dict[str, object]:
    """Decision-for-decision harness: PythonFleet vs the compiled kernel.

    Mirrors `serving.engine.verify_backends`: both backends run the same
    sorted trace, the same shared unit-draw block and the same router
    uniforms, and the full decision log — (replica, action) per epoch,
    waits included — plus per-arrival latencies / routing / drop + shed
    flags / energy / SLO misses must agree.  ``faults`` (a FaultSchedule)
    and ``buffer`` exercise the degraded-mode lanes on both sides;
    ``phase_mode``/``beliefs`` the belief row-selection rules.  With
    M = 1 (and no degraded-mode knobs, which the single-server kernel
    lacks) the fleet lane is additionally checked against
    `simulate_compiled`: identical batch-size sequence, latencies, energy
    and final clock.
    """
    from .compiled import simulate_compiled

    tables = _norm_tables(tables)
    M = tables.shape[0]
    trace = np.sort(np.asarray(trace, dtype=np.float64))
    n = len(trace)
    budget = n_epochs if n_epochs is not None else 2 * n + M + 4
    draws = service.unit_draws(np.random.default_rng(seed), budget)
    means = np.asarray(
        [0.0] + [float(service.mean(b)) for b in range(1, b_max + 1)]
    )
    router_u = np.random.default_rng(seed + 1).random((n, 2))
    kw = dict(
        router=router, means=means, zeta=energy_table, draws=draws,
        b_max=b_max, horizon=horizon, drain=drain, slo=slo, phases=phases,
        phase_mode=phase_mode, beliefs=beliefs, router_u=router_u,
        faults=faults, buffer=buffer,
    )
    py = PythonFleet(tables, trace, **kw).run(max_epochs=n_epochs)
    comp = simulate_fleet(
        tables, trace, max_epochs=n_epochs, record=True, **kw
    )
    dec_py = np.asarray(py.decisions, dtype=np.int64).reshape(-1, 2)
    dec_c = np.stack([comp.servers, comp.actions], axis=1)
    np.testing.assert_array_equal(dec_py, dec_c)
    assert py.neps == comp.n_epochs, (py.neps, comp.n_epochs)
    # the python reference drops post-horizon arrivals; the compiled lane
    # keeps full-length arrays where they are simply never admitted
    n_eff = py.n
    assert not comp.served[n_eff:].any()
    assert (comp.arr_server[n_eff:] == -1).all()
    np.testing.assert_array_equal(py.served, comp.served[:n_eff])
    np.testing.assert_array_equal(py.arr_server, comp.arr_server[:n_eff])
    np.testing.assert_array_equal(py.dropped, comp.dropped[:n_eff])
    np.testing.assert_array_equal(py.shed, comp.shed[:n_eff])
    assert int(py.n_crashes) == comp.n_crashes
    assert int(sum(py.ndrop)) == comp.n_dropped
    assert int(sum(py.nshed)) == comp.n_shed
    np.testing.assert_allclose(
        py.latencies[py.served], comp.latencies[comp.served], atol=atol
    )
    assert int(py.slo_miss) == comp.slo_miss
    np.testing.assert_allclose(py.energy, comp.energy, atol=atol)
    np.testing.assert_allclose(py.t, comp.t_final, atol=atol)
    np.testing.assert_array_equal(py.qlen, comp.qlen)
    out = {
        "python": py, "compiled": comp,
        "n_decisions": int(len(py.decisions)),
    }
    if M == 1 and faults is None and buffer is None:
        single = simulate_compiled(
            tables[0], trace, means=means, zeta=energy_table, draws=draws,
            b_max=b_max, max_epochs=n_epochs, horizon=horizon, drain=drain,
            deadlines=None if slo is None else trace + slo,
            phases=phases, phase_mode=phase_mode, beliefs=beliefs,
            record=True,
        )
        np.testing.assert_array_equal(single.batch_sizes, comp.batch_sizes)
        assert single.n_served == comp.n_served
        np.testing.assert_allclose(
            single.latencies, comp.latencies[comp.served], atol=atol
        )
        np.testing.assert_allclose(single.energy, comp.energy, atol=atol)
        assert single.slo_miss == comp.slo_miss
        np.testing.assert_allclose(single.t_final, comp.t_final, atol=atol)
        assert single.n_epochs == comp.n_epochs, (
            single.n_epochs, comp.n_epochs,
        )
        out["single"] = single
    return out


# ---------------------------------------------------------------------------
# Chunked streaming: O(chunk) memory at any horizon
# ---------------------------------------------------------------------------


class FleetStream:
    """Chunked fleet simulation folding into O(1)-memory aggregates.

    Feed the (globally time-sorted) arrival stream through `push` in
    chunks; per-replica leftover queues, busy clocks, router and phase
    state carry across chunk boundaries, and each chunk's latencies / SLO
    misses / energy fold into `ServingMetrics`-style streaming aggregates
    (P² quantile estimators + the fixed-bin histogram sketch).  `finish`
    runs the b_max-capped tail drain and returns a `FleetResult` whose
    aggregates match a one-shot `simulate_fleet` of the concatenated
    stream exactly (decision-for-decision, `n_epochs` included —
    completions that outrun a chunk's last arrival are deferred to the
    next chunk, latencies are accounted at serve start, and the pending
    decision flags carry across chunks so parked replicas are not
    re-decided at chunk seams).

    Memory is O(chunk + carried queues); a billion-event horizon streams
    through a fixed-size window instead of materializing per-request
    buffers (`simulate_fleet(record=True)`'s regime).
    """

    def __init__(
        self,
        tables,
        *,
        router="jsq",
        means,
        zeta=None,
        draws=None,
        b_max: int,
        drain: bool = True,
        slo: Optional[float] = None,
        hist_edges=None,
        quantiles: Sequence[float] = (0.5, 0.95, 0.99),
        router_seed: int = 0,
        t0: float = 0.0,
        phase_mode: str = "oracle",
        belief_filter=None,
        faults=None,
        buffer: Optional[int] = None,
    ):
        self.tables = _norm_tables(tables)
        self.M, self.K, self.L = self.tables.shape
        self.rid = router_id(router)
        self.thr = threshold_gaps(self.tables)
        self.means = np.asarray(means, dtype=np.float64)
        zeta_a = (
            np.zeros(b_max + 1)
            if zeta is None
            else np.asarray(zeta, dtype=np.float64).copy()
        )
        zeta_a[0] = 0.0
        self.zeta = zeta_a
        self.draws = (
            np.ones(1) if draws is None else np.asarray(draws, np.float64)
        )
        self.b_max = int(b_max)
        self.drain = bool(drain)
        self.slo = slo
        self.edges = (
            default_hist_edges(self.means)
            if hist_edges is None
            else np.asarray(hist_edges, dtype=np.float64)
        )
        self._rng = np.random.default_rng(router_seed)
        # belief phase modes run the forward filter per chunk, carrying
        # the posterior across chunk boundaries (aggregates == one-shot)
        if phase_mode not in ("oracle", "belief_argmax", "belief_mix"):
            raise ValueError(f"unknown phase_mode {phase_mode!r}")
        if (phase_mode != "oracle") != (belief_filter is not None):
            raise ValueError(
                'belief phase modes need belief_filter= (an '
                'arrivals.PhaseBeliefFilter) and vice versa'
            )
        if belief_filter is not None and len(belief_filter.rates) != self.K:
            raise ValueError(
                f"belief filter K={len(belief_filter.rates)} != table "
                f"phase axis K={self.K}"
            )
        self.phase_mode = phase_mode
        self._filt = belief_filter
        self._bel0 = (
            None
            if belief_filter is None
            else np.asarray(belief_filter.belief, dtype=np.float64).copy()
        )
        self.fb, self.fmult, self.max_retries = _prep_faults(faults, self.M)
        if buffer is not None and int(buffer) < 0:
            raise ValueError("buffer must be >= 0")
        self.buf_cap = _NO_BUFFER if buffer is None else int(buffer)
        # --- carried state --------------------------------------------
        self.t0 = float(t0)
        self.t = float(t0)
        self.rr = 0
        self.ph = 0
        self.busy = np.full(self.M, np.inf)
        self.nbat = np.zeros(self.M, dtype=np.int64)
        self.queues = [
            (np.zeros(0), np.zeros(0)) for _ in range(self.M)
        ]  # (times, deadlines) per replica, admission order
        # degraded-mode carry: the first infl[m] entries of queues[m] are
        # the crashed in-flight batch (front-requeue keeps them there)
        self.fcur = np.zeros(self.M, dtype=np.int64)
        self.rty = np.zeros(self.M, dtype=np.int64)
        self.infl = np.zeros(self.M, dtype=np.int64)
        # pending-decision flags carry exactly: a parked wait is not
        # re-decided at the chunk seam (phase-indexed tables would
        # otherwise re-read a newer fleet phase than the one-shot run)
        self.needs = np.ones(self.M, dtype=bool)
        self._t_hwm = -np.inf  # high-water mark: chunks must be sorted
        self._finished = False
        # --- streaming aggregates -------------------------------------
        self.quantiles = {q: P2Quantile(q) for q in quantiles}
        self.hist = np.zeros(len(self.edges) + 1, dtype=np.int64)
        self.n_admitted = 0
        self.n_served = 0
        self.n_batches = 0
        self.n_epochs = 0
        self.energy = 0.0
        self.lat_sum = 0.0
        self.slo_miss = 0
        self.n_crashes = 0
        self.n_dropped = 0
        self.n_shed = 0
        self.n_routed = np.zeros(self.M, dtype=np.int64)
        self.n_served_m = np.zeros(self.M, dtype=np.int64)

    def push(self, times, deadlines=None, *, phases=None, router_u=None):
        """Simulate one chunk of arrivals (must not precede earlier ones)."""
        if self._finished:
            raise RuntimeError("push() after finish()")
        times = np.asarray(times, dtype=np.float64)
        if len(times) == 0:
            return self
        if times.min() < self._t_hwm:
            raise ValueError(
                "chunks must be globally time-sorted: arrival "
                f"{times.min():g} precedes an earlier chunk's last arrival "
                f"{self._t_hwm:g}"
            )
        self._t_hwm = float(times.max())
        self._run_chunk(
            times, deadlines, phases, router_u, more_coming=True,
            t_last=self._t_hwm,
        )
        return self

    def finish(self) -> FleetResult:
        """Drain the carried queues (b_max-capped) and return the totals."""
        if not self._finished:
            self._run_chunk(
                np.zeros(0), None, None, None, more_coming=False,
                t_last=np.inf,
            )
            self._finished = True
        return self.result()

    def result(self) -> FleetResult:
        res = FleetResult(
            t_final=self.t,
            n_served=self.n_served,
            n_batches=self.n_batches,
            n_epochs=self.n_epochs,
            n_admitted=self.n_admitted,
            energy=self.energy,
            lat_sum=self.lat_sum,
            slo_miss=self.slo_miss,
            terminated=self._finished,
            hist=self.hist.copy(),
            hist_edges=self.edges,
            n_crashes=self.n_crashes,
            n_dropped=self.n_dropped,
            n_shed=self.n_shed,
            # queues carry the crashed in-flight batch at the front; the
            # kernel's qlen convention counts only the waiting part
            qlen=np.asarray(
                [len(q[0]) for q in self.queues], np.int64
            ) - self.infl,
            busy=self.busy.copy(),
            n_routed=self.n_routed.copy(),
            n_served_m=self.n_served_m.copy(),
        )
        return res

    def report(self) -> Dict[str, float]:
        """ServingMetrics-style summary (NaN-with-count-zero on empties)."""
        span = self.t - self.t0
        out = {
            "W_mean": (
                self.lat_sum / self.n_served
                if self.n_served
                else float("nan")
            ),
            "power": (
                self.energy / span
                if self.n_batches and span > 0
                else float("nan")
            ),
            "mean_batch": (
                self.n_served / self.n_batches
                if self.n_batches
                else float("nan")
            ),
            "n_served": float(self.n_served),
            "slo_miss": float(self.slo_miss),
            # degraded-mode counters: goodput is the served-through rate
            # (NaN on an empty span, like the other rate metrics)
            "goodput": (
                self.n_served / span if span > 0 else float("nan")
            ),
            "drop_rate": (
                (self.n_dropped + self.n_shed) / self.n_admitted
                if self.n_admitted
                else float("nan")
            ),
            "n_dropped": float(self.n_dropped),
            "n_shed": float(self.n_shed),
            "n_crashes": float(self.n_crashes),
        }
        for q, est in self.quantiles.items():
            out[f"P{round(q * 100)}"] = est.value
        return out

    #: phase_mode <-> checkpoint integer code
    _PHASE_MODES = ("oracle", "belief_argmax", "belief_mix")

    def save(self, path) -> None:
        """Persist the stream durably: config, chunk-seam carry, aggregates.

        Written through checkpoint.CheckpointManager (atomic rename +
        per-array CRC) with an incrementing step per save, so a crash
        mid-save can never shadow the previous good snapshot.  The payload
        is the *complete* seam state — per-replica queues, busy clocks,
        pending-decision flags, fault cursors, P² marker sketches, the
        histogram, the belief posterior and the router RNG state — so a
        killed-and-resumed stream matches the uninterrupted one on every
        aggregate, n_epochs included (see resume()).
        """
        from repro.checkpoint import CheckpointManager

        cfg = {
            "version": np.int64(1),
            "tables": self.tables,
            "means": self.means,
            "zeta": self.zeta,
            "draws": self.draws,
            "edges": self.edges,
            "b_max": np.int64(self.b_max),
            "drain": np.bool_(self.drain),
            "slo": np.float64(np.nan if self.slo is None else self.slo),
            "rid": np.int64(self.rid),
            "fb": self.fb,
            "fmult": self.fmult,
            "max_retries": np.int64(self.max_retries),
            "buf_cap": np.int64(self.buf_cap),
            "t0": np.float64(self.t0),
            "phase_mode": np.int64(self._PHASE_MODES.index(self.phase_mode)),
            "qprobs": np.asarray(list(self.quantiles), dtype=np.float64),
            # PCG64 state holds 128-bit ints — json round-trips them exactly
            "rng": np.frombuffer(
                json.dumps(self._rng.bit_generator.state).encode(), np.uint8
            ),
        }
        carry = {
            "t": np.float64(self.t),
            "rr": np.int64(self.rr),
            "ph": np.int64(self.ph),
            "busy": self.busy,
            "nbat": self.nbat,
            "needs": self.needs,
            "fcur": self.fcur,
            "rty": self.rty,
            "infl": self.infl,
            "t_hwm": np.float64(self._t_hwm),
            "finished": np.bool_(self._finished),
            "q_lens": np.asarray(
                [len(q[0]) for q in self.queues], dtype=np.int64
            ),
            "q_times": np.concatenate([q[0] for q in self.queues]),
            "q_deads": np.concatenate([q[1] for q in self.queues]),
        }
        agg = {
            "hist": self.hist,
            "n_admitted": np.int64(self.n_admitted),
            "n_served": np.int64(self.n_served),
            "n_batches": np.int64(self.n_batches),
            "n_epochs": np.int64(self.n_epochs),
            "energy": np.float64(self.energy),
            "lat_sum": np.float64(self.lat_sum),
            "slo_miss": np.int64(self.slo_miss),
            "n_crashes": np.int64(self.n_crashes),
            "n_dropped": np.int64(self.n_dropped),
            "n_shed": np.int64(self.n_shed),
            "n_routed": self.n_routed,
            "n_served_m": self.n_served_m,
        }
        tree = {
            "cfg": cfg,
            "carry": carry,
            "agg": agg,
            "p2": {
                str(k): est.snapshot()
                for k, est in enumerate(self.quantiles.values())
            },
        }
        if self.phase_mode != "oracle":
            tree["bel"] = {
                "rates": self._filt.rates,
                "gen": self._filt.gen,
                "b0": self._filt._b0,
                "belief": self._filt.belief,
                "last": np.float64(self._filt._last),
                "n_observed": np.int64(self._filt.n_observed),
                "bel0": self._bel0,
            }
        mgr = CheckpointManager(path, keep_last_k=2)
        last = mgr.latest_step()
        mgr.save(0 if last is None else last + 1, tree)

    @classmethod
    def resume(cls, path) -> "FleetStream":
        """Reconstruct a saved stream; the seam contract survives the trip.

        Every aggregate of resume(path) -> push...(rest) -> finish() equals
        the uninterrupted stream's: queues, clocks, decision flags, fault
        cursors, sketches, posterior and RNG all restore exactly, so the
        continuation replays decision-for-decision.
        """
        from repro.checkpoint import CheckpointManager

        flat = CheckpointManager(path).restore_flat()
        pm = cls._PHASE_MODES[int(flat["cfg//phase_mode"])]
        filt = None
        if pm != "oracle":
            from .arrivals import PhaseBeliefFilter

            filt = PhaseBeliefFilter(
                flat["bel//rates"], flat["bel//gen"], b0=flat["bel//b0"]
            )
            filt.restore(
                {
                    "belief": flat["bel//belief"],
                    "last": float(flat["bel//last"]),
                    "n_observed": int(flat["bel//n_observed"]),
                }
            )
        slo = float(flat["cfg//slo"])
        self = cls(
            flat["cfg//tables"],
            means=flat["cfg//means"],
            zeta=flat["cfg//zeta"],
            draws=flat["cfg//draws"],
            b_max=int(flat["cfg//b_max"]),
            drain=bool(flat["cfg//drain"]),
            slo=None if np.isnan(slo) else slo,
            hist_edges=flat["cfg//edges"],
            quantiles=tuple(float(q) for q in flat["cfg//qprobs"]),
            t0=float(flat["cfg//t0"]),
            phase_mode=pm,
            belief_filter=filt,
        )
        # fields the constructor derives from args we did not persist in
        # their original form (router name, faults spec, buffer flag)
        self.rid = int(flat["cfg//rid"])
        self.fb = flat["cfg//fb"]
        self.fmult = flat["cfg//fmult"]
        self.max_retries = int(flat["cfg//max_retries"])
        self.buf_cap = int(flat["cfg//buf_cap"])
        self._rng = np.random.default_rng(0)
        self._rng.bit_generator.state = json.loads(
            bytes(bytearray(flat["cfg//rng"])).decode()
        )
        if pm != "oracle":
            self._bel0 = np.asarray(flat["bel//bel0"], dtype=np.float64)
        # --- carried seam state ---------------------------------------
        self.t = float(flat["carry//t"])
        self.rr = int(flat["carry//rr"])
        self.ph = int(flat["carry//ph"])
        self.busy = np.asarray(flat["carry//busy"], dtype=np.float64)
        self.nbat = np.asarray(flat["carry//nbat"], dtype=np.int64)
        self.needs = np.asarray(flat["carry//needs"], dtype=bool)
        self.fcur = np.asarray(flat["carry//fcur"], dtype=np.int64)
        self.rty = np.asarray(flat["carry//rty"], dtype=np.int64)
        self.infl = np.asarray(flat["carry//infl"], dtype=np.int64)
        self._t_hwm = float(flat["carry//t_hwm"])
        self._finished = bool(flat["carry//finished"])
        lens = flat["carry//q_lens"]
        qt, qd = flat["carry//q_times"], flat["carry//q_deads"]
        queues, off = [], 0
        for m in range(self.M):
            ln = int(lens[m])
            queues.append((qt[off : off + ln].copy(), qd[off : off + ln].copy()))
            off += ln
        self.queues = queues
        # --- streaming aggregates -------------------------------------
        self.hist = np.asarray(flat["agg//hist"], dtype=np.int64)
        self.n_admitted = int(flat["agg//n_admitted"])
        self.n_served = int(flat["agg//n_served"])
        self.n_batches = int(flat["agg//n_batches"])
        self.n_epochs = int(flat["agg//n_epochs"])
        self.energy = float(flat["agg//energy"])
        self.lat_sum = float(flat["agg//lat_sum"])
        self.slo_miss = int(flat["agg//slo_miss"])
        self.n_crashes = int(flat["agg//n_crashes"])
        self.n_dropped = int(flat["agg//n_dropped"])
        self.n_shed = int(flat["agg//n_shed"])
        self.n_routed = np.asarray(flat["agg//n_routed"], dtype=np.int64)
        self.n_served_m = np.asarray(flat["agg//n_served_m"], dtype=np.int64)
        for k, est in enumerate(self.quantiles.values()):
            est.restore(
                {
                    f: flat[f"p2//{k}//{f}"]
                    for f in ("q", "init", "n", "ns", "heights")
                }
            )
        return self

    def _run_chunk(self, times, deadlines, phases, router_u, *,
                   more_coming, t_last):
        order = np.argsort(times, kind="stable")
        times = times[order]
        if deadlines is not None:
            deadlines = np.asarray(deadlines, np.float64)[order]
        elif self.slo is not None:
            deadlines = times + self.slo
        bel = None
        if self.phase_mode != "oracle":
            if phases is not None:
                raise ValueError(
                    "belief phase modes derive phases from the filter; "
                    "don't pass phases= per chunk"
                )
            # forward-filter this chunk from the carried posterior, then
            # advance the filter state so the next chunk resumes exactly
            if len(times):
                rows, (b_f, t_f) = belief_forward_jax(times, self._filt)
                rows = np.asarray(rows)
                phases = np.argmax(rows, axis=-1).astype(np.int64)
                if self.phase_mode == "belief_mix":
                    bel = rows
                self._filt.belief = np.asarray(b_f, dtype=np.float64)
                self._filt._last = float(t_f)
                self._filt.n_observed += len(times)
            else:
                phases = np.zeros(0, dtype=np.int64)
        elif phases is not None:
            phases = np.asarray(phases, np.int64)[order]
        elif self.K > 1 and len(times):
            # the finish() drain pushes zero arrivals and needs no phases
            raise ValueError("phase-indexed tables need phases= per chunk")
        n = len(times)
        padded = pad_arrivals(times, deadlines, phases=phases)
        if phases is None:
            arr, dl = padded
            ph_arr = np.zeros(len(arr), dtype=np.int64)
        else:
            arr, dl, ph_arr = padded
        mix = self.phase_mode == "belief_mix"
        if mix:
            bel_p = np.zeros((len(arr), self.K))
            if bel is not None:
                bel_p[:n] = bel
            bel_j = jnp.asarray(bel_p)
            bel0_j = jnp.asarray(self._bel0)
        else:
            bel_j = jnp.zeros((1, 1))
            bel0_j = bel_j[0]
        if router_u is None:
            router_u = self._rng.random((len(arr), 2))
        else:
            ru = np.full((len(arr), 2), 0.5)
            ru[:len(router_u)] = np.asarray(router_u, np.float64)[order]
            router_u = ru
        # carried queues -> (M, Q0) +inf-padded arrays
        c0 = max([len(q[0]) for q in self.queues] + [1])
        Q0 = _bucket(c0, floor=16)
        q0_t = np.full((self.M, Q0), np.inf)
        q0_d = np.full((self.M, Q0), np.inf)
        for m, (qt, qd) in enumerate(self.queues):
            q0_t[m, : len(qt)] = qt
            q0_d[m, : len(qd)] = qd
        q0_total = int(sum(len(q[0]) for q in self.queues))
        # boundaries not yet replayed can each cost a step (and a crash
        # re-decision): budget them alongside arrivals and epochs
        n_bnd = int(np.isfinite(self.fb).sum() - self.fcur.sum())
        n_bnd = max(n_bnd, 0)
        max_eps = 2 * (n + q0_total) + 2 * self.M + 8 + 2 * n_bnd
        cap = _bucket(2 * (n + max_eps + n_bnd) + 2 * self.M + 8)
        n_steps = min(
            _bucket(max(256, 2 * n + 2 * q0_total + 2 * self.M + 8)), cap
        )
        while True:
            out = _fleet_jit(
                jnp.asarray(self.tables), jnp.asarray(self.thr),
                jnp.asarray(arr), jnp.asarray(dl), jnp.asarray(ph_arr),
                bel_j, bel0_j,
                jnp.asarray(router_u), jnp.asarray(q0_t), jnp.asarray(q0_d),
                jnp.asarray(self.draws), jnp.asarray(self.means),
                jnp.asarray(self.zeta), jnp.asarray(self.edges),
                jnp.asarray(self.fb), jnp.asarray(self.fmult),
                int(self.rid), float(self.t), np.inf, max_eps,
                self.drain, self.b_max,
                int(self.buf_cap), int(self.max_retries),
                int(self.rr), int(self.ph), jnp.asarray(self.busy),
                jnp.asarray(self.nbat), jnp.asarray(self.needs),
                jnp.asarray(self.fcur),
                jnp.asarray(self.rty), jnp.asarray(self.infl),
                bool(more_coming), float(t_last),
                int(n_steps), True, mix,
            )
            agg, rec = out
            if n_steps >= cap or not bool(agg["incomplete"]):
                break
            n_steps = min(2 * n_steps, cap)
        agg = {k: np.asarray(v) for k, v in agg.items()}
        (_, _, arr_lat, arr_served, arr_dropped, arr_shed, arr_server,
         arr_pos, q0_lat, q0_served, q0_dropped) = (
            np.asarray(x) for x in rec
        )
        if int(agg["n_admitted"]) != n:
            raise RuntimeError(
                f"chunk admitted {int(agg['n_admitted'])}/{n} arrivals "
                "(epoch budget bound mid-chunk; this is a bug)"
            )
        if mix and n:
            self._bel0 = np.asarray(self._filt.belief, dtype=np.float64)
        # --- fold aggregates ------------------------------------------
        self.n_admitted += n
        self.n_served += int(agg["n_served"])
        self.n_batches += int(agg["n_batches"])
        self.n_epochs += int(agg["n_epochs"])
        self.energy += float(agg["energy"])
        self.lat_sum += float(agg["lat_sum"])
        self.slo_miss += int(agg["slo_miss"])
        self.n_crashes += int(agg["n_crashes"])
        self.n_dropped += int(agg["n_dropped"])
        self.n_shed += int(agg["n_shed"])
        self.hist += agg["hist"]
        # P2 updates in a fixed order: carried queues (replica-major,
        # position order), then this chunk's arrivals in time order
        for m in range(self.M):
            for lat in q0_lat[m][q0_served[m]]:
                for est in self.quantiles.values():
                    est.update(float(lat))
        for lat in arr_lat[arr_served]:
            for est in self.quantiles.values():
                est.update(float(lat))
        # --- carry state ----------------------------------------------
        n_srv_m = agg["n_srv"]
        new_queues = []
        for m in range(self.M):
            qt, qd = self.queues[m]
            keep = ~(q0_served[m] | q0_dropped[m])[: len(qt)]
            # shed arrivals record their would-be replica but never queue
            mask = (
                (arr_server[:len(arr)] == m)
                & ~arr_served & ~arr_dropped & ~arr_shed
            )
            new_queues.append((
                np.concatenate([qt[keep], arr[mask]]),
                np.concatenate([qd[keep], dl[mask]]),
            ))
        self.queues = new_queues
        # a crashed in-flight batch stays in the carried queue (front,
        # unresolved positions) but outside the kernel's qlen count
        assert int(sum(len(q[0]) for q in self.queues)) == int(
            agg["qlen"].sum() + agg["infl"].sum()
        )
        self.t = float(agg["t_final"])
        self.busy = agg["busy"].copy()
        self.rr = int(agg["rr"])
        self.ph = int(agg["ph"])
        self.nbat = agg["nbat"].copy()
        self.needs = agg["needs"].copy()
        self.fcur = agg["fcur"].copy()
        self.rty = agg["rty"].copy()
        self.infl = agg["infl"].copy()
        # the kernel's n_route carry starts at the carried-queue count
        # (substream positions offset past q0) — only the excess is new
        self.n_routed += agg["n_route"] - np.sum(
            np.isfinite(q0_t), axis=1
        ).astype(np.int64)
        self.n_served_m += n_srv_m


def simulate_fleet_stream(
    tables,
    arrivals,
    *,
    chunk_size: int = 65536,
    deadlines=None,
    phases=None,
    router_u=None,
    **kwargs,
) -> FleetResult:
    """Stream a long arrival array through `FleetStream` in fixed chunks.

    ``arrivals`` may be one sorted array (sliced into ``chunk_size``
    windows) or an iterable of chunk arrays.  Accepts `FleetStream`'s
    keyword arguments; per-arrival ``deadlines`` / ``phases`` /
    ``router_u`` are sliced alongside when given as arrays.
    """
    fs = FleetStream(tables, **kwargs)
    if isinstance(arrivals, np.ndarray) or (
        isinstance(arrivals, (list, tuple))
        and arrivals
        and np.isscalar(arrivals[0])
    ):
        arrivals = np.asarray(arrivals, dtype=np.float64)
        n = len(arrivals)
        for lo in range(0, n, chunk_size):
            hi = min(lo + chunk_size, n)
            fs.push(
                arrivals[lo:hi],
                None if deadlines is None else deadlines[lo:hi],
                phases=None if phases is None else phases[lo:hi],
                router_u=None if router_u is None else router_u[lo:hi],
            )
    else:
        for chunk in arrivals:
            fs.push(np.asarray(chunk, dtype=np.float64))
    return fs.finish()


# ---------------------------------------------------------------------------
# The vmapped (seeds x scenarios) x policies x routers grid, mesh-shardable
# ---------------------------------------------------------------------------


#: steps per iteration of the grid's event loop (at most; it divides the
#: step count): the loop tests for active instances once per chunk
_GRID_CHUNK = 256


def _fleet_grid_core(tables, thrs, rids, arr, dl, ph, bel, ru, draws,
                     means, zeta, edges, t0, horizon, max_eps, drain, b_max,
                     *faults, n_steps: int, mix: bool):
    """(S, P, R) fleet grid: vmap lanes x table-stacks x router ids.

    Returns the aggregates and, as a (1,) array, the steps the event loop
    ran.  The loop runs outside the vmap, a chunk of steps at a time, and
    stops at the first chunk boundary where no instance is active (an
    instance never becomes active again) or at ``n_steps``; the rows it
    never reaches hold what an inactive step emits.  So the aggregates are
    bitwise those of a fixed ``n_steps``-step scan per instance
    (`_fleet_jit`).

    ``faults`` is empty for a fault-free grid, whose kernels get the
    constants all-+inf boundaries, unit multipliers and ``max_retries``
    0; or ``(fb, fmult, max_retries)``: the lanes' (S, M, 2F) boundaries
    and (S, M, D) multipliers (`faults.stack_schedules`), vmapped with
    the other lane arrays, and the retry bound every lane shares.
    """
    M = tables.shape[1]
    q0 = jnp.full((M, 1), jnp.inf)
    busy0 = jnp.full(M, jnp.inf)
    nbat0 = jnp.zeros(M, dtype=jnp.int64)
    zm = jnp.zeros(M, dtype=jnp.int64)
    if faults:
        fb, fmult, max_retries = faults
        lane_faults = (fb, fmult)
    else:
        lane_faults, max_retries = (), 0
        fb0 = jnp.full((M, 1), jnp.inf)
        fmult0 = jnp.ones((M, 1))
    nf = len(lane_faults)

    def over_grid(f, in_axes=(), out_axes=0):
        """``f(kernel, *xs)`` on one (lane, table, router) instance,
        vmapped over the grid; each of ``xs`` carries the grid's axes at
        its entry of ``in_axes``, the results at ``out_axes``."""
        def lane(a_, d_, p_, b_, u_, dr_, *xs):
            fb_, fm_ = xs[:nf] if nf else (fb0, fmult0)
            xs = xs[nf:]

            def per_table(tab, thr, *xs):
                def per_router(rid, *xs):
                    kernel = _fleet_kernel(
                        tab, thr, a_, d_, p_, b_, b_[0], u_, q0, q0, dr_,
                        means, zeta, edges, fb_, fm_,
                        rid, t0, horizon, max_eps, drain, b_max,
                        _NO_BUFFER, max_retries,
                        0, 0, busy0, nbat0, jnp.ones(M, dtype=bool),
                        zm, zm, zm, False, jnp.inf, mix=mix,
                    )
                    return f(kernel, *xs)
                return jax.vmap(per_router, (0, *in_axes), out_axes)(
                    rids, *xs
                )
            return jax.vmap(per_table, (0, 0, *in_axes), out_axes)(
                tables, thrs, *xs
            )
        lane_axes = (0,) * (6 + nf) + tuple(in_axes)
        return lambda *xs: jax.vmap(lane, lane_axes, out_axes)(
            arr, dl, ph, bel, ru, draws, *lane_faults, *xs
        )

    def start(kernel):
        carry0, step, _ = kernel
        # what an inactive step emits: the same step on a finished carry
        finished = carry0[:6] + (jnp.asarray(True),) + carry0[7:]
        _, idle_out = step(finished, None)
        return carry0, idle_out

    chunk = math.gcd(n_steps, _GRID_CHUNK)

    def run_chunk(kernel, carry):
        return jax.lax.scan(kernel[1], carry, None, length=chunk)

    carry0, idle_out = over_grid(start)()
    bufs0 = jax.tree.map(
        lambda o: jnp.broadcast_to(o, (n_steps, *o.shape)), idle_out
    )
    run = over_grid(run_chunk, (0,), (0, 1))  # outs (chunk, S, P, R)

    def any_active(state):
        i, carry, _ = state
        neps, done = carry[4], carry[6]
        return (i < n_steps) & jnp.any(~done & (neps < max_eps))

    def body(state):
        i, carry, bufs = state
        carry, outs = run(carry)
        bufs = jax.tree.map(
            lambda b, o: jax.lax.dynamic_update_slice_in_dim(b, o, i, 0),
            bufs, outs,
        )
        return i + chunk, carry, bufs

    i, carry, bufs = jax.lax.while_loop(
        any_active, body, (jnp.asarray(0, dtype=jnp.int32), carry0, bufs0)
    )
    agg = over_grid(
        lambda kernel, c, o: kernel[2](c, o, record=False), (0, 1)
    )(carry, bufs)
    return agg, i[None]


#: jitted grid dispatchers keyed by (mesh identity, n_steps) — the
#: escalation ladder revisits sizes, and partial() would bust jit's cache
_FLEET_GRID_CACHE: dict = {}


def _fleet_grid_fn(mesh, n_steps: int, mix: bool, faults: bool = False):
    key = (None if mesh is None else id(mesh), n_steps, mix, faults)
    fn = _FLEET_GRID_CACHE.get(key)
    if fn is not None:
        return fn
    core = partial(_fleet_grid_core, n_steps=n_steps, mix=mix)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from repro.distributed.meshcompat import shard_map

        axis = mesh.axis_names[0]
        rep = P()
        core = shard_map(
            core, mesh=mesh,
            # lanes (S-leading arrays) shard over the mesh's first axis;
            # tables / router ids / service constants replicate; a fault
            # schedule's boundaries and multipliers are lane arrays, its
            # retry bound a constant
            in_specs=(rep, rep, rep, P(axis), P(axis), P(axis), P(axis),
                      P(axis), P(axis), rep, rep, rep, rep, rep, rep, rep,
                      rep) + ((P(axis), P(axis), rep) if faults else ()),
            out_specs=P(axis),
        )
    fn = jax.jit(core)
    _FLEET_GRID_CACHE[key] = fn
    return fn


def run_fleet_grid(
    tables,
    arrivals,
    *,
    routers: Sequence = ("jsq",),
    n_replicas: Optional[int] = None,
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
    router_seed: int = 0,
    mesh=None,
    faults=None,
):
    """The fleet sweep: (seeds x scenarios) traces x policies x routers.

    ``tables`` — (P, M, L) per-policy per-replica action tables (or
    (P, M, K, L) phase-indexed stacks with ``phases`` = (S, N) ints,
    or ``phase_mode="belief_argmax"`` + ``beliefs`` = (S, N, K)
    posterior rows, lowered to the same phase stream); a
    (P, L) array plus ``n_replicas=M`` runs each policy homogeneously on
    M replicas.  ``arrivals`` — (S, N) padded sorted traces
    (`pad_arrivals` / `pad_arrivals_batch`); ``draws`` — (S, D) unit
    service draws per lane.  ``routers`` — router names (or kernel ids);
    the router axis is vmapped, not re-dispatched.

    Returns a dict of (S, P, R) aggregate arrays — plus (S, P, R, M)
    per-replica queue/served/routed counts for conservation checks — and
    the derived ``w_mean`` (NaN on starved lanes), ``power``, and
    ``q_time_avg`` (time-averaged total backlog, ``lat_sum / span`` by
    Little's law — the JSQ-vs-pow2 dominance statistic).
    ``steps_executed`` is the number of event steps the kernel ran: it
    stops once every lane, policy and router is done, short of the
    step-count bucket it was compiled for.

    ``faults`` — None (fault-free), one `serving.faults.FaultSchedule`
    for every lane, or a sequence of S schedules, one per lane, each over
    the fleet's M replicas and all with one ``max_retries``.  Every
    instance of lane s then runs ``simulate_fleet(faults=schedule_s)``'s
    degraded-mode semantics, bitwise (given the same draws and router
    uniforms), and ``n_crashes`` / ``n_dropped`` count its crashed
    attempts and dropped requests.  The schedules are stacked into lane
    arrays (`faults.stack_schedules`); the fault-free grid is a program
    of its own that compiles the constants in.

    ``mesh=`` shards the S axis across the mesh's *first* axis via
    `shard_map` (through distributed.meshcompat — `launch.mesh.
    make_sim_mesh()` builds the 1-D all-devices mesh); S is padded to a
    device multiple by repeating the first lane and trimmed on return.
    """
    with TraceAnnotation(
        "repro.fleet.prepare", lanes=len(arrivals)
    ) as prepare:
        tables = np.asarray(tables, dtype=np.int64)
        if tables.ndim == 2:
            if n_replicas is None:
                raise ValueError(
                    "(P, L) tables need n_replicas=M (or pass (P, M, L))"
                )
            tables = np.repeat(tables[:, None, :], n_replicas, axis=1)
        if tables.ndim == 3:
            tables = tables[:, :, None, :]
        if tables.ndim != 4:
            raise ValueError(
                f"tables must be (P, L), (P, M, L) or (P, M, K, L); "
                f"got {tables.shape}"
            )
        if n_replicas is not None and tables.shape[1] != n_replicas:
            raise ValueError(
                f"tables have {tables.shape[1]} replicas, "
                f"n_replicas={n_replicas}"
            )
        Pn, M, K, L = tables.shape
        arr = np.asarray(arrivals, dtype=np.float64)
        if arr.ndim != 2:
            raise ValueError("run_fleet_grid wants (S, N) arrivals")
        bel = None
        if phase_mode != "oracle" or beliefs is not None:
            if (
                beliefs is not None
                and np.asarray(beliefs).shape[:2] != arr.shape
            ):
                raise ValueError(
                    "beliefs must be (S, N, K) aligned with arrivals"
                )
            phases, bel = _belief_phases(phase_mode, beliefs, phases, K)
        if arr.shape[1] < _ADMIT_W or not np.isinf(arr[:, -_ADMIT_W:]).all():
            raise ValueError("pad each trace with pad_arrivals first")
        S, N = arr.shape
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
            if ph.min() < 0 or ph.max() >= K:
                raise ValueError(f"phases outside the table stack [0, {K})")
        else:
            if K > 1:
                raise ValueError(
                    "phase-indexed tables need phases= (S, N) ints"
                )
            ph = np.zeros(arr.shape, dtype=np.int64)
        rids = np.asarray([router_id(r) for r in routers], dtype=np.int64)
        ru = np.random.default_rng(router_seed).random((S, N, 2))
        means = np.asarray(means, dtype=np.float64)
        zeta_a = (
            np.zeros(b_max + 1)
            if zeta is None
            else np.asarray(zeta, dtype=np.float64).copy()
        )
        zeta_a[0] = 0.0
        if draws is None:
            draws = np.ones((S, 1))
        draws = np.asarray(draws, dtype=np.float64)
        if draws.ndim == 1:  # one shared draw stream -> every lane
            draws = np.tile(draws[None, :], (S, 1))
        if draws.shape[0] != S:
            raise ValueError(f"draws lane axis {draws.shape[0]} != S={S}")
        edges = (
            default_hist_edges(means)
            if hist_edges is None
            else np.asarray(hist_edges, dtype=np.float64)
        )
        thrs = np.stack([threshold_gaps(tables[p]) for p in range(Pn)])
        mix = bel is not None
        bel_g = (
            np.asarray(bel, dtype=np.float64) if mix else np.zeros((S, 1, 1))
        )
        n_arr_max = int(np.isfinite(arr).sum(axis=1).max())
        lane_faults = ()
        n_bnd = n_bounds = 0  # the most of any lane, and over all lanes
        if faults is not None:
            from .faults import FaultSchedule, stack_schedules

            if isinstance(faults, FaultSchedule):
                faults = [faults] * S
            if len(faults) != S:
                raise ValueError(
                    f"{len(faults)} fault schedules for {S} lanes"
                )
            fb, fmult, max_retries = stack_schedules(faults, M)
            finite = np.isfinite(fb).sum(axis=(1, 2))
            n_bnd, n_bounds = int(finite.max()), int(finite.sum())
            lane_faults = (fb, fmult)
        prepare.set_metadata(fault_bounds=n_bounds)
        # crashes re-serve their batch and repairs wake queued replicas —
        # at most two extra epochs per finite fault boundary
        max_eps = (
            2 * n_arr_max + M + 4 + 2 * n_bnd
            if max_epochs is None
            else int(max_epochs)
        )
        # mesh: pad the lane axis to a device multiple (repeat lane 0), trim
        pad_s = 0
        if mesh is not None:
            ndev = int(np.prod([mesh.shape[a] for a in mesh.axis_names[:1]]))
            pad_s = (-S) % ndev
            if pad_s:
                def _pad(x):
                    return np.concatenate([x, np.repeat(x[:1], pad_s, axis=0)])
                arr, dl, ph, bel_g, ru, draws = map(
                    _pad, (arr, dl, ph, bel_g, ru, draws)
                )
                lane_faults = tuple(map(_pad, lane_faults))
        cap = _bucket(2 * (n_arr_max + max_eps + n_bnd) + 2 * M + 8)
        n_steps = min(
            _bucket(max(256, (5 * n_arr_max) // 2 + 2 * M + 8)), cap
        )
        # uploaded once: an escalated dispatch reuses the device arrays
        dev = tuple(
            jnp.asarray(x)
            for x in (tables, thrs, rids, arr, dl, ph, bel_g, ru, draws,
                      means, zeta_a, edges)
        )
        fault_args = (
            tuple(jnp.asarray(x) for x in lane_faults) + (max_retries,)
            if lane_faults else ()
        )
    while True:
        with TraceAnnotation("repro.fleet.run", steps_run=n_steps):
            fn = _fleet_grid_fn(mesh, int(n_steps), mix, bool(fault_args))
            out, ran = fn(
                *dev,
                float(t0), np.inf if horizon is None else float(horizon),
                max_eps, bool(drain), int(b_max), *fault_args,
            )
            done = n_steps >= cap or not bool(
                np.asarray(out["incomplete"]).any()
            )
        if done:
            break
        n_steps = min(2 * n_steps, cap)
    with TraceAnnotation("repro.fleet.post", steps_run=n_steps) as post:
        out = {k: np.asarray(v) for k, v in out.items()}
        # the vmapped loop runs every lane, policy and router in lockstep
        # until the last is done (the most any device's lanes needed)
        steps_executed = int(np.asarray(ran).max())
        if pad_s:
            out = {k: v[:S] for k, v in out.items()}
        post.set_metadata(steps_used=int(out["n_steps_used"].max()),
                          steps_executed=steps_executed,
                          crashes=int(out["n_crashes"].sum()),
                          dropped=int(out["n_dropped"].sum()))
        out["steps_executed"] = steps_executed
        out["hist_edges"] = edges
        with np.errstate(invalid="ignore", divide="ignore"):
            span = out["t_final"] - t0
            # a starved lane (no served request) has no mean latency: NaN,
            # not 0 — the metrics-satellite convention
            out["w_mean"] = np.where(
                out["n_served"] > 0,
                out["lat_sum"] / np.maximum(out["n_served"], 1),
                np.nan,
            )
            have_energy = zeta is not None
            out["power"] = np.where(
                have_energy & (out["n_batches"] > 0) & (span > 0),
                out["energy"] / span,
                np.nan,
            )
            # time-averaged total backlog (Little): integral of queue+in-
            # service size over time / span == sum of latencies / span
            out["q_time_avg"] = np.where(
                span > 0,
                out["lat_sum"] / np.where(span > 0, span, 1.0),
                np.nan,
            )
            out["events_total"] = int(
                out["n_served"].sum() + out["n_epochs"].sum()
            )
        return out
