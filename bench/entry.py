"""The base of the program entries that a cell's window drives.

A traffic file (bench/traffic/<mix>.json) names its entry (``"entry"``);
the entry's class is ``ENTRY`` in bench/entries/<entry>.py, found by that
name (``common.make_entry``).  An entry builds the cell's inputs from the
configuration and the seed, warms every shape up with one full call, runs
one call per ``call()``, and afterwards compares what the calls produced
with the plain reference.

A mix holds only keys that its entry reads.  ``schema(mix)`` gives them
(a dict for a group, None for a value), besides those every mix may have
(``COMMON_KEYS``); a mix with any other key is refused, and so are limits
that are not exactly the numbers the entry compares (``checks(mix)``).
``"options"`` are forwarded to the program's call as keyword arguments:
an entry lists in ``OPTIONS`` the ones it forwards and the values each may
take, only options under which the answers stay those of the plain
reference, and refuses every other.

``program`` holds the program's callables that the window drives; the
tests replace them to plant faults.
"""
from __future__ import annotations

import math
import time

import numpy as np

import compare
import deploy
from common import BenchError

COMMON_KEYS = {"entry": None, "about": None, "trace_mode": None,
               "limits": None, "options": None, "cpu_test": None}


def span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def unknown_keys(mix: dict, schema: dict, where: str = "") -> list:
    """Keys of ``mix`` (dotted, nested) that ``schema`` does not have."""
    out = []
    for k, v in mix.items():
        if k not in schema:
            out.append(where + k)
        elif isinstance(schema[k], dict):
            if isinstance(v, dict):
                out += unknown_keys(v, schema[k], f"{where}{k}.")
            else:
                out.append(where + k)
    return out


class Entry:
    #: the mix keys this entry reads, besides COMMON_KEYS
    KEYS: dict = {}
    #: program options forwarded to the call: name -> values it may take
    OPTIONS: dict = {}
    #: the numbers check() returns, each with a limit in the mix
    CHECKS: tuple = ()

    def __init__(self, cfg, mix, seed, test=False):
        self.validate(mix)
        self.cfg = cfg
        self.mix = dict(mix, **mix.get("cpu_test", {})) if test else dict(mix)
        self.seed = int(seed)
        self.options = self.program_options(mix.get("options", {}))
        self.lat, self.zeta = deploy.profile(cfg)
        self.calls = []  # (seconds, work) of each window call

    def schema(self, mix):
        return self.KEYS

    def checks(self, mix):
        return self.CHECKS

    def validate(self, mix):
        schema = dict(COMMON_KEYS, **self.schema(mix))
        bad = unknown_keys(mix, schema)
        test = mix.get("cpu_test", {})
        bad += ["cpu_test." + k for k in unknown_keys(test, self.schema(dict(mix, **test)))]
        if bad:
            raise BenchError(
                f"entry {mix['entry']!r} reads no mix key {', '.join(bad)}")
        for k, v in mix.get("options", {}).items():
            if v not in self.OPTIONS.get(k, ()):
                raise BenchError(
                    f"entry {mix['entry']!r} does not forward option {k}={v!r}; "
                    f"it forwards {self.OPTIONS or 'none'}")
        want, have = set(self.checks(mix)), set(mix.get("limits", {}))
        if want != have:
            raise BenchError(
                f"limits {sorted(have)} are not the numbers entry "
                f"{mix['entry']!r} compares, {sorted(want)}")

    def program_options(self, options):
        """The keyword arguments that ``options`` stand for."""
        return dict(options)

    def timed(self, i):
        t0 = time.perf_counter()
        work = self.call(i)
        self.calls.append((time.perf_counter() - t0, work))

    def limits(self):
        return self.mix["limits"]

    def attempted(self):
        """(answers the window attempted, answers that failed)."""
        return self._attempted, self._failed


# ---------------------------------------------------------------------------
# simulations: arrival processes and the shared window call
# ---------------------------------------------------------------------------


class Poisson:
    """``per_lane`` arrivals per lane at the mix's rate; every one of them
    has to reach the padded array."""

    KEYS = {"process": None, "rho": None, "per_lane": None}
    CHECKS = ("sampler_short",)

    def __init__(self, a, rate):
        import jax

        from repro.serving.arrivals import poisson_times_jax

        self.n = a["per_lane"]
        self._sample = jax.jit(
            jax.vmap(lambda k: poisson_times_jax(k, rate, self.n)))

    def sample(self, keys):
        return list(np.asarray(self._sample(keys)))

    def idc(self):
        return 1.0

    def checks(self, n_lane, t_lane):
        """sampler_short: arrivals missing from, or extra to, the lanes."""
        return {"sampler_short": int(np.abs(n_lane - self.n).sum())}


class MMPP2:
    """Two-phase Markov-modulated Poisson arrivals: phase rates
    ``phase_rates`` times the mix's rate, each phase dwelling
    ``dwell_gaps`` mean gaps (equal dwells); ``sampler_steps`` events per
    lane, each an arrival or a phase switch."""

    KEYS = {"process": None, "rho": None, "phase_rates": None,
            "dwell_gaps": None, "sampler_steps": None}
    CHECKS = ("switch_z",)

    def __init__(self, a, rate):
        import jax

        from repro.serving.arrivals import MMPP2 as Process
        from repro.serving.arrivals import mmpp2_times_jax

        self.r1, self.r2 = (f * rate for f in a["phase_rates"])
        self.dwell = a["dwell_gaps"] / rate
        self.steps = a["sampler_steps"]
        proc = Process(lam1=self.r1, lam2=self.r2, dwell1=self.dwell,
                       dwell2=self.dwell)
        self._sample = jax.jit(
            jax.vmap(lambda k: mmpp2_times_jax(k, proc, self.steps)[0]))

    def sample(self, keys):
        return [t[np.isfinite(t)] for t in np.asarray(self._sample(keys))]

    def idc(self):
        # asymptotic index of dispersion of MMPP2 counts (equal dwells)
        s = 1.0 / self.dwell
        return 1.0 + 2.0 * (self.r1 - self.r2) ** 2 * s * s / (
            (2 * s) ** 2 * (self.r1 * s + self.r2 * s))

    def checks(self, n_lane, t_lane):
        """switch_z: z-score of the phase switches, the sampler's steps
        that emitted no arrival.  With equal dwells the switches are a
        Poisson process of rate 1/dwell, so a lane that ends at t holds
        t/dwell of them; a sampler that loses arrivals, or cuts a lane's
        tail, has too many."""
        switches = float(np.sum(self.steps - n_lane))
        expected = float(np.sum(t_lane)) / self.dwell
        if expected <= 0:
            return {"switch_z": math.inf}
        return {"switch_z": abs(switches - expected) / math.sqrt(expected)}


PROCESSES = {"poisson": Poisson, "mmpp2": MMPP2}


class Simulation(Entry):
    """``sim_requests_per_s``: arrivals the benchmark generated, counted
    before the program pads them, over the calls' wall seconds."""

    kind = None
    #: the jitted function of the simulator's kernel, as traces name it
    KERNEL = None
    CHECKS = ("served_mismatch", "pad_mismatch", "count_mismatch",
              "sum_rel_err", "tied_rel_err", "rate_z")

    def __init__(self, cfg, mix, seed, test=False):
        super().__init__(cfg, mix, seed, test)
        import jax

        from repro.serving import pad_arrivals_batch

        m = self.mix
        tab = m["table"]
        ref = compare.reference_tables(
            cfg, self.lat, self.zeta,
            [(deploy.arrival_rate(cfg, tab["rho"]), tab["w2"])])[0]
        # the policy table comes from the reference solver: an input of
        # the simulation, made without the program
        self.table = ref["policy"][: ref["s_max"] + 1].astype(np.int64)
        self.lanes, self.slots = m["lanes"], m["slots"]
        self.rate = cfg["replicas"] * deploy.arrival_rate(cfg, m["arrivals"]["rho"])
        self.process = PROCESSES[m["arrivals"]["process"]](m["arrivals"], self.rate)
        self.program = {"pad": pad_arrivals_batch}
        self.base_key = jax.random.PRNGKey(
            int(np.random.default_rng(self.seed).integers(2**31)))
        self.pick = np.random.default_rng([self.seed, 1])
        self.reset()

    def schema(self, mix):
        proc = mix.get("arrivals", {}).get("process")
        if proc not in PROCESSES:
            raise BenchError(
                f"unknown arrival process {proc!r}; known: {sorted(PROCESSES)}")
        return dict(self.KEYS, arrivals=PROCESSES[proc].KEYS,
                    table={"rho": None, "w2": None}, lanes=None, slots=None,
                    check={"lanes": None, "per_call": None})

    def checks(self, mix):
        return self.CHECKS + PROCESSES[mix["arrivals"]["process"]].CHECKS

    def reset(self):
        self.kept = []  # (arrivals, padded arrivals, aggregates per router)
        self.n_lanes, self.t_lanes = [], []  # per call: arrivals, last arrival
        self.lane_faults = 0  # lanes served short, or cut off
        self.pad_faults = 0  # lanes whose padded count differs from the sample
        self.short_requests = 0

    def keys(self, i):
        import jax

        return jax.random.split(jax.random.fold_in(self.base_key, i), self.lanes)

    def warm(self):
        self.call(2**30)
        self.reset()

    def call(self, i):
        with span("bench.sample"):
            times = self.process.sample(self.keys(i))
        n_lane = np.array([np.isfinite(t).sum() for t in times])
        t_lane = np.array([np.where(np.isfinite(t), t, 0.0).max(initial=0.0)
                           for t in times])
        with span("bench.pad"):
            arr = self.program["pad"](times, size=self.slots)
        with span("bench.dispatch"):
            out = self.dispatch(arr)
        padded = np.isfinite(arr).sum(axis=1)
        self.pad_faults += int((padded != n_lane).sum())
        served = np.asarray(out["n_served"]).reshape(self.lanes, -1)
        short = np.abs(served - n_lane[:, None])
        self.short_requests += int(short.sum())
        self.lane_faults += int(
            (short != 0).sum() + np.asarray(out["incomplete"]).sum())
        self.n_lanes.append(n_lane)
        self.t_lanes.append(t_lane)
        want = self.mix["check"]["lanes"] - len(self.kept)
        if want > 0:
            for lane in self.pick.choice(self.lanes, min(want, self.mix["check"]["per_call"]), replace=False):
                aggs = [
                    {k: np.asarray(out[k]).reshape(self.lanes, -1)[lane, r].item()
                     for k in compare.INT_KEYS + compare.SUM_KEYS}
                    for r in range(len(self.routers()))
                ]
                self.kept.append((np.array(times[lane]), arr[lane].copy(), aggs))
        return int(n_lane.sum())

    def e2e(self):
        secs = sum(s for s, _ in self.calls)
        return {"sim_requests_per_s": sum(w for _, w in self.calls) / secs}

    def counters(self):
        return {"calls": len(self.calls),
                "requests": sum(w for _, w in self.calls)}

    def routers(self):
        return [None]

    def check(self, control=False):
        pairs = []
        pad_bad = 0
        for times, padded, aggs in self.kept:
            # the padded lane holds exactly the sampled arrivals, in order
            want = np.sort(times[np.isfinite(times)])
            got = padded[np.isfinite(padded)]
            pad_bad += int(got.shape != want.shape or not np.array_equal(got, want))
            for r, router in enumerate(self.routers()):
                ref = compare.reference_lane(
                    self.kind, want, self.table, self.cfg, self.lat, self.zeta,
                    router)
                if control:
                    prog = compare.reference_lane(
                        self.kind, want, self.table, self.cfg, self.lat,
                        self.zeta, router, dtype=np.float32)
                else:
                    prog = aggs[r]
                pairs.append((prog, ref))
        n_lane = np.concatenate(self.n_lanes)
        t_lane = np.concatenate(self.t_lanes)
        out = compare.compare_lanes(pairs)
        out["served_mismatch"] = 0 if control else self.lane_faults
        out["pad_mismatch"] = 0 if control else self.pad_faults + pad_bad
        out["rate_z"] = compare.rate_z(
            int(n_lane.sum()), float(t_lane.sum()), self.rate, self.process.idc())
        out.update(self.process.checks(n_lane, t_lane))
        self._attempted = sum(w for _, w in self.calls) * len(self.routers())
        self._failed = 0 if control else self.short_requests
        return out
