"""Attribute the device's busy and idle time to the program's host spans.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs the cell once with ``--trace 1`` through bench/run.py, unchanged, and
splits that run's traced window before the harness deletes the trace: it
prints the harness's own output, then one JSON line ``{"spans": ...,
"metrics": ...}`` (the attribution below, and the numbers of ``metrics``).

The program opens named host spans around its phases
(``jax.profiler.TraceAnnotation``, named ``repro.<layer>.<phase>``, with
counts as arguments).  The profiler writes them on the host plane, on the
clock of the device planes.  Each instant of the window belongs to the
innermost program span open at that instant on the thread that ran the
window's calls (the thread of ``bench.window``), or to ``outside`` where
none is open.  The busy union of the first device (as
``trace_reduce.reduce`` takes it: op events in device mode, the runtime's
executions in host mode) is split along those owners:

  window_s  length of the window
  idle_s    idle seconds of the window (window less the busy union)
  spans     per span name: ``count`` (spans that start in the window),
            ``idle_s`` and ``busy_s`` (idle and busy seconds of which it
            is the innermost span), ``idle_max_s`` (the most of that idle
            under one span: where a host stall fell), ``empty`` (spans
            under which the device ran nothing at all) and ``args`` (each
            numeric argument summed over its spans)
  outside   ``idle_s`` and ``busy_s`` under no program span
  kernels   per XLA module (device mode) or jitted function (host mode):
            device seconds under each innermost span name, or "outside"

A trace without program spans (a program that opens none) gives empty
``spans``, and ``metrics`` then gives nothing.
"""
from __future__ import annotations

import contextlib
import json
import sys

import numpy as np

import trace_reduce

PREFIX = "repro."
OUTSIDE = "outside"


def events(path):
    """(window (start, end), the window thread's program spans [(name,
    start, end, args)], per device plane in the trace's order its op and
    module events ([(name, start, end)], [(name, start, end)])), all in
    nanoseconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window, lines, devices = None, [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                mine, has_window = [], False
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                        has_window = True
                    elif ev.name.startswith(PREFIX):
                        mine.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns,
                                     dict(ev.stats)))
                lines.append((has_window, mine))
        elif plane.name.startswith("/device:TPU"):
            found = {
                line.name: [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events]
                for line in plane.lines if line.name in ("XLA Ops", "XLA Modules")
            }
            devices.append((found.get("XLA Ops", []), found.get("XLA Modules", [])))
    spans = next((mine for has, mine in lines if has), [])
    return window, spans, devices


def busy_union(window, devices, red):
    """The first device's busy union [[start, end]] in the window, as
    ``trace_reduce.reduce`` takes it: its op events (else its module
    events), clipped to the window; in host mode the runtime's executions
    (``red["executions"]``)."""
    lo, hi = window
    for ops, modules in devices:
        ivals = [(max(s, lo), min(e, hi)) for _, s, e in ops or modules]
        u = trace_reduce._union([iv for iv in ivals if iv[1] > iv[0]])
        if u:
            return u
    return trace_reduce._union([x[:2] for x in red["executions"]])


def segments(lo, hi, spans):
    """[(start, end, index of the innermost span, or -1)] that partition
    [lo, hi].  Spans of one thread nest; a span that would outlast the
    span it opened in is cut at that span's end."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1], -spans[i][2]))
    out, stack, ends = [], [], []
    t = lo

    def emit(t1):
        nonlocal t
        t1 = min(max(t1, lo), hi)
        if t1 > t:
            out.append((t, t1, stack[-1] if stack else -1))
            t = t1

    for i in order:
        s, e = spans[i][1], spans[i][2]
        while stack and ends[-1] <= s:
            emit(ends[-1])
            stack.pop()
            ends.pop()
        emit(s)
        stack.append(i)
        ends.append(min(e, ends[-1]) if ends else e)
    while stack:
        emit(ends[-1])
        stack.pop()
        ends.pop()
    emit(hi)
    return out


def _covered(intervals, times):
    """Seconds of the disjoint sorted ``intervals`` (ns) before each time."""
    times = np.asarray(times, dtype=np.float64)
    if not len(intervals):
        return np.zeros_like(times)
    iv = np.asarray(intervals, dtype=np.float64)
    # on the first interval's clock: clock readings are large numbers
    origin = iv[0, 0]
    s, e = iv[:, 0] - origin, iv[:, 1] - origin
    times = times - origin
    cum = np.concatenate([[0.0], np.cumsum(e - s)])
    k = np.searchsorted(s, times, side="right") - 1
    inside = np.clip(times - s[np.maximum(k, 0)], 0.0, (e - s)[np.maximum(k, 0)])
    return np.where(k < 0, 0.0, cum[np.maximum(k, 0)] + inside) * 1e-9


def _within(intervals, edges):
    """Seconds of the disjoint sorted ``intervals`` inside each (start,
    end) row of ``edges``."""
    edges = np.asarray(edges, dtype=np.float64).reshape(-1, 2)
    return np.diff(_covered(intervals, edges.ravel()).reshape(-1, 2), axis=1)[:, 0]


def _record(out, name):
    return out["spans"].setdefault(
        name, {"count": 0, "idle_s": 0.0, "idle_max_s": 0.0, "busy_s": 0.0,
               "empty": 0, "args": {}})


def attribute(window, spans, busy, kernels=()):
    """The attribution (module docstring) of the window ``(lo, hi)``, the
    program ``spans`` [(name, start, end, args)], the busy union ``busy``
    [(start, end)] and the kernel executions [(name, start, end)]."""
    lo, hi = window
    spans = [x for x in spans if x[2] > lo and x[1] < hi]
    segs = segments(lo, hi, spans)
    owner = [spans[i][0] if i >= 0 else OUTSIDE for _, _, i in segs]
    out = {"window_s": (hi - lo) * 1e-9, "spans": {},
           OUTSIDE: {"idle_s": 0.0, "busy_s": 0.0}}
    seg_busy = _within(busy, [(a, b) for a, b, _ in segs])
    own_idle = np.zeros(len(spans))
    for (a, b, i), name, bs in zip(segs, owner, seg_busy):
        rec = out[OUTSIDE] if name == OUTSIDE else _record(out, name)
        rec["busy_s"] += float(bs)
        rec["idle_s"] += (b - a) * 1e-9 - float(bs)
        if i >= 0:
            own_idle[i] += (b - a) * 1e-9 - float(bs)
    span_busy = _within(busy, [(max(s, lo), min(e, hi)) for _, s, e, _ in spans])
    for (name, s, _, args), bs, idle in zip(spans, span_busy, own_idle):
        rec = _record(out, name)
        rec["count"] += int(s >= lo)
        rec["idle_max_s"] = max(rec["idle_max_s"], float(idle))
        rec["empty"] += int(bs <= 0.0)
        for k, v in args.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                rec["args"][k] = rec["args"].get(k, 0) + v
    out["idle_s"] = out[OUTSIDE]["idle_s"] + sum(
        r["idle_s"] for r in out["spans"].values())
    out["kernels"] = _kernels(lo, hi, segs, owner, kernels)
    return out


def _kernels(lo, hi, segs, owner, kernels):
    """Device seconds of each kernel under each innermost span name."""
    by_name = {}
    for name, s, e in kernels:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            by_name.setdefault(name, []).append((s, e))
    out = {}
    for name, ivals in by_name.items():
        secs = _within(trace_reduce._union(ivals), [(a, b) for a, b, _ in segs])
        rec = out.setdefault(name, {})
        for who, x in zip(owner, secs):
            if x > 0:
                rec[who] = rec.get(who, 0.0) + float(x)
    return out


def read(path, red):
    """The attribution of the trace at ``path``, whose reduction
    (``trace_reduce.reduce``) is ``red``."""
    window, spans, devices = events(path)
    if window is None:
        raise ValueError(f"no {trace_reduce.WINDOW} span in {path}")
    modules = next((m for _, m in devices if m), None)
    if modules:
        kernels = [(trace_reduce._SUFFIX.sub("", n), s, e) for n, s, e in modules]
    else:
        kernels = [(fn, s, e) for s, e, _, _, fn in red["executions"]]
    return attribute(window, spans, busy_union(window, devices, red), kernels)


def idle_ms_per(sp, prefixes, per):
    """Idle milliseconds under the spans whose names start with one of
    ``prefixes``, per span named ``per``; None without such spans."""
    n = sp["spans"].get(per, {}).get("count", 0)
    hits = [r for k, r in sp["spans"].items() if k.startswith(tuple(prefixes))]
    if not n or not hits:
        return None
    return 1e3 * sum(r["idle_s"] for r in hits) / n


# per grid (``repro.sweep.solve`` span): device idle ms under these spans
SOLVE_IDLE = {
    "solve_idle_ms.build": ["repro.smdp.build"],
    "solve_idle_ms.evaluate": ["repro.evaluate."],
    "solve_idle_ms.rvi": ["repro.rvi."],
    "solve_idle_ms.sweep": ["repro.sweep.solve"],  # under no child span
}
POST = ("repro.grid.post", "repro.fleet.post")


def metrics(sp):
    """The per-layer numbers of an attribution ``sp``, by name; a number
    whose spans are missing is left out.

      solve_idle_ms.*      (ms/grid) device idle under the phase's spans
                           (SOLVE_IDLE), per ``repro.sweep.solve`` span
      f64_share.solve      (%) device busy seconds under ``repro.rvi.f64``
                           over those under every ``repro.rvi.*`` span
      scan_fill.sim        (%) the simulator's scan steps used (the most
                           any lane, table or router took) over the steps
                           run (the completed dispatch's length), summed
                           over the ``post`` spans
      wrapper_idle_ms.sim  (ms/call) device idle under the host wrapper's
                           ``prepare`` and ``post`` spans, per ``post`` span
    """
    out = {name: idle_ms_per(sp, prefixes, "repro.sweep.solve")
           for name, prefixes in SOLVE_IDLE.items()}
    rvi = {k: r["busy_s"] for k, r in sp["spans"].items()
           if k.startswith("repro.rvi.")}
    if "repro.rvi.f64" in rvi and sum(rvi.values()) > 0:
        out["f64_share.solve"] = 100.0 * rvi["repro.rvi.f64"] / sum(rvi.values())
    args = [sp["spans"][k]["args"] for k in POST if k in sp["spans"]]
    ran = sum(a.get("steps_run", 0) for a in args)
    used = sum(a.get("steps_used", 0) for a in args)
    if ran and used:
        out["scan_fill.sim"] = 100.0 * used / ran
    for kind in ("grid", "fleet"):
        value = idle_ms_per(sp, [f"repro.{kind}.prepare", f"repro.{kind}.post"],
                            f"repro.{kind}.post")
        if value is not None:
            out["wrapper_idle_ms.sim"] = value
            break
    return {k: v for k, v in out.items() if v is not None}


@contextlib.contextmanager
def capture():
    """Within the block, every ``trace_reduce.reduce`` of a trace also
    appends the trace's attribution to the yielded list: bench/run.py
    reduces its traced window that way, and deletes the trace after."""
    found = []
    reduce = trace_reduce.reduce

    def reduce_and_attribute(path):
        red = reduce(path)
        found.append(read(path, red))
        return red

    trace_reduce.reduce = reduce_and_attribute
    try:
        yield found
    finally:
        trace_reduce.reduce = reduce


def main(argv=None) -> int:
    import run

    argv = list(sys.argv[1:] if argv is None else argv)
    with capture() as found:
        rc = run.main(argv + ["--trace", "1"])
    if rc == 0 and found:
        print(json.dumps({"spans": found[-1], "metrics": metrics(found[-1])}),
              flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
