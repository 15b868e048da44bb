"""Reduce a profiler trace (``*.xplane.pb``) to what the metrics read.

The window is the host span ``bench.window`` that the harness records
around the measured calls; everything is clipped to it.  A trace comes
in one of two modes (``trace_mode`` of the traffic file):

  device  the TPU's own op and module events (``XLA Ops``, ``XLA
          Modules``): exact, but one event per op per loop iteration, so
          only for programs of short loops (the solver)
  host    the TPU runtime's record of each program execution, from its
          launch (``tpu::System::Execute``) to its completion
          (``tpu::System::Execute=>Done``), paired in order: one event
          per execution, for programs that scan a million steps.  Each
          execution is named by the jitted function whose dispatch
          (``PjitFunction(<name>)`` on the host) launched it

  window_s    length of the window
  busy_s      union of the intervals in which a device ran an op (device
              mode) or an execution (host mode), averaged over devices
  modules     device mode: per XLA module (name without its "(id)"
              suffix), device seconds and executions
  ops         device mode: device seconds per XLA op
  executions  host mode: (start, end, name and index of the harness span
              that launched it, name of the jitted function)
  idle_gaps   every gap of the busy union, longest first, each named by
              the harness span (other than the window) that covers most
              of it, or "other"
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
LAUNCH = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"
PJIT = re.compile(r"PjitFunction\((.*)\)$")
_SUFFIX = re.compile(r"\(\d+\)$")
_KIND = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


def op_name(text: str) -> str:
    """'%fusion.12 = f32[8]{0} fusion(...)' -> '%fusion.12 fusion'."""
    head, _, rest = text.partition(" = ")
    kind = _KIND.search(" " + rest) if rest else None
    return f"{head} {kind.group(1)}" if kind else head


def find_xplane(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def events(path):
    """(device planes {name: {line: [(name, start, end)]}}, harness spans,
    runtime executions [(start, end)], jitted-function dispatches [(start,
    end, name)])."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans, launches, dones, pjit = {}, [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                    ]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif ev.name == LAUNCH:
                        launches.append(ev.start_ns)
                    elif ev.name == DONE:
                        dones.append(ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith("PjitFunction("):
                        pjit.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                     PJIT.match(ev.name).group(1)))
    return devices, spans, _pair(sorted(launches), sorted(dones)), pjit


def _pair(launches, dones):
    """Executions run in launch order: the k-th done ends the k-th launch.
    A done before the first launch belongs to a launch before the trace."""
    while dones and launches and dones[0] < launches[0]:
        dones.pop(0)
    return list(zip(launches, dones))


def _launcher(pjit, t):
    """The innermost jitted function whose dispatch covers time t (the
    dispatch runs on the thread that launches), or "other"."""
    inside = [(s, name) for s, e, name in pjit if s <= t < e]
    return max(inside)[1] if inside else "other"


def reduce(path) -> dict:
    devices, spans, execs, pjit = events(path)
    win = [(s, e) for n, s, e in spans if n == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW} span in {path}")
    lo, hi = win[0]
    window_ns = hi - lo
    modules, ops = {}, {}
    busy = []
    unions = []
    for lines in devices.values():
        op_events = lines.get("XLA Ops", [])
        mod_events = lines.get("XLA Modules", [])
        ivals = []
        for name, s, e in op_events or mod_events:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                ivals.append((s, e))
        for name, s, e in op_events:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                key = op_name(name)
                ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
        for name, s, e in mod_events:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                key = _SUFFIX.sub("", name)
                rec = modules.setdefault(key, {"seconds": 0.0, "count": 0})
                rec["seconds"] += (e - s) * 1e-9
                rec["count"] += 1
        if ivals:
            u = _union(ivals)
            unions.append(u)
            busy.append(sum(e - s for s, e in u))
    inner = sorted((x for x in spans if x[0] != WINDOW), key=lambda x: x[1])
    gaps = []
    executions = []
    if not busy and execs:
        # host mode: the runtime's executions stand for the device's work
        for s, e in execs:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                i = next((i for i, (_, a, b) in enumerate(inner) if a <= s < b), -1)
                executions.append((s, e, inner[i][0] if i >= 0 else "other", i,
                                   _launcher(pjit, s)))
        u = _union([x[:2] for x in executions])
        if u:
            unions.append(u)
            busy.append(sum(e - s for s, e in u))
    if unions:
        # gaps of the first device's busy union (one chip per cell)
        u = unions[0]
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            best, cover = "other", 0
            for n, a, b in inner:
                c = min(b, e) - max(a, s)
                if c > cover:
                    best, cover = n, c
            gaps.append((best, (e - s) * 1e-9))
        gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": (sum(busy) / len(busy)) * 1e-9 if busy else 0.0,
        "devices": len(busy),
        "modules": modules,
        "ops": ops,
        "executions": executions,
        "idle_gaps": gaps,
    }


def kernel_seconds(red: dict, module: str):
    """(device seconds, executions) of a kernel: the XLA modules (device
    mode) or the executions of the jitted functions (host mode) whose name
    holds ``module``.  (0, 0) where none does: a reader then reports
    nothing, and the run lacks the metric."""
    if red["modules"]:
        hits = [v for k, v in red["modules"].items() if module in k]
        return sum(v["seconds"] for v in hits), sum(v["count"] for v in hits)
    hits = [(e - s) * 1e-9 for s, e, _, _, fn in red["executions"] if module in fn]
    return sum(hits), len(hits)


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: top device ops (host mode: the
    executions by jitted function and launching span), longest idle gaps."""
    ops = dict(red["ops"])
    if not ops:
        for s, e, who, _, fn in red["executions"]:
            key = f"{fn} in {who}"
            ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
    ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": [[n, s] for n, s in red["idle_gaps"][:top]],
    }
