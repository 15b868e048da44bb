#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (BENCHMARK.json ``workloads``) names a deployment
(bench/configs/<config>.json) and a traffic mix
(bench/traffic/<traffic>.json); the mix names the program entry its
window drives (bench/entries/<entry>.py).  One process: set-up builds the inputs
from the seed and warms every shape with one full call; the window then
repeats calls until ``--seconds`` have passed and the running call ends;
finally the calls' outputs are compared with the plain reference
(bench/reference/).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones read by bench/metrics/<name>.py from
a profiler trace of the window), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each compared number with its limit.
The same numbers end standard error.  Without a TPU, with fewer chips
than the cell asks for, or outside a full checkout, the run exits 2 and
prints no result.  JAX's compilation cache lives in .bench_cache/ in the
checkout.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import common  # noqa: E402
from common import BenchError  # noqa: E402


def _profile_options(jax, mode):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the harness spans are enough
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    if mode == "host":
        # one event per program execution instead of one per op and loop
        # iteration: a million-step scan would otherwise trace gigabytes
        opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_HOST"}
    return opts


def run_cell(bench, wl, cfg, mix, seed, seconds, trace, *, test=False,
             plant=None, log=print, cache=True, sink=None, warm=True):
    """Set up, measure and check one cell; returns the result dict.

    ``test`` runs the mix at its ``cpu_test`` sizes; ``plant(entry)`` may
    replace the program's callables to plant a fault; ``sink`` (a list)
    receives the entry, whose ``check(control=True)`` reads the control;
    ``warm=False`` skips the warm-up call (for a process whose programs
    are compiled already).
    """
    import jax

    import trace_reduce

    if cache:
        common.enable_cache(jax)
    import repro.core  # noqa: F401  (float64 on, before any computation)

    clock = common.CompileClock()
    t_setup = time.perf_counter()
    entry = common.make_entry(cfg, mix, seed, test)
    if plant is not None:
        plant(entry)
    if sink is not None:
        sink.append(entry)
    if warm:
        entry.warm()
    setup_s = time.perf_counter() - t_setup
    setup_compile = (clock.seconds, clock.count, clock.cache_hits)
    tdir = common.ROOT / ".bench_cache" / "trace"
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(
            str(tdir), profiler_options=_profile_options(jax, entry.mix["trace_mode"]))
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        i = 0
        while True:
            entry.timed(i)
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    window_compiles = clock.count - setup_compile[1]
    memory = common.memory_peak_bytes(jax)
    device = dict(common.device_info(jax), memory_peak_bytes=memory)
    log(json.dumps({
        "cell": wl["name"], "seed": seed, "setup_s": setup_s,
        "setup_compile_s": setup_compile[0], "setup_compiles": setup_compile[1],
        "setup_cache_hits": setup_compile[2], "window_s": window_s,
        "window_calls": len(entry.calls), "window_compiles": window_compiles,
        "call_s": sorted(s for s, _ in entry.calls),
        "counters": entry.counters(),
    }))
    checks = {k: v if isinstance(v, int) else float(v)
              for k, v in entry.check().items()}
    limits = entry.limits()
    correct = all(checks[k] <= limits[k] for k in limits)
    metrics = {}
    result = {}
    if trace:
        xplane = trace_reduce.find_xplane(tdir)
        log(json.dumps({"trace_bytes": Path(xplane).stat().st_size}))
        red = trace_reduce.reduce(xplane)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = {"trace": red, "counters": entry.counters(),
               "window_compiles": window_compiles,
               "kernel": getattr(entry, "KERNEL", None)}
        for m in common.metrics_for(bench, wl["name"], "per_layer"):
            value = common.load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = trace_reduce.breakdown(red)
    else:
        e2e = dict(entry.e2e(), setup_s=setup_s)
        for m in common.metrics_for(bench, wl["name"], "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    attempted, failed = entry.attempted()
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {
        k: {"value": checks[k], "limit": limits[k]} for k in limits
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = common.benchmark()
        wl, cfg, mix = common.cell(bench, args.workload)
        src = common.ROOT / "src"
        if not (src / "repro").is_dir():
            raise BenchError(f"no program under {src}: run from a full checkout")
        sys.path.insert(0, str(src))
        common.use_checkout_cache()
        import jax

        dev = common.device_info(jax)
        if dev["platform"] != "tpu":
            raise BenchError(
                f"no TPU: jax.devices()[0].platform is {dev['platform']!r}")
        if dev["count"] < wl["chips"]:
            raise BenchError(
                f"the cell asks for {wl['chips']} chips, JAX sees {dev['count']}")
        out = run_cell(bench, wl, cfg, mix, args.seed, args.seconds, args.trace)
    except (BenchError, OSError, KeyError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for k, v in out["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
