"""Shared plumbing of the benchmark: files, the compile cache, the device.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name BENCHMARK.json gives:

    bench/configs/<config>.json     a deployment (sizes, source, cuts)
    bench/traffic/<traffic>.json    a traffic mix (entry, load, limits)
    bench/entries/<entry>.py        the program entry a mix's window drives
    bench/metrics/<metric>.py       a per-layer metric's reader
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".bench_cache" / "jax"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, missing file, ...)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path.name} at the checkout root")
    return load_json(path)


def cell(bench: dict, name: str):
    """(workload entry, configuration dict, traffic dict) of one cell."""
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = load_json(ROOT / cfg_entry["file"])
    mix = load_json(BENCH / "traffic" / f"{wl['traffic']}.json")
    return wl, cfg, mix


def metrics_for(bench: dict, cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [
        m for m in bench[kind]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def _load(kind: str, name: str):
    """The module bench/<kind>/<name>.py."""
    path = BENCH / kind / f"{name}.py"
    if not _NAME.fullmatch(str(name)) or not path.is_file():
        raise BenchError(f"no {kind} file for {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric_name: str):
    """The ``read(ctx)`` function of bench/metrics/<metric_name>.py."""
    return _load("metrics", metric_name).read


def make_entry(cfg: dict, mix: dict, seed: int, test: bool = False):
    """The entry that the mix names (``ENTRY`` of bench/entries/<entry>.py),
    built for this configuration and seed."""
    return _load("entries", mix.get("entry")).ENTRY(cfg, mix, seed, test)


def use_checkout_cache() -> None:
    """Point JAX's persistent cache at the checkout; call before jax loads.

    A cache directory set from outside is overridden on purpose: two
    checkouts measured side by side must not share compiled programs.
    """
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)


def enable_cache(jax) -> None:
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # the solver compiles many small programs; keep every one of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Seconds and count of XLA compiles (or cache loads), and cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_info(jax) -> dict:
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def memory_peak_bytes(jax) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
