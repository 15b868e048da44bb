#!/usr/bin/env python3
"""Readings for the limits of ``correct``: sound runs and the control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

On the chip, for each seed: set the cell up, run a short window at the
cell's own sizes and load, and print the numbers the run compares, first
for the program's outputs, then with the control in the program's place:
the reference itself computed in float32, one precision below the
float64 that the configurations state.  A sound limit lies above every
program reading and below every control reading.  One process serves all
seeds, so programs compile once.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench = common.benchmark()
    wl, cfg, mix = common.cell(bench, args.workload)
    sys.path.insert(0, str(common.ROOT / "src"))
    common.use_checkout_cache()
    import run

    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        sink = []
        out = run.run_cell(bench, wl, cfg, mix, seed, args.seconds, 0,
                           log=lambda s: None, sink=sink, warm=k == 0)
        limits = sink[0].limits()
        ctl = sink[0].check(control=True)
        print(json.dumps({
            "cell": wl["name"], "seed": seed, "correct": out["correct"],
            "metrics": out["metrics"],
            "program": {k: v["value"] for k, v in out["checks"].items()},
            "control": {k: ctl[k] for k in limits},
            "control_correct": all(ctl[k] <= limits[k] for k in limits),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
