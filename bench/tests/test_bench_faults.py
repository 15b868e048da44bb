"""A run whose timed path is broken underneath reads ``correct`` false,
for each fault a one-chip cell can have."""
import numpy as np
import pytest

import common
from cells import CELLS, plant, run_cpu


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_is_caught(name, kind):
    out = run_cpu(name, plant=plant(name, kind))
    assert not out["correct"], (kind, out["checks"])


def _drop_tail(kind):
    """The sampler, or the padding, loses the last 5% of every lane."""

    def cut(t):
        t = np.asarray(t)
        n = int(np.isfinite(t).sum())
        return t[: n - n // 20]

    def plant(entry):
        if kind == "sampler":
            sample = entry.process.sample
            entry.process.sample = lambda keys: [cut(t) for t in sample(keys)]
        else:
            pad = entry.program["pad"]
            entry.program["pad"] = lambda times, size: pad(
                [cut(t) for t in times], size=size)

    return plant


SIM_CELLS = [n for n in CELLS
             if common.cell(common.benchmark(), n)[2]["entry"] != "sweep_solve"]


@pytest.mark.parametrize("kind", ["sampler", "pad"])
@pytest.mark.parametrize("name", SIM_CELLS)
def test_dropped_arrivals_are_caught(name, kind):
    out = run_cpu(name, plant=_drop_tail(kind))
    assert not out["correct"], (kind, out["checks"])
