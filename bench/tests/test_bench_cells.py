"""Every cell runs end to end on the CPU at tiny sizes and is correct; the
control (the reference in float32 in the program's place) is not."""
import pytest

import common
from cells import CELLS, run_cpu


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    out = run_cpu(name)
    bench = common.benchmark()
    want = {m["name"] for m in common.metrics_for(bench, name, "end_to_end")}
    assert set(out["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks" and out["failed"] == 0
    assert out["attempted"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    sink = []
    out = run_cpu(name, sink=sink)
    limits = sink[0].limits()
    control = sink[0].check(control=True)
    assert out["correct"]
    assert any(control[k] > limits[k] for k in limits), control
