"""The harness: work counts, fixed shapes, data-driven lookup, refusals."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import common
import peaks

RUN = str(common.BENCH / "run.py")


def _entry(name, seed):
    wl, cfg, mix = common.cell(common.benchmark(), name)
    return common.make_entry(cfg, mix, seed, test=True)


SIM_CELLS = [w["name"] for w in common.benchmark()["workloads"]
             if common.cell(common.benchmark(), w["name"])[2]["entry"] != "sweep_solve"]


@pytest.mark.parametrize("name", SIM_CELLS)
def test_sim_work_is_the_generated_arrivals(name):
    e = _entry(name, 7)
    shapes, sampled = [], []
    pad, sample = e.program["pad"], e.process.sample

    def spy_pad(times, size):
        arr = pad(times, size=size)
        shapes.append(arr.shape)
        return arr

    def spy_sample(keys):
        times = sample(keys)
        sampled.append(sum(len(t) for t in times))
        return times

    e.program["pad"], e.process.sample = spy_pad, spy_sample
    works = [e.call(i) for i in range(2)]
    # every seed and call pads to the same (lanes, slots): no recompiles
    assert shapes == [(e.lanes, e.slots)] * 2
    # the work is what the benchmark sampled, counted before padding
    assert works == sampled
    a = e.mix["arrivals"]
    if a["process"] == "poisson":
        assert works == [e.lanes * a["per_lane"]] * 2
    else:
        steps = a["sampler_steps"]
        assert all(0.9 * e.lanes * steps < w <= e.lanes * steps for w in works)
    assert e.pad_faults == 0 and sum(n.sum() for n in e.n_lanes) == sum(works)


def test_solve_grid_is_the_same_set_in_a_seeded_order():
    a, b, c = (_entry("cmdr.solve.light", s) for s in (1, 1, 2))
    assert a.points_rw == b.points_rw and a.points_rw != c.points_rw
    assert sorted(a.points_rw) == sorted(c.points_rw)
    w2 = sorted({w for _, w in a.points_rw})
    np.testing.assert_allclose(w2, np.geomspace(0.1, 10.0, len(w2)))


def _add_cell(root, bench, cell, mix_name, mix, config="googlenet-p4"):
    (root / "bench" / "traffic" / f"{mix_name}.json").write_text(json.dumps(mix))
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": mix_name, "chips": 1, "why": "test"})


@pytest.mark.parametrize("new", ["mix", "entry"])
def test_a_new_cell_needs_only_new_files(new, tmp_path, monkeypatch):
    """A new mix (run_grid under MMPP2 arrivals: no cell has them), or a
    new entry file, plus a new metric, run by name alone."""
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    poisson = common.load_json(common.BENCH / "traffic" / "poisson.json")
    bursty = common.load_json(common.BENCH / "traffic" / "bursty.json")
    root = tmp_path / "checkout"
    shutil.copytree(common.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    mix = dict(poisson, arrivals=bursty["arrivals"],
               limits=dict(bursty["limits"]),
               cpu_test=dict(poisson["cpu_test"],
                             arrivals=bursty["cpu_test"]["arrivals"]))
    if new == "entry":
        shutil.copy(root / "bench" / "entries" / "run_grid.py",
                    root / "bench" / "entries" / "run_grid_copy.py")
        mix["entry"] = "run_grid_copy"
    _add_cell(root, bench, "p4.sim.dummy", "dummy-bursty-single", mix)
    (root / "bench" / "metrics" / "calls_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx['counters']['calls'])\n")
    bench["per_layer"].append({"name": "calls_in_window", "unit": "calls",
                               "better": "lower", "source": "program_counter",
                               "layer": "harness", "moves": "sim_requests_per_s",
                               "workloads": ["p4.sim.dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(common, "ROOT", root)
    monkeypatch.setattr(common, "BENCH", root / "bench")
    import run

    b = common.benchmark()
    wl, cfg, m = common.cell(b, "p4.sim.dummy")
    out = run.run_cell(b, wl, cfg, m, 3, 0.1, 1, test=True, cache=False,
                       log=lambda s: None)
    assert out["correct"], out["checks"]
    assert "switch_z" in out["checks"]
    assert out["metrics"]["calls_in_window"]["value"] >= 1
    assert out["device"]["window_s"] > 0 and "breakdown" in out


@pytest.mark.parametrize("name,option,value", [
    ("cmdr.solve.light", "backup", "pallas"),
    ("cmdr.fleet.bursty", "mesh", "lanes"),
])
def test_mix_options_reach_the_program(name, option, value):
    wl, cfg, mix = common.cell(common.benchmark(), name)
    mix["options"] = {option: value}
    e = common.make_entry(cfg, mix, 5, test=True)
    key = mix["entry"]
    seen = []
    call = e.program[key]

    def spy(*a, **k):
        seen.append(k)
        return call(*a, **k)

    e.program[key] = spy
    e.warm()
    e.timed(0)
    assert seen and all(option in k for k in seen)
    limits = e.limits()
    assert all(v <= limits[k] for k, v in e.check().items())


def _edit(mix, path, value):
    *head, last = path.split(".")
    for k in head:
        mix = mix[k]
    if value is None:
        del mix[last]
    else:
        mix[last] = value


@pytest.mark.parametrize("name,path,value,says", [
    ("p4.sim.poisson", "backup", "pallas", "reads no mix key backup"),
    ("p4.sim.poisson", "arrivals.phase_mode", "belief_mix",
     "reads no mix key arrivals.phase_mode"),
    ("p4.sim.poisson", "cpu_test.lane", 3, "reads no mix key cpu_test.lane"),
    ("p4.sim.poisson", "options", {"mesh": "lanes"}, "does not forward option"),
    ("cmdr.fleet.bursty", "options", {"faults": "outages"},
     "does not forward option"),
    ("cmdr.solve.light", "options", {"backup": "dense"}, "does not forward option"),
    ("cmdr.solve.light", "limits.policy_gap", None, "are not the numbers"),
    ("cmdr.fleet.bursty", "limits.extra", 1.0, "are not the numbers"),
    ("p4.sim.poisson", "arrivals.process", "diurnal", "unknown arrival process"),
    ("p4.sim.poisson", "entry", "run_grid2", "no entries file"),
])
def test_a_mix_the_entry_cannot_read_is_refused(name, path, value, says):
    wl, cfg, mix = common.cell(common.benchmark(), name)
    _edit(mix, path, value)
    with pytest.raises(common.BenchError, match=says):
        common.make_entry(cfg, mix, 1, test=True)


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_chip():
    p = _run([RUN, "--workload", "cmdr.solve.light", "--seed", "3",
              "--seconds", "1", "--trace", "0"], common.ROOT)
    assert p.returncode not in (0, None) and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    p = _run(["bench/run.py", "--workload", "p4.sim.poisson", "--seed", "3",
              "--seconds", "1", "--trace", "0"], tmp_path,
             {"PYTHONPATH": ""})
    assert p.returncode not in (0, None) and p.stdout.strip() == ""


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
