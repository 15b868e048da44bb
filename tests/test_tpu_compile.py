"""Compile the main path's kernels and jitted steps for a described TPU v5e.

Nothing runs: each test lowers and compiles at a deployment size for one
chip of a ``v5e:2x2`` topology that is described, not attached, so what
the chip's compiler refuses (unlowerable Pallas ops, misaligned blocks,
float64 LU, programs that overflow 16 GB) fails here without a chip.  The
topology is described inside a module fixture, never at import, and the
persistent compilation cache is off (a described device cannot read it
back).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401  (float64 on)
from repro.core import rvi
from repro.kernels import bellman
from repro.serving import compiled, fleet
from repro.serving.arrivals import poisson_times_jax

N_SPECS = 72  # the paper grid: 9 loads x 8 energy weights
B_MAX = 32
S_MAX = 128  # paper_spec's truncation (the grid's auto-grown s_max)
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]


def _fits(compiled_fn):
    m = compiled_fn.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes
    )
    assert total < HBM_BYTES, total
    return total


@pytest.mark.parametrize("batched", [True, False])
def test_bellman_kernel_lowers(one_chip, batched):
    T, A, K = S_MAX + 1, B_MAX + 1, S_MAX + 1
    f32 = jnp.float32
    if batched:
        fn = bellman.bellman_banded_batched
        args = _shapes(one_chip, ((N_SPECS, T + K), f32),
                       ((N_SPECS, A, K), f32), ((N_SPECS, T, A), f32),
                       ((N_SPECS,), f32))
    else:
        fn = bellman.bellman_banded
        args = _shapes(one_chip, ((T + K,), f32), ((A, K), f32),
                       ((T, A), f32), ((), f32))
    c = jax.jit(lambda *a: fn(*a, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in c.as_text()


def _solver_args(one_chip, dtype, S=S_MAX + 2, Kb=S_MAX + 1):
    A, T = B_MAX + 1, S_MAX + 1
    return _shapes(one_chip, ((N_SPECS, S, A), dtype), ((N_SPECS, A, Kb), dtype),
                   ((N_SPECS, A, T), dtype), ((N_SPECS, S, A), dtype))


def test_exact_gain_solve_compiles(one_chip):
    """The float64 policy-evaluation solve: f32 LU + f64 refinement."""
    c_tilde, pmfs, tails, scale = _solver_args(one_chip, jnp.float64)
    pol = jax.ShapeDtypeStruct((N_SPECS, S_MAX + 2), jnp.int64,
                               sharding=one_chip)
    c = rvi._exact_gain.lower(
        c_tilde, pmfs, tails, scale, S_MAX, pol
    ).compile()
    _fits(c)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_mpi_loop_compiles(one_chip, dtype):
    """The accelerated sweep loop (accel="mpi", the high-load default)."""
    c_tilde, pmfs, tails, scale = _solver_args(one_chip, dtype)
    c = rvi._rvi_loop_batched_mpi.lower(
        c_tilde, pmfs, tails, scale, 1e-2, 2e-4, 10_000, S_MAX,
        backup_kind="pallas" if dtype == jnp.float32 else "banded",
    ).compile()
    _fits(c)


def test_simulate_jit_2p20(one_chip):
    n = 1 << 20
    f64, i64 = jnp.float64, jnp.int64
    table, arr, dl, ph, bel, draws, means, zeta, edges = _shapes(
        one_chip, ((1, S_MAX + 1), i64), ((n,), f64), ((n,), f64),
        ((n,), i64), ((1, 1), f64), ((1,), f64), ((B_MAX + 1,), f64),
        ((B_MAX + 1,), f64), ((65,), f64),
    )
    c = compiled._simulate_jit.lower(
        table, arr, dl, ph, bel, draws, means, zeta, edges, 0.0, np.inf,
        2 * n + 2, True, B_MAX, None, None, False,
        n_steps=compiled._bucket(3 * n // 4), record=False, mix=False,
        adaptive=False, qman=False,
    ).compile()
    _fits(c)


def test_grid_poisson(one_chip):
    """The early-exit grid at the poisson benchmark cell's shapes: 16 lanes
    x 1 table, 2^20 arrival slots, in the cell's 786,432-step bucket."""
    S, n = 16, 1 << 20
    f64, i64 = jnp.float64, jnp.int64
    args = _shapes(
        one_chip, ((1, 1, S_MAX + 1), i64), ((S, n), f64), ((S, n), f64),
        ((S, n), i64), ((S, 1, 1), f64), ((S, 1), f64), ((B_MAX + 1,), f64),
        ((B_MAX + 1,), f64), ((compiled.DEFAULT_N_BINS + 1,), f64),
    )
    c = compiled._grid_jit.lower(
        *args, 0.0, np.inf, 2 * n + 2, True, B_MAX, n_steps=786432,
        mix=False,
    ).compile()
    _fits(c)


def test_fleet_jit_m8(one_chip):
    M, n = 8, 1 << 18
    f64, i64 = jnp.float64, jnp.int64
    L = S_MAX + 1
    args = _shapes(
        one_chip, ((M, 1, L), i64), ((M, 1, L), i64), ((n,), f64),
        ((n,), f64), ((n,), i64), ((1, 1), f64), ((1,), f64), ((n, 2), f64),
        ((M, 1), f64), ((M, 1), f64), ((1,), f64), ((B_MAX + 1,), f64),
        ((B_MAX + 1,), f64), ((65,), f64), ((M, 1), f64), ((M, 1), f64),
    )
    zm = np.zeros(M, dtype=np.int64)
    c = fleet._fleet_jit.lower(
        *args, 1, 0.0, np.inf, 2 * n + M + 4, True, B_MAX,
        fleet._NO_BUFFER, 0, 0, 0, np.full(M, np.inf), zm,
        np.ones(M, dtype=bool), zm, zm, zm, False, np.inf,
        n_steps=compiled._bucket(3 * n // 2), record=False, mix=False,
    ).compile()
    _fits(c)


def test_fleet_grid_bursty(one_chip):
    """The early-exit fleet grid at the bursty benchmark cell's shapes:
    16 lanes x 1 table x 2 routers, M = 8, 2^18 arrival slots."""
    S, P, R, M, n = 16, 1, 2, 8, 1 << 18
    f64, i64 = jnp.float64, jnp.int64
    L = S_MAX + 1
    args = _shapes(
        one_chip, ((P, M, 1, L), i64), ((P, M, 1, L), i64), ((R,), i64),
        ((S, n), f64), ((S, n), f64), ((S, n), i64), ((S, 1, 1), f64),
        ((S, n, 2), f64), ((S, 1), f64), ((B_MAX + 1,), f64),
        ((B_MAX + 1,), f64), ((65,), f64),
    )
    c = fleet._fleet_grid_fn(None, 786432, False).lower(
        *args, 0.0, np.inf, 2 * n + M + 4, True, B_MAX,
    ).compile()
    _fits(c)


def _grid_args(sharding, lane_sharding, S, faults):
    """The fleet grid's arguments at the bursty cells' shapes (M = 8, 1
    table, 2 routers, 2^18 slots) for S lanes, plus a failover schedule's
    (S, 8, 2) boundaries and (S, 8, 4096) multipliers."""
    P, R, M, n = 1, 2, 8, 1 << 18
    f64, i64 = jnp.float64, jnp.int64
    L = S_MAX + 1
    rep = _shapes(sharding, ((P, M, 1, L), i64), ((P, M, 1, L), i64),
                  ((R,), i64))
    lanes = _shapes(lane_sharding, ((S, n), f64), ((S, n), f64),
                    ((S, n), i64), ((S, 1, 1), f64), ((S, n, 2), f64),
                    ((S, 1), f64))
    consts = _shapes(sharding, ((B_MAX + 1,), f64), ((B_MAX + 1,), f64),
                     ((65,), f64))
    args = rep + lanes + consts + [0.0, np.inf, 2 * n + M + 8, True, B_MAX]
    if faults:
        args += _shapes(lane_sharding, ((S, M, 2), f64),
                        ((S, M, 4096), f64)) + [2]
    return args


def test_fleet_grid_failover(one_chip):
    """The faulted fleet grid at the failover cell's shapes: 16 lanes,
    each with its own boundaries and straggler multipliers."""
    c = fleet._fleet_grid_fn(None, 786432, False, True).lower(
        *_grid_args(one_chip, one_chip, 16, True)
    ).compile()
    _fits(c)


@pytest.mark.parametrize("faults", [False, True], ids=["bursty", "failover"])
def test_fleet_grid_lanes_mesh_2x2(topo, faults):
    """The lane-sharded grid over the described 2x2 host, 16 lanes per
    chip as in the four-chip cell; with faults, the schedules shard with
    the lanes."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(topo.devices), ("data",))
    rep = NamedSharding(mesh, P())
    lanes = NamedSharding(mesh, P("data"))
    c = fleet._fleet_grid_fn(mesh, 786432, False, faults).lower(
        *_grid_args(rep, lanes, 64, faults)
    ).compile()
    _fits(c)


def test_poisson_times_jax(one_chip):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    c = jax.jit(
        lambda k: poisson_times_jax(k, 2.0, 1 << 20)
    ).lower(key).compile()
    _fits(c)
