"""Pallas TPU kernel: banded RVI Bellman backup (the paper's compute core).

The discrete-time backup for serve actions is a *banded correlation*

    G[t, a] = sum_k p^{[a]}_k h(t + k) + tail(t, a) * h(S_o)

(repro.core.rvi.banded_backup).  The naive dense backup is an (S,A,S)
tensor contraction — O(A*S^2) and memory-bound.  On TPU we instead build
Hankel (sliding-window) tiles of h in VMEM and feed the MXU:

    grid (N, T/Tb, A/Ab); per tile:
        for each 128-wide k-chunk:
            hwin (Tb, 128) <- strided lane roll of one h window  (VMEM)
            acc (Tb, Ab)  += hwin @ pmf_chunk.T                 (MXU)
        out = acc + tails_tile * h_overflow

The Hankel tile is one ``pltpu.roll`` with a sublane stride: a (1, Tb+Kb)
window of h, broadcast over Tb rows and rolled right by u in row u, holds
h(t0 + u + k0 + Kb-1 - j) in column j (the window is stored reversed and
circularly, see _windows; the pmf chunk is reversed to match).  Every
block is (8, 128)-aligned or spans its whole array, and h(S_o) rides in
SMEM, so the kernel lowers through Mosaic as is.

Arithmetic intensity rises from O(1) (dense, streaming the transition
tensor) to O(Tb*Ab/(Tb+Ab)) — the kernel is compute-bound for K >= 128.

Validated in interpret mode against ref.bellman_banded_ref.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TB = 128  # base-state tile
AB = 128  # action tile (A is padded up; extra actions have zero pmfs)
KB = 128  # k-chunk width
W = TB + KB  # lane width of one h window
_0 = np.int32(0)  # block index literal: Mosaic wants int32 even under x64


def auto_interpret(interpret: Optional[bool]) -> bool:
    """Resolve the backend-aware default: the Mosaic lowering on TPU,
    interpret mode everywhere else (the kernel is TPU-only)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _windows(h_p, t_pad: int, k_pad: int):
    """(N, T/TB, K/KB, W) h windows, one per (base tile, k-chunk).

    Window (i, c) serves rows t0 = i*TB and columns k0 = c*KB.  The kernel
    rolls row u right by u, so column j of row u reads window position
    (j - u) mod W; storing h(t0 + k0 + KB-1 - m) at position m mod W for
    m in [-TB, KB) makes that h(t0 + u + k0 + KB-1 - j).
    """
    m = np.where(np.arange(W) < KB, np.arange(W), np.arange(W) - W)
    idx = (
        np.arange(t_pad // TB)[:, None, None] * TB
        + np.arange(k_pad // KB)[None, :, None] * KB
        + (KB - 1 - m)[None, None, :]
    )
    return h_p[:, idx]


def _kernel(win_ref, pmf_ref, tail_ref, hso_ref, out_ref, *, n_c: int):
    n = pl.program_id(0)
    acc = jnp.zeros((TB, AB), dtype=jnp.float32)
    for c in range(n_c):
        row = jnp.broadcast_to(win_ref[0, 0, c : c + 1, :], (TB, W))
        # Hankel tile: hwin[u, j] = h[t0 + u + c*KB + KB-1 - j]
        hwin = pltpu.roll(row, _0, 1, stride=1, stride_axis=0)[:, :KB]
        pmf_chunk = pmf_ref[0, :, c * KB : (c + 1) * KB]  # (AB, KB), k reversed
        acc = acc + jax.lax.dot_general(
            hwin,
            pmf_chunk,
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    out_ref[0] = acc + tail_ref[0] * hso_ref[n]


def bellman_banded_batched(
    h_main, pmfs, tails, h_overflow, *, interpret: Optional[bool] = None
):
    """Spec-batched banded backup: one kernel launch for a whole sweep.

    The spec axis is a third grid dimension — not a vmap of a scalar
    kernel — so a lowered TPU run walks N x (T/TB) x (A/AB) tiles of one
    pallas_call; this is what the batched RVI lockstep (rvi.
    relative_value_iteration_batched with backup="pallas") dispatches.

    h_main: (N, <= T + K) (zero-padded past s_max); pmfs: (N, A, K);
    tails: (N, T, A); h_overflow: (N,).  Returns (N, T, A) f32.
    ``interpret=None`` autodetects the backend (lowered on TPU only).
    """
    interpret = auto_interpret(interpret)
    N, T, A = tails.shape
    K = pmfs.shape[2]
    t_pad = -(-T // TB) * TB
    a_pad = -(-A // AB) * AB
    k_pad = -(-K // KB) * KB
    h_p = jnp.zeros((N, t_pad + k_pad), jnp.float32)
    h_p = h_p.at[:, : h_main.shape[1]].set(h_main.astype(jnp.float32))
    pmf_p = jnp.zeros((N, a_pad, k_pad), jnp.float32).at[:, :A, :K].set(
        pmfs.astype(jnp.float32)
    )
    # reverse k inside each chunk, matching the windows' column order
    pmf_p = pmf_p.reshape(N, a_pad, k_pad // KB, KB)[..., ::-1].reshape(
        N, a_pad, k_pad
    )
    tail_p = jnp.zeros((N, t_pad, a_pad), jnp.float32).at[:, :T, :A].set(
        tails.astype(jnp.float32)
    )
    hso = jnp.asarray(h_overflow, jnp.float32).reshape(N)
    n_c = k_pad // KB

    out = pl.pallas_call(
        functools.partial(_kernel, n_c=n_c),
        grid=(N, t_pad // TB, a_pad // AB),
        in_specs=[
            pl.BlockSpec((1, 1, n_c, W), lambda n, i, j: (n, i, _0, _0)),
            pl.BlockSpec((1, AB, k_pad), lambda n, i, j: (n, j, _0)),
            pl.BlockSpec((1, TB, AB), lambda n, i, j: (n, i, j)),
            pl.BlockSpec((N,), lambda n, i, j: (_0,), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, TB, AB), lambda n, i, j: (n, i, j)),
        out_shape=jax.ShapeDtypeStruct((N, t_pad, a_pad), jnp.float32),
        interpret=interpret,
    )(_windows(h_p, t_pad, k_pad), pmf_p, tail_p, hso)
    return out[:, :T, :A]


def bellman_banded(
    h_main, pmfs, tails, h_overflow, *, interpret: Optional[bool] = None
):
    """G[t, a] = sum_k pmfs[a,k] h_main[t+k] + tails[t,a] * h_overflow.

    h_main: (T + K,) f32 (zero-padded past s_max); pmfs: (A, K); tails: (T, A).
    Returns (T, A) f32 — the N = 1 launch of bellman_banded_batched.
    """
    return bellman_banded_batched(
        h_main[None], pmfs[None], tails[None],
        jnp.reshape(jnp.asarray(h_overflow), (1,)), interpret=interpret,
    )[0]
