"""Kernel microbenchmarks (interpret mode on CPU: correctness-path timing).

On-TPU wall times are NOT measurable in this container; the derived column
reports the analytic FLOPs/bytes per call used by the §Roofline analysis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops

from .common import emit, timed


def run(smoke: bool = False) -> None:
    del smoke  # already CPU-reduced: uniform interface for run.py --smoke
    key = jax.random.PRNGKey(0)

    # bellman: paper-size backup (s_max=192, Bmax=32)
    T, A, K = 193, 33, 193
    ks = jax.random.split(key, 3)
    h = jax.random.normal(ks[0], (T + K,))
    pmfs = jax.nn.softmax(jax.random.normal(ks[1], (A, K)), -1)
    tails = jax.random.uniform(ks[2], (T, A))
    ops.bellman_backup(h, pmfs, tails, 1.0)  # compile
    _, us = timed(lambda: jax.block_until_ready(
        ops.bellman_backup(h, pmfs, tails, 1.0)), repeat=3)
    flops = 2 * T * A * K
    emit("kernel_bellman_192x33", us, f"flops/call={flops:.2e};banded_vs_dense_flops_ratio={K/ (T):.2f}")

    # bellman, spec-batched: the sweep-engine lockstep shape (17-point grid)
    N = 17
    ks = jax.random.split(jax.random.fold_in(key, 7), 3)
    hb = jax.random.normal(ks[0], (N, T + K))
    pmfb = jax.nn.softmax(jax.random.normal(ks[1], (N, A, K)), -1)
    tailb = jax.random.uniform(ks[2], (N, T, A))
    hso = jax.random.normal(jax.random.fold_in(key, 8), (N,))
    ops.bellman_backup_batched(hb, pmfb, tailb, hso)  # compile
    _, us = timed(lambda: jax.block_until_ready(
        ops.bellman_backup_batched(hb, pmfb, tailb, hso)), repeat=3)
    emit("kernel_bellman_batched_17x192x33", us, f"flops/call={N * flops:.2e}")

    # interpret vs lowered: on a TPU the Mosaic lowering is *validated*
    # against interpret mode (identical inputs, max |diff|); elsewhere there
    # is no lowering, so the case records the skip (tests/test_tpu_compile.py
    # compiles the kernel for a described v5e on every test run).
    if jax.default_backend() == "tpu":
        lowered = ops.bellman_backup(h, pmfs, tails, 1.0, interpret=False)
        interp = ops.bellman_backup(h, pmfs, tails, 1.0, interpret=True)
        diff = float(jnp.max(jnp.abs(lowered - interp)))
        lowered_b = ops.bellman_backup_batched(hb, pmfb, tailb, hso, interpret=False)
        interp_b = ops.bellman_backup_batched(hb, pmfb, tailb, hso, interpret=True)
        diff_b = float(jnp.max(jnp.abs(lowered_b - interp_b)))
        emit("kernel_bellman_lowered_vs_interpret", 0.0,
             f"max_abs_diff={diff:.2e};max_abs_diff_batched={diff_b:.2e}")
    else:
        emit("kernel_bellman_lowered_vs_interpret", 0.0,
             "skipped=cpu-backend-has-no-mosaic-lowering")

    # flash attention: 1k x 1k, 8 heads
    B, S, H, KV, D = 1, 1024, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.bfloat16)
    ops.flash_attention(q, k, v)
    _, us = timed(lambda: jax.block_until_ready(ops.flash_attention(q, k, v)), repeat=1)
    emit("kernel_flash_1k", us, f"flops/call={4 * B * H * S * S * D:.2e}")

    # decode: 32k cache
    B, S, H, KV, D = 4, 4096, 8, 2, 64
    ks = jax.random.split(key, 4)
    q1 = jax.random.normal(ks[0], (B, H, D), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (B, S, KV, D), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (B, S, KV, D), jnp.bfloat16)
    lens = jnp.full((B,), S, jnp.int32)
    ops.decode_attention(q1, kc, vc, lens)
    _, us = timed(lambda: jax.block_until_ready(
        ops.decode_attention(q1, kc, vc, lens)), repeat=1)
    bytes_moved = 2 * B * S * KV * D * 2
    emit("kernel_decode_4k", us, f"hbm_bytes/call={bytes_moved:.2e}")


if __name__ == "__main__":
    run()
