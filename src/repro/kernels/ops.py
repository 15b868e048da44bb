"""jit'd public wrappers for the Pallas kernels.

On CPU (this container) kernels execute in interpret mode — the kernel body
runs as traced JAX ops, validating logic exactly; on TPU (`jax.devices()[0]
.platform == 'tpu'`) they compile to Mosaic.  ``interpret=None`` auto-detects.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import bellman as _bellman
from . import decode_attention as _decode
from . import flash_attention as _flash


_auto_interpret = _bellman.auto_interpret


@partial(jax.jit, static_argnames=("interpret",))
def _bellman_jit(h_main, pmfs, tails, h_overflow, interpret=True):
    return _bellman.bellman_banded(
        h_main, pmfs, tails, h_overflow, interpret=interpret
    )


def bellman_backup(h_main, pmfs, tails, h_overflow, interpret: Optional[bool] = None):
    """Banded RVI backup G[t,a] (see kernels/bellman.py).

    ``interpret=None`` lowers the kernel on TPU and interprets it elsewhere.
    """
    return _bellman_jit(
        h_main, pmfs, tails, jnp.asarray(h_overflow, jnp.float32),
        interpret=_auto_interpret(interpret),
    )


@partial(jax.jit, static_argnames=("interpret",))
def _bellman_batched_jit(h_main, pmfs, tails, h_overflow, interpret=True):
    return _bellman.bellman_banded_batched(
        h_main, pmfs, tails, h_overflow, interpret=interpret
    )


def bellman_backup_batched(
    h_main, pmfs, tails, h_overflow, interpret: Optional[bool] = None
):
    """Spec-batched banded RVI backup G[n,t,a] (see kernels/bellman.py)."""
    return _bellman_batched_jit(
        h_main, pmfs, tails, jnp.asarray(h_overflow, jnp.float32),
        interpret=_auto_interpret(interpret),
    )


@partial(jax.jit, static_argnames=("causal", "softcap", "block_q", "block_k", "interpret"))
def _flash_jit(q, k, v, causal=True, softcap=None, block_q=128, block_k=128, interpret=True):
    return _flash.flash_attention(
        q, k, v, causal=causal, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def flash_attention(q, k, v, *, causal=True, softcap=None, block_q=128,
                    block_k=128, interpret: Optional[bool] = None):
    return _flash_jit(
        q, k, v, causal=causal, softcap=softcap, block_q=block_q,
        block_k=block_k, interpret=_auto_interpret(interpret),
    )


@partial(jax.jit, static_argnames=("softcap", "block_k", "interpret"))
def _decode_jit(q, k_cache, v_cache, lengths, softcap=None, block_k=256, interpret=True):
    return _decode.decode_attention(
        q, k_cache, v_cache, lengths, softcap=softcap, block_k=block_k,
        interpret=interpret,
    )


def decode_attention(q, k_cache, v_cache, lengths, *, softcap=None,
                     block_k=256, interpret: Optional[bool] = None):
    return _decode_jit(
        q, k_cache, v_cache, lengths, softcap=softcap, block_k=block_k,
        interpret=_auto_interpret(interpret),
    )
