"""Jit'd wrapper + analytic HBM-traffic model for the flash kernel.

``flash_attention`` is the public entry (falls back to the oracle for
shapes the kernel doesn't tile).  ``kernel_hbm_bytes`` is the traffic the
kernel performs by construction — Q, K, V read once, O written once —
used by the roofline's kernel-substitution analysis (§Perf): on real TPU
this kernel replaces the XLA online-softmax path whose score blocks
round-trip HBM.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_pallas
from .ref import mha_ref

__all__ = ["flash_attention", "kernel_hbm_bytes", "kernel_flops"]


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_block", "kv_block", "interpret")
)
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0,
    q_block: int = 128, kv_block: int = 128, interpret: bool | None = None,
) -> jax.Array:
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if sq % min(q_block, sq) or sk % min(kv_block, sk):
        return mha_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, interpret=interpret,
    )


def kernel_hbm_bytes(
    batch: int, sq: int, sk: int, heads: int, kv_heads: int, head_dim: int,
    *, bytes_per_el: int = 2, backward: bool = False,
) -> float:
    """HBM traffic of the kernel by construction (K/V VMEM-resident):
    forward reads Q,K,V and writes O; backward re-reads Q,K,V,O,dO and
    writes dQ,dK,dV (+ fp32 logsumexp stats, negligible)."""
    q_b = batch * sq * heads * head_dim * bytes_per_el
    kv_b = 2 * batch * sk * kv_heads * head_dim * bytes_per_el
    o_b = q_b
    fwd = q_b + kv_b + o_b
    if not backward:
        return fwd
    bwd = (2 * q_b + kv_b) + (q_b + kv_b)  # reads (Q,K,V,O,dO) + writes (dQ,dK,dV)
    return fwd + bwd


def kernel_flops(
    batch: int, sq: int, sk: int, heads: int, head_dim: int,
    *, causal: bool = True, backward: bool = False,
) -> float:
    """MXU FLOPs: 2·(QK^T) + 2·(PV) per head, halved by causal skipping."""
    full = 2.0 * 2.0 * batch * heads * sq * sk * head_dim
    if causal and sq == sk:
        full *= 0.5
    return full * (3.5 if backward else 1.0)
