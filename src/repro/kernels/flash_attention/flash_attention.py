"""Flash attention Pallas TPU kernel (forward), GQA-aware.

TPU-native schedule (this is the production replacement for the XLA
online-softmax path in ``models/attention.py``, whose score-block HBM
traffic dominates the measured memory roofline term):

* grid = (B, KVH, n_q): one program per (batch, kv-head, query block);
* K/V for the program's kv-head are **VMEM-resident** across the q loop
  (the revisiting/HPC discipline: index_map is constant in the q axis, so
  Mosaic keeps the buffer resident instead of re-streaming — HBM traffic
  becomes Q+K+V+O exactly);
* all G query heads sharing the kv-head are processed together as rows of
  a (G·qb, D) block — MXU-shaped matmuls even for small qb;
* online softmax (running max / denom / accumulator, fp32) over kv blocks
  with a ``fori_loop``; causal programs stop the loop at the diagonal
  block (no wasted FLOPs on fully-masked blocks — the XLA path cannot
  skip them);
* local (windowed) masks supported for the hybrid arch's attention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import resolve_interpret

__all__ = ["flash_attention_pallas"]

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, window,
                  q_block, kv_block, seq_k, groups):
    iq = pl.program_id(2)
    d = q_ref.shape[-1]
    # q block rows = G heads × q_block positions
    q = q_ref[0, 0, 0].astype(jnp.float32) * scale        # (G*qb, D)

    n_kv_total = seq_k // kv_block
    if causal:
        # last kv block the diagonal touches
        limit = jnp.minimum(((iq + 1) * q_block + kv_block - 1) // kv_block,
                            n_kv_total)
    else:
        limit = n_kv_total

    q_pos = iq * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (groups, q_block), 1
    ).reshape(groups * q_block)

    def body(ik, carry):
        m_run, l_run, acc = carry
        kblk = k_ref[0, 0, pl.dslice(ik * kv_block, kv_block), :].astype(jnp.float32)
        vblk = v_ref[0, 0, pl.dslice(ik * kv_block, kv_block), :].astype(jnp.float32)
        s = jnp.dot(q, kblk.T, preferred_element_type=jnp.float32)  # (G*qb, kvb)
        k_pos = ik * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_block), 1
        )
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos[:, None]
        if window:
            mask &= k_pos > (q_pos[:, None] - window)
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        pmat = jnp.exp(s - m_new[:, None])
        l_new = l_run * alpha + jnp.sum(pmat, axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(
            pmat, vblk, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc

    rows = groups * q_block
    m0 = jnp.full((rows,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows,), jnp.float32)
    a0 = jnp.zeros((rows, d), jnp.float32)
    m_f, l_f, acc = jax.lax.fori_loop(0, limit, body, (m0, l0, a0))
    o_ref[0, 0, 0] = (acc / jnp.maximum(l_f, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,           # (B, Sq, H, D)
    k: jax.Array,           # (B, Sk, KVH, D)
    v: jax.Array,           # (B, Sk, KVH, D)
    *,
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    g = h // kvh
    scale = d**-0.5 if scale is None else scale
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    assert sq % q_block == 0 and sk % kv_block == 0, (sq, q_block, sk, kv_block)
    n_q = sq // q_block

    # layout: (B, KVH, G·Sq, D) with G-major rows per q block
    qr = q.reshape(b, sq, kvh, g, d).transpose(0, 2, 3, 1, 4)   # (B,KVH,G,Sq,D)
    qr = qr.reshape(b, kvh, g * sq, d)
    # group rows by q block: (B, KVH, n_q, G*qb, D)
    qr = qr.reshape(b, kvh, g, n_q, q_block, d).transpose(0, 1, 3, 2, 4, 5)
    qr = qr.reshape(b, kvh, n_q, g * q_block, d)
    kr = k.transpose(0, 2, 1, 3)                                # (B,KVH,Sk,D)
    vr = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, seq_k=sk, groups=g,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, kvh, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, 1, g * q_block, d), lambda ib, ih, iq: (ib, ih, iq, 0, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda ib, ih, iq: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda ib, ih, iq: (ib, ih, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, g * q_block, d), lambda ib, ih, iq: (ib, ih, iq, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, n_q, g * q_block, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(qr, kr, vr)

    # undo layout: (B,KVH,n_q,G,qb,D) → (B,Sq,H,D)
    out = out.reshape(b, kvh, n_q, g, q_block, d).transpose(0, 1, 3, 2, 4, 5)
    out = out.reshape(b, kvh, g, sq, d).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, sq, h, d)
