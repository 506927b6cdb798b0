"""SSD (Mamba2) chunked-scan Pallas TPU kernel.

The model's XLA path (``models/ssm._ssd_chunked``) materializes the
(B, n_chunks, Q, Q, H) decay tensor L at fusion boundaries — the SSM
analogue of the attention score-block traffic.  This kernel keeps the
whole per-(batch, head) chunk pipeline in VMEM:

* grid = (B, H): one program per (batch, head) — the recurrent state
  (P, N) lives in VMEM registers across the *sequential* chunk loop,
  which is the data dependency the algorithm fundamentally has;
* per chunk: the (Q, Q) decay/score matrices, the (Q, N) B/C blocks and
  the (Q, P) x block are VMEM-resident; two MXU matmuls (C·Bᵀ ⊙ L)·x and
  C·h per chunk plus rank-1 state updates;
* HBM traffic = x, B, C, dt read once and y written once — O(S) instead
  of O(S·Q) boundary crossings.

Time-sequential chunk recurrence is expressed with ``fori_loop`` carrying
the (P, N) state, exactly like the flash kernel carries (m, l, acc).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..platform import resolve_interpret

__all__ = ["ssd_scan_pallas"]


def _ssd_kernel(x_ref, loga_ref, b_ref, c_ref, y_ref, hout_ref, *, chunk, n_chunks):
    p = x_ref.shape[-1]
    n = b_ref.shape[-1]

    def body(ic, h):
        sl = pl.dslice(ic * chunk, chunk)
        xb = x_ref[0, 0, sl, :].astype(jnp.float32)          # (Q, P)
        la = loga_ref[0, 0, sl].astype(jnp.float32)          # (Q,)
        bb = b_ref[0, sl, :].astype(jnp.float32)             # (Q, N)
        cb = c_ref[0, sl, :].astype(jnp.float32)             # (Q, N)

        cs = jnp.cumsum(la)                                  # (Q,)
        # intra-chunk: L[i,j] = exp(cs_i − cs_j) for i ≥ j
        seg = cs[:, None] - cs[None, :]
        li = jnp.tril(jnp.exp(seg))                          # (Q, Q)
        s = jnp.dot(cb, bb.T, preferred_element_type=jnp.float32) * li
        y_intra = jnp.dot(s, xb, preferred_element_type=jnp.float32)
        # inter-chunk: contribution of the carried state
        y_inter = jnp.exp(cs)[:, None] * jnp.dot(
            cb, h.T, preferred_element_type=jnp.float32
        )                                                    # (Q, P)
        y_ref[0, 0, sl, :] = (y_intra + y_inter).astype(y_ref.dtype)
        # state update: h' = exp(cs_Q)·h + Σ_j exp(cs_Q − cs_j) x_j ⊗ B_j
        decay_out = jnp.exp(cs[-1] - cs)                     # (Q,)
        h_new = jnp.exp(cs[-1]) * h + jnp.dot(
            (xb * decay_out[:, None]).T, bb,
            preferred_element_type=jnp.float32,
        )                                                    # (P, N)
        return h_new

    h0 = jnp.zeros((p, n), jnp.float32)
    h_final = jax.lax.fori_loop(0, n_chunks, body, h0)
    hout_ref[0, 0] = h_final


def ssd_scan_pallas(
    x: jax.Array,       # (B, S, H, P) — pre-scaled by dt
    log_a: jax.Array,   # (B, S, H)
    Bm: jax.Array,      # (B, S, N)
    Cm: jax.Array,      # (B, S, N)
    *,
    chunk: int = 64,
    interpret: Optional[bool] = None,
):
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    xr = x.transpose(0, 2, 1, 3)           # (B, H, S, P)
    lar = log_a.transpose(0, 2, 1)         # (B, H, S)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, s, p), lambda ib, ih: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, s), lambda ib, ih: (ib, ih, 0)),
            pl.BlockSpec((1, s, n), lambda ib, ih: (ib, 0, 0)),
            pl.BlockSpec((1, s, n), lambda ib, ih: (ib, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, s, p), lambda ib, ih: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(xr, lar, Bm, Cm)
    return y.transpose(0, 2, 1, 3), h_final
