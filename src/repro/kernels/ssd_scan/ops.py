"""Jit'd wrapper for the SSD scan kernel."""

from __future__ import annotations

import functools

import jax

from .ref import ssd_ref
from .ssd_scan import ssd_scan_pallas

__all__ = ["ssd_scan", "ssd_ref"]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, log_a, Bm, Cm, *, chunk: int = 64, interpret: bool | None = None):
    """(y, final_state) — Pallas path; falls back to the oracle when the
    sequence doesn't tile."""
    if x.shape[1] % chunk:
        return ssd_ref(x, log_a, Bm, Cm, chunk)
    return ssd_scan_pallas(x, log_a, Bm, Cm, chunk=chunk, interpret=interpret)
