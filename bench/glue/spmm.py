"""SpMM per-chunk glue: a chunk of rows mapped onto the program's ops.

This is the user code of the paper's programmer, kept to plain closures
over the program's public API: ``CsrWindowOp`` running
``spmm_csr_window`` (ACC units: the matrix and X placed once per device,
one compiled program on a fixed-height window) and ``spmm_rows_host``
(CC units, numpy on the host).

A loop's result (419 MB) is a view of one of a few buffers allocated and
touched at set-up, as a caller that solves again and again keeps its
output buffers: a buffer is taken again only once no view of it is held
(the harness keeps the results of the loops it compares).  Each chunk's
rows are written where they are computed (CC) or assembled (ACC), and the
assembly sets every row that no chunk of the loop wrote to NaN, which the
check reads as unwritten.  A fresh 419 MB result a loop spent 0.4 s in
first-touch page faults on a TPU v5e host (PERF.md, section 6).
"""

from __future__ import annotations

import weakref
from typing import Dict, List, Tuple

import jax
import numpy as np

from repro.kernels.spmm.csr import spmm_csr_window
from repro.kernels.spmm.ops import CsrWindowOp, HostCsr, spmm_rows_host

# The jitted module an ACC chunk runs, as the profiler names it, and the
# host op a CC chunk runs: both are names of this module's imports.
KERNEL = "spmm_csr_window"
CC_OP = "spmm_rows_host"
# Result buffers made at set-up: the loops the harness compares, the loop
# it holds last, and the one being written.
BUFFERS = 6


def make(prob, acc_chunk: int):
    """Closures for one problem: ``place(device)``, ``acc_work(device)``,
    ``cc_work``, ``begin_loop``, ``assemble`` and ``release``."""
    csr = HostCsr(prob.indptr, prob.indices, prob.data)
    x = prob.x
    shape = (csr.rows, x.shape[1])
    placed: Dict = {}
    loop: Dict = {}
    # [buffer, weak reference to the view of it handed out last]
    buffers: List[list] = [[np.full(shape, np.nan, np.float32), None]
                           for _ in range(BUFFERS)]

    def place(device) -> None:
        placed[device] = CsrWindowOp(csr, x, device, window=acc_chunk,
                                     kernel=spmm_csr_window)

    def acc_work(device):
        op = placed[device]

        def work(chunk):
            lo, out = op(chunk.start, chunk.stop)
            loop["acc"][(chunk.start, chunk.stop)] = (lo, out)
            return out
        return work

    def cc_work(chunk) -> None:
        loop["result"][chunk.start:chunk.stop] = spmm_rows_host(
            csr, x, chunk.start, chunk.stop)
        loop["cc"].append((chunk.start, chunk.stop))

    def begin_loop(_loop: int) -> None:   # every loop has the same inputs
        loop.pop("result", None)
        for entry in buffers:
            if entry[1] is None or entry[1]() is None:
                break
        else:
            entry = [np.empty(shape, np.float32), None]
            buffers.append(entry)
        view = entry[0].view()
        entry[1] = weakref.ref(view)
        loop["result"] = view
        loop["acc"] = {}
        loop["cc"] = []

    def assemble() -> Tuple[np.ndarray, List[Tuple[int, int]]]:
        result = loop["result"]
        written = np.zeros(shape[0], bool)
        spans = list(loop["acc"])
        # every window is computed by now: fetch them all in one transfer
        windows = jax.device_get([loop["acc"][k] for k in spans])
        for (s, e), (lo, out) in zip(spans, windows):
            result[s:e] = out[s - lo:e - lo, :shape[1]]
            written[s:e] = True
        for s, e in loop["cc"]:
            written[s:e] = True
        result[~written] = np.nan
        return result, sorted(loop["acc"])

    def release() -> None:
        placed.clear()
        loop.clear()
        buffers.clear()

    return dict(place=place, acc_work=acc_work, cc_work=cc_work,
                begin_loop=begin_loop, assemble=assemble, release=release)
