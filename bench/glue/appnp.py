"""APPNP per-chunk glue: a chunk of rows of one propagation step mapped
onto the program's ops.

This is the user code of the paper's programmer, kept to plain closures
over the program's public API: ``AppnpWindowOp`` running
``appnp_csr_window`` (ACC units: Â, H and two operand buffers placed once
per device, one compiled program on a fixed-height window) and
``appnp_rows_host`` (CC units, numpy on the host).

A loop is one step.  ``begin_loop(i)`` hands over the step's operand as a
caller of a propagation would: H at every loop whose number is a multiple
of the configuration's ``steps``, else the loop before's result, which is
already on the device (the ACC windows and the CC rows put up, in the
operand buffer the device op wrote) and on the host (the assembled
result).  Each CC chunk puts its rows into the device's next operand when
it completes.  The ACC units run on one device.

A loop's result is a view of one of a few buffers allocated and touched at
set-up, as in ``spmm.py``'s glue, with one buffer more for the result the
CC units read as their operand.  The assembly sets every row that no chunk
of the loop wrote to NaN, which the check reads as unwritten.
"""

from __future__ import annotations

import weakref
from typing import Dict, List, Tuple

import jax
import numpy as np

from repro.kernels.spmm.csr import appnp_csr_window
from repro.kernels.spmm.ops import AppnpWindowOp, HostCsr, appnp_rows_host

# The jitted module an ACC chunk runs, as the profiler names it, and the
# host op a CC chunk runs: both are names of this module's imports.
KERNEL = "appnp_csr_window"
CC_OP = "appnp_rows_host"
# Result buffers made at set-up: the loops the harness compares, the loop
# it holds last, the one the CC units read and the one being written.
BUFFERS = 7


def make(prob, acc_chunk: int):
    """Closures for one problem: ``place(device)``, ``acc_work(device)``,
    ``cc_work``, ``begin_loop``, ``assemble`` and ``release``."""
    csr = HostCsr(prob.indptr, prob.indices, prob.data)
    h = prob.h
    alpha, steps = prob.cfg["alpha"], prob.cfg["steps"]
    shape = h.shape
    placed: Dict = {}
    loop: Dict = {}
    # [buffer, weak reference to the view of it handed out last]
    buffers: List[list] = [[np.full(shape, np.nan, np.float32), None]
                           for _ in range(BUFFERS)]

    def place(device) -> None:
        if placed:
            raise ValueError("APPNP's ACC units run on one device: each device's next "
                             "operand would need the windows of every other")
        placed[device] = AppnpWindowOp(csr, h, device, window=acc_chunk, alpha=alpha,
                                       steps=steps, kernel=appnp_csr_window)

    def acc_work(device):
        op = placed[device]

        def work(chunk):
            lo, out = op(chunk.start, chunk.stop)
            loop["acc"][(chunk.start, chunk.stop)] = (lo, out)
            return out
        return work

    def cc_work(chunk) -> None:
        rows = appnp_rows_host(csr, loop["operand"], h, chunk.start, chunk.stop, alpha)
        loop["result"][chunk.start:chunk.stop] = rows
        for op in placed.values():
            op.put_rows(chunk.start, rows)
        loop["cc"].append((chunk.start, chunk.stop))

    def begin_loop(i: int) -> None:
        k = i % steps
        before = loop.pop("result", None)
        loop["operand"] = h if k == 0 else before
        for op in placed.values():
            op.begin_step(k)
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
