"""Where a Pallas kernel runs: compiled on a TPU, interpreted on the CPU.

Every kernel wrapper takes ``interpret=None`` and resolves it here, so the
CPU test suite runs the kernel bodies in the interpreter while a TPU run
always executes the Mosaic-compiled kernel.  ``interpret=False`` is
allowed anywhere: that is how a CPU host lowers a kernel for a described
(not attached) TPU.
"""

from __future__ import annotations

from typing import Optional

import jax

__all__ = ["resolve_interpret", "vmem_limit", "VMEM_DEFAULT_BYTES", "VMEM_CAP_BYTES"]

# A kernel may use this much VMEM without asking (the scoped default).
VMEM_DEFAULT_BYTES = 16 * 1024 * 1024
# The most a kernel here asks for: a v5e core has 128 MiB of VMEM, and
# Mosaic keeps some of it for its own scratch.
VMEM_CAP_BYTES = 120 * 1024 * 1024


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret unless the default backend is a TPU.

    Asking for the interpreter on a TPU is an error: it would time the
    interpreter, not the kernel.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend: the kernel would run in the "
            "Pallas interpreter; pass interpret=None (or False) to run it "
            "compiled"
        )
    return bool(interpret)


def vmem_limit(nbytes: int, *, what: str) -> int:
    """The scoped-VMEM limit for a kernel that keeps ``nbytes`` resident.

    Raises when the kernel cannot fit under :data:`VMEM_CAP_BYTES`, naming
    ``what`` so the caller knows which operand to tile.
    """
    need = int(nbytes)
    if need > VMEM_CAP_BYTES:
        raise ValueError(
            f"{what} needs {need / 2**20:.1f} MiB of VMEM, more than the "
            f"{VMEM_CAP_BYTES / 2**20:.0f} MiB a kernel may use"
        )
    return max(need, VMEM_DEFAULT_BYTES)
