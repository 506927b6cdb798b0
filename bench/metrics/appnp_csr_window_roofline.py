"""Share of its roofline the APPNP step kernel reaches (kernels layer).

The least time the chip could take for the rows the ACC units delivered
in the traced window (the problem's operations and bytes: SpMM's product
and the teleport term, each written row's row of H read once; never a
slid-back window's extra rows), over the device time of the
``appnp_csr_window`` jitted module in the trace."""


def read(r):
    return r.kernel_roofline_pct("appnp_csr_window")
