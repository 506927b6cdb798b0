"""Share of its roofline the CSR SpMM ACC kernel reaches (kernels layer).

The least time the chip could take for the rows the ACC units delivered
in the traced window (the algorithm's operations and bytes from the
problem: each stored entry, row pointer and written row once, each
distinct column's dense row once; never a slid-back window's extra rows),
over the device time of the ``spmm_csr_window`` jitted module in the
trace."""


def read(r):
    return r.kernel_roofline_pct("spmm_csr_window")
