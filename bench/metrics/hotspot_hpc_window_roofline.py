"""Share of its roofline the HotSpot ACC kernel reaches (kernels layer).

The least time the chip could take for the rows the ACC units delivered in
the traced window (the algorithm's operations and bytes, from the problem,
never the window's halo rows), over the device time of the
``hotspot_hpc_window`` jitted module in the trace."""


def read(r):
    return r.kernel_roofline_pct("hotspot_hpc_window")
