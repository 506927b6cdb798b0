"""Share of the traced window in which no op ran on the device (device
layer): 1 - busy / window, with busy the union of the op intervals on each
ACC device, averaged over the cell's devices."""


def read(r):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.mean_busy_s() / r.trace.window_s)
