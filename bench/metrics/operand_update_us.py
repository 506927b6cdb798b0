"""Device microseconds a loop spends putting host rows into the next
operand (operand layer): the device time of the ``put_operand_rows``
jitted modules in the traced window, over the loops of the window (0
where no module of that name ran in it).  The ACC windows write their own
rows inside their kernel and are not counted."""

MODULE = "put_operand_rows"


def read(r):
    if r.trace is None or not r.reports:
        return None
    return 1e6 * r.trace.kernel_seconds(MODULE) / len(r.reports)
