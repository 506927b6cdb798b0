"""Host microseconds a CC unit spends per row (CC units layer): the CC
units' busy seconds (``RunReport.per_worker_busy``) over their rows."""


def read(r):
    busy = sum(rep.per_worker_busy.get(u, 0.0) for rep in r.reports for u in r.cc_units)
    rows = sum(rep.per_worker_items.get(u, 0) for rep in r.reports for u in r.cc_units)
    return 1e6 * busy / rows if rows else None
