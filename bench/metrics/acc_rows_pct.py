"""Share of the loops' rows the ACC units computed (scheduler layer).

From ``RunReport.per_worker_items``; nothing to read in a cell without CC
units, where the share is 100% by construction."""


def read(r):
    total = sum(rep.items for rep in r.reports)
    if not r.cc_units or not total:
        return None
    acc = sum(rep.per_worker_items.get(u, 0) for rep in r.reports for u in r.acc_units)
    return 100.0 * acc / total
