"""Share of the loops' stored entries the ACC units computed (scheduler
layer).

From ``RunReport.per_worker_work``, which the program's ops fill with the
entries of each chunk; nothing to read where no op counts work (a program
without the counter, or a cell without CC units)."""


def read(r):
    work = [getattr(rep, "per_worker_work", None) for rep in r.reports]
    if not r.cc_units or any(w is None for w in work):
        return None
    total = sum(sum(w.values()) for w in work)
    acc = sum(w.get(u, 0) for w in work for u in r.acc_units)
    return 100.0 * acc / total if total else None
