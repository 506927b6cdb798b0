"""Host nanoseconds a CC unit spends per stored entry (CC units layer):
the CC units' busy seconds (``RunReport.per_worker_busy``) over the
entries their ops counted (``RunReport.per_worker_work``); nothing to read
where no op counts work or the CC units took none."""


def read(r):
    busy = work = 0
    for rep in r.reports:
        counted = getattr(rep, "per_worker_work", None)
        if counted is None:
            return None
        busy += sum(rep.per_worker_busy.get(u, 0.0) for u in r.cc_units)
        work += sum(counted.get(u, 0) for u in r.cc_units)
    return 1e9 * busy / work if work else None
