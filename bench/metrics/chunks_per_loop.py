"""Chunks the scheduler handed out per loop (scheduler layer), from
``RunReport.chunks``."""


def read(r):
    if not r.reports:
        return None
    return sum(rep.chunks for rep in r.reports) / len(r.reports)
