"""Host microseconds per loop that ``parallel_for`` spends on work that does
not scale with its chunks (runtime layer): the dispatcher's ``units_start``
(building the backends and starting their threads), ``units_close``
(joining them) and ``report`` (building the scheduler and the
``RunReport``) phases, from ``RunReport.timeline.phase_s``; mean over the
window's loops.  Nothing to read from a runtime without a timeline."""

FIXED = ("units_start", "units_close", "report")


def read(r):
    timelines = [t for t in (getattr(rep, "timeline", None) for rep in r.reports)
                 if t is not None]
    if not timelines:
        return None
    return 1e6 * sum(t.phase_s.get(p, 0.0) for t in timelines for p in FIXED) / len(timelines)
