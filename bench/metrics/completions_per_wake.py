"""Completions the dispatcher takes off the ``CompletionBus`` per wake-up
(dispatcher layer): completions drained over returns of
``CompletionBus.wait``, summed over the window's loops, from
``RunReport.timeline``.  One means a wake-up per chunk; more, that
completions queued while the dispatcher was busy.  Nothing to read from a
runtime without a timeline."""


def read(r):
    timelines = [t for t in (getattr(rep, "timeline", None) for rep in r.reports)
                 if t is not None]
    wakeups = sum(t.wakeups for t in timelines)
    return sum(t.drained for t in timelines) / wakeups if wakeups else None
