"""Mean host microseconds from a unit posting a completion to the
dispatcher taking it off the ``CompletionBus`` (dispatcher layer): the
wake-up of the bus, over every completed chunk of the window, from
``RunReport.timeline``.  Nothing to read from a runtime without a
timeline."""


def read(r):
    total = n = 0
    for rep in r.reports:
        tl = getattr(rep, "timeline", None)
        for c in tl.chunks if tl is not None else ():
            total += c.drained - c.posted
            n += 1
    return total / n / 1e3 if n else None
