"""Mean host microseconds from an ACC chunk's result being ready to the
same unit's next submit (dispatcher layer): the completion path and the
scheduler's decision between two chunks of one ACC unit in one loop, from
``RunReport.timeline``.  Nothing to read where no ACC unit takes a second
chunk in a loop, or from a runtime without a timeline."""


def read(r):
    acc = set(r.acc_units)
    total = n = 0
    for rep in r.reports:
        tl = getattr(rep, "timeline", None)
        by_unit = {}
        for c in tl.chunks if tl is not None else ():
            if c.unit in acc:
                by_unit.setdefault(c.unit, []).append(c)
        for chunks in by_unit.values():
            chunks.sort(key=lambda c: c.submitted)
            for done, after in zip(chunks, chunks[1:]):
                total += after.submitted - done.ready
                n += 1
    return total / n / 1e3 if n else None
