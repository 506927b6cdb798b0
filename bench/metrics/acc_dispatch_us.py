"""Mean host time to enqueue one ACC chunk, in microseconds (dispatcher
layer).

``RunReport.dispatch_latency`` of a ``JaxDeviceUnit`` is the mean time of
its ``submit`` call over the unit's chunks in that loop; weighted here by
the chunks each unit took."""


def read(r):
    total = chunks = 0.0
    for rep in r.reports:
        lat = rep.dispatch_latency or {}
        for u in r.acc_units:
            n = rep.per_worker_chunks.get(u, 0)
            if n and u in lat:
                total += lat[u] * n
                chunks += n
    return 1e6 * total / chunks if chunks else None
