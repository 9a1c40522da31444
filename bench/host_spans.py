"""Device idle time inside the program's own host spans:
``FLServer.run_round`` opens ``host.dispatch`` (the step call), one
``host.fetch`` per device-to-host read and ``host.account`` as profiler
annotations on the thread that drew the window, so they share the
trace's clock with the device ops."""
from bench.trace import _minus, _union


def idle_ms_per_round(reduced, names):
    """Time inside the host spans named in ``names`` during which no
    operation ran on the device, ms per round, averaged over the
    devices; None when the trace holds no such span or no device."""
    spans = _union([(max(a, reduced.t0), min(b, reduced.t1))
                    for n, a, b in reduced.host if n in names])
    if not spans or not reduced.top:
        return None
    idle = sum(_minus(spans, _union([(a, b) for _, a, b in top]))
               for top in reduced.top)
    return idle / len(reduced.top) / 1e6 / reduced.rounds
