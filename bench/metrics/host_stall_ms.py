"""Device idle time inside the ``host.dispatch`` and ``host.account``
spans of ``run_round``, ms per round, averaged over the chips: the chip
waiting on host work, launching the step and the float64 accounting."""
from bench.host_spans import idle_ms_per_round


def read(ctx):
    return idle_ms_per_round(ctx.reduced, ("host.dispatch", "host.account"))
