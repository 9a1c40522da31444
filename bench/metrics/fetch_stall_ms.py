"""Device idle time inside the ``host.fetch`` spans of ``run_round``, ms
per round, averaged over the chips: the chip waiting while the host
reads the round's results back (the transfer and its latency after the
step ends)."""
from bench.host_spans import idle_ms_per_round


def read(ctx):
    return idle_ms_per_round(ctx.reduced, ("host.fetch",))
