"""Device time of the ``round.aggregate`` scope, ms per round: Eq. 5-13
aggregation, which also holds the K reference trainings and, under a
compressed hierarchy, the edge-uplink codec."""


def read(ctx):
    return ctx.reduced.phase_ms_per_round().get("aggregate")
