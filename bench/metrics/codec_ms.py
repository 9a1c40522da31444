"""Device time of the whole uplink codec, ms per round, averaged over the
chips: the client uplinks (``round.compress``: the top-k selection,
error feedback, the Pallas mask and the residual table's gather and
scatter) and the edge uplinks (``edge_codec`` inside
``round.aggregate``)."""


def read(ctx):
    return ctx.reduced.matching_ms_per_round(
        r"(?:^|/)round\.compress(?:/|$)|/edge_codec(?:/|$)")
