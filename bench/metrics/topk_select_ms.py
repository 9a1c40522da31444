"""Device time of the top-k threshold selection (``lax.top_k`` inside the
top-k codec), ms per round, client and edge uplinks together."""


def read(ctx):
    return ctx.reduced.matching_ms_per_round(r"jit\(topk_mask\)/top_k")
