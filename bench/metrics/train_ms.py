"""Device time of the ``round.train`` scope (client LocalTrain), ms per
round: the round engine's training phase."""


def read(ctx):
    return ctx.reduced.phase_ms_per_round().get("train")
