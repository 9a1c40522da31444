"""Collective time during which nothing else runs on the chip, ms per
round, averaged over the chips: the mesh engine's psums not hidden by
compute."""


def read(ctx):
    return ctx.reduced.collective_exposed_ms_per_round()
