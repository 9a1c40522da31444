"""Device time of the K reference trainings of Eq. 11-12 (the
``ref_train`` scope inside ``round.aggregate``), ms per round, averaged
over the chips: on the mesh engine every chip runs all K of them."""


def read(ctx):
    return ctx.reduced.matching_ms_per_round(r"/ref_train(?:/|$)")
