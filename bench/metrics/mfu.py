"""Model FLOP/s utilisation of the whole round, %: the forward+backward
operations the round's LocalTrain calls require (bench.roofline), times
the traced window's rounds per second, over chips x peak FLOP/s."""


def read(ctx):
    rate = ctx.rounds / ctx.window_s
    return 100.0 * ctx.model_flops_per_round * rate / (ctx.chips * ctx.peaks["flops"])
