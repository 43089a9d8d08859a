from . import mean_of


def read(ctx):
    return mean_of(ctx, lambda m: m["planTimeS"] * 1e3)
