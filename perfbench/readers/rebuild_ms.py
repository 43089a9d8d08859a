from .programs_per_query import mean_total


def read(ctx):
    return mean_total(ctx, ("traceS", "lowerS", "compileS", "loadS"), 1e3)
