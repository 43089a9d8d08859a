import statistics


def read(ctx):
    """95th percentile of the latencies of all queries of the window; a
    window of fewer than twenty has no such tail."""
    if len(ctx["latencies_s"]) < 20:
        return None
    return statistics.quantiles(ctx["latencies_s"], n=20,
                                method="inclusive")[-1]
