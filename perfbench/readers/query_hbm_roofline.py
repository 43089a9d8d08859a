def read(ctx):
    """The chip's least time for the bytes the queries must read, as a share
    of the time its ops were busy. Bound by HBM bandwidth: these queries do
    a few operations per byte."""
    if not ctx["trace"] or not ctx["queries"]:
        return None
    least_s = ctx["bytes_per_query"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ctx["trace"]["busy_s"] / ctx["queries"])
