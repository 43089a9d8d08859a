def read(ctx):
    if not ctx["trace"] or not ctx["queries"]:
        return None
    return ctx["trace"]["busy_s"] * 1e3 / ctx["queries"]
