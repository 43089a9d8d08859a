def read(ctx):
    """The whole window over all its queries: start of the window to the
    end of the last query completed, per query."""
    if not ctx["queries"]:
        return None
    return ctx["window_s"] / ctx["queries"]
