def read(ctx):
    if not ctx["queries"]:
        return None
    return ctx["compile"]["compiles"] / ctx["queries"]
