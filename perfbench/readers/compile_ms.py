def read(ctx):
    if not ctx["queries"]:
        return None
    return ctx["compile"]["compile_s"] * 1e3 / ctx["queries"]
