def read(ctx):
    """Process start to the start of the window."""
    return ctx["setup_s"]
