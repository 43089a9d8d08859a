from .programs_per_query import traced_metrics


def read(ctx):
    metrics = traced_metrics(ctx)
    if metrics is None:
        return None
    waits = [m["sync"].get("syncWaitS") for m in metrics]
    if any(w is None for w in waits):
        return None
    return sum(waits) * 1e3 / len(waits)
