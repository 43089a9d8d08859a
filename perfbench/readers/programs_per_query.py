"""Programs dispatched per query, and what the four readers of the
program's own counters share: the ``programs`` map and the timed readbacks
of ``last_query_metrics()``. A program without them (a commit before they
existed) gives ``None``, and the harness leaves the metric out.

The four are per-layer metrics of a TRACED CHIP RUN and are read only
beside a device trace (``ctx["trace"]``). A rehearsal off the chip has none,
and goes on reporting exactly the program-side metrics that
``tests/perfbench/test_perfbench.py::
test_traced_rehearsal_reports_the_program_side_metrics`` holds it to — a
file the change that added these may not edit (PERF.md section 7, "For the
next benchmark issue")."""


def traced_metrics(ctx):
    """``last_query_metrics()`` of each traced query, or ``None`` where the
    run has no device trace or no traced query."""
    if not ctx["trace"] or not ctx["query_metrics"]:
        return None
    return ctx["query_metrics"]


def mean_total(ctx, fields, scale=1.0):
    """Mean over the traced queries of the sum of ``fields`` over every
    family of a query's ``programs`` map."""
    metrics = traced_metrics(ctx)
    if metrics is None:
        return None
    maps = [m.get("programs") for m in metrics]
    if any(p is None for p in maps):
        return None
    return scale * sum(entry.get(f, 0) for p in maps for entry in p.values()
                       for f in fields) / len(maps)


def read(ctx):
    return mean_total(ctx, ("dispatches",))
