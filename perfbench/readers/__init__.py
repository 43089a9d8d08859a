"""One reader per metric, end-to-end or per-layer, found by the metric's name
in ``BENCHMARK.json``: ``read(ctx)`` returns the number, or ``None`` where it
finds nothing to read (the harness then leaves the metric out).

``ctx`` is what the harness gathered over the window (``--trace 0``) or the
traced queries (``--trace 1``): ``queries`` (how many), ``latencies_s`` (of
each), ``window_s`` and ``setup_s`` (host clock), ``query_metrics``
(``last_query_metrics()`` of each traced query), ``compile`` (the harness's
own compile counter over the window), ``trace``
(``trace_reduce.reduce_trace`` or ``None``), ``bytes_per_query`` (the query's
own bytes function) and ``peaks`` (this device's row of ``peaks.json``)."""


def mean_of(ctx, pick):
    values = [pick(m) for m in ctx["query_metrics"]]
    return sum(values) / len(values) if values else None
