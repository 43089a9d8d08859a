"""perfbench/run.py: one run of one cell of the benchmark.

    python3 perfbench/run.py --workload tpch_sf1.q6 --seed 7 --seconds 48 --trace 0

Builds the cell's tables from ``--seed``, registers them with a
``TpuSession`` under the configuration's confs, warms the cell's own
queries, then drives ``TpuSession.sql(text).collect_batch().fetch_to_host()``
in a closed loop with one client for ``--seconds`` seconds, each execution
with the TPC-H substitution parameters drawn from the seed. After the
window every answer is compared with the plain reference
(``perfbench/queries/``). The last line of stdout is the result.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1`` profiles
the workload's ``traced_queries`` executions with ``jax.profiler`` and
reports its per-layer metrics. Every metric of either kind is read by the
file of its name under ``readers/``.

Exits non-zero, printing no result, on any platform but ``tpu`` or with
fewer chips than the configuration asks for: :func:`main` is what refuses;
:func:`run_cell` is platform-agnostic so the tests can rehearse it.
"""

import time
_PROCESS_START = time.perf_counter()

import argparse
import glob
import importlib
import json
import logging
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import compare, probes, trace_reduce  # noqa: E402
from perfbench.tables import stream  # noqa: E402

#: the conf that makes the program's ``trace_span`` write profiler
#: annotations: a traced run adds it to the configuration's
_TRACING_CONF = "spark.rapids.tpu.sql.tracing.enabled"


def conf_env(key):
    """The environment's name for a conf key. Parts of the program
    (``exec/tracing._tracing_on``, ``plan/physical._matmul_agg_enabled``)
    read a default conf, which sees the environment and not the session:
    a configuration's ``conf`` is therefore set in both."""
    return "SPARK_RAPIDS_TPU_CONF__" + key.upper().replace(".", "__")


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of_cell(entries, cell):
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def make_tables(config, names, seed, rows_scale=1.0):
    """The named tables from the seed. ``rows_scale`` cuts every row count
    alike (the tests' size; the command never passes it)."""
    rows = {t: max(int(n * rows_scale), 1) for t, n in config["rows"].items()}
    tables = {name: importlib.import_module(
        f"perfbench.tables.{name}").generate(rows, seed) for name in names}
    return tables, rows


def to_arrow(cols):
    import numpy as np
    import pyarrow as pa

    def column(name, v):
        if isinstance(v, pa.Array):
            return v
        if v.dtype == np.int32 and name.endswith("date"):
            return pa.array(v, type=pa.date32())
        return pa.array(v)
    return pa.table({k: column(k, v) for k, v in cols.items()})


class Traffic:
    """The cell's executions, a function of the seed alone: the workload's
    query (``queries/<traffic>.py``), each execution with the substitution
    parameters its ``draw`` takes from the seed's stream."""

    def __init__(self, workload, seed):
        self.query = importlib.import_module(
            f"perfbench.queries.{workload['traffic']}")
        self.rng = stream(seed, "traffic")

    def next(self):
        params = self.query.draw(self.rng)
        return params, self.query.sql(params)


def execute(session, text):
    """The timed path: the caller's call and the rows as fetched."""
    return session.sql(text).collect_batch().fetch_to_host().rows()


def device_info(devices):
    peak = 0
    for d in devices:
        peak = max(peak, (d.memory_stats() or {}).get("peak_bytes_in_use", 0))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(workload_name, seed, seconds, trace, rows_scale=1.0,
             out=sys.stdout, err=sys.stderr):
    """One run; returns the result (also printed as the last line of
    ``out``). Platform-agnostic on purpose, see the module's docstring."""
    workload = load_json("workloads", workload_name + ".json")
    config = load_json("configs", workload["config"] + ".json")
    conf = dict(config["conf"])
    if trace:
        conf[_TRACING_CONF] = "true"
    counter = probes.CompileCounter().install()
    fusion = probes.FusionWarnings()
    logging.getLogger(probes.FUSION_LOGGER).addHandler(fusion)
    env_before = {conf_env(k): os.environ.get(conf_env(k)) for k in conf}
    os.environ.update({conf_env(k): str(v) for k, v in conf.items()})
    try:
        return _run(workload_name, workload, config, conf, seed, seconds,
                    trace, rows_scale, counter, fusion, out, err)
    finally:        # a rehearsal leaves its process as it found it
        for k, v in env_before.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        logging.getLogger(probes.FUSION_LOGGER).removeHandler(fusion)
        counter.uninstall()


def _run(workload_name, workload, config, conf, seed, seconds, trace,
         rows_scale, counter, fusion, out, err):
    bench = load_benchmark()
    import jax
    from spark_rapids_tpu.api.session import TpuSession
    devices = jax.devices()
    session = TpuSession.builder.config(conf).getOrCreate()
    print(json.dumps({"compile_cache_dir":
                      jax.config.jax_compilation_cache_dir}), file=err)

    traffic = Traffic(workload, seed)
    query = traffic.query
    tables, rows = make_tables(config, query.TABLES, seed, rows_scale)
    for name, cols in tables.items():
        session.createDataFrame(to_arrow(cols)).createOrReplaceTempView(name)

    done = []            # (params, rows or None) of each execution
    faults = []          # (index into done, what went wrong)

    def one():
        params, text = traffic.next()
        t0 = time.perf_counter()
        try:
            answer = execute(session, text)
        except Exception as e:          # a query that raises has failed
            answer = None
            faults.append((len(done), f"{params}: raised "
                           f"{type(e).__name__}: {e}"[:500]))
        latency = time.perf_counter() - t0
        if answer is not None:
            faults.extend((len(done), f"{params}: {f}")
                          for f in probes.plan_faults(session))
        done.append((params, answer))
        return latency

    for _ in range(int(workload["warmup_executions"])):
        one()
    warm = counter.snapshot()
    print(json.dumps({"setup_compilations": warm}), file=err)

    latencies, query_metrics, reduced = [], [], None
    n_faults, n_warned = len(faults), len(fusion.messages)
    n_warm = len(done)
    if trace:
        from jax.profiler import ProfileOptions
        options = ProfileOptions()
        options.python_tracer_level = 0
        trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        try:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            window_start = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                for _ in range(int(workload["traced_queries"])):
                    with jax.profiler.TraceAnnotation("perfbench_query"):
                        latencies.append(one())
                    query_metrics.append(session.last_query_metrics())
            window_end = time.perf_counter()
            jax.profiler.stop_trace()
            files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                              recursive=True)
            if files:
                reduced = trace_reduce.reduce_trace(
                    trace_reduce.load_xplane(files[0]))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        window_start = time.perf_counter()
        while True:                 # at least the query in flight
            latencies.append(one())
            if time.perf_counter() - window_start >= seconds:
                break
        window_end = time.perf_counter()
    in_window = probes.delta(counter.snapshot(), warm)
    print(json.dumps({"window_compilations": in_window,
                      "latencies_s": [round(t, 4) for t in latencies]}),
          file=err)
    device = device_info(devices)

    # the answers of the run against the plain reference, after the window
    t0 = time.perf_counter()
    answers = [answer for _, answer in done]
    references = [query.reference(tables, params) for params, _ in done]
    correct, checks = compare.judge(answers, references, workload["limits"])
    warned = ["fusion warning: " + m[:500]
              for m in fusion.messages[n_warned:]]
    failed = min(len(latencies),
                 len({i for i, _ in faults[n_faults:]}) + len(warned))
    print(json.dumps({"reference_s": time.perf_counter() - t0,
                      "compared": len(done), "warmup_compared": n_warm,
                      "faults": ([f for _, f in faults] + warned)[:20]}),
          file=err)

    n = len(latencies)
    if trace:
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        elif device["platform"] == "tpu":
            raise SystemExit("the trace holds no device operation")
    peaks = load_json("peaks.json").get(device["kind"])
    if peaks is None and device["platform"] == "tpu":
        raise SystemExit(f"no peaks for device {device['kind']!r}")
    ctx = {"queries": n, "latencies_s": latencies,
           "window_s": window_end - window_start,
           "setup_s": window_start - _PROCESS_START,
           "query_metrics": query_metrics, "compile": in_window,
           "trace": reduced, "peaks": peaks,
           "bytes_per_query": query.bytes_read(rows)}
    values = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in metrics_of_cell(bench[kind], workload_name):
        value = importlib.import_module(
            f"perfbench.readers.{m['name']}").read(ctx)
        if value is not None:
            values[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": n, "failed": failed,
              "metrics": values, "device": device}
    if trace and reduced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r}",
              file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    workload = load_json("workloads", args.workload + ".json")
    config = load_json("configs", workload["config"] + ".json")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < config["chips"]:
        print(f"perfbench: needs {config['chips']} TPU chip(s), found "
              f"{len(devices)} device(s) of platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    run_cell(args.workload, args.seed, args.seconds, args.trace)
    return 0


if __name__ == "__main__":
    sys.exit(main())
