"""Where a coalesce sees ONE batch under its target, the target rule of PR 35
changes nothing: the CPU rehearsals of ``tpch_sf1.q1``, ``tpch_sf1.q6`` and
``tpch_sf1_mesh4.q3``, built as ``tests/perfbench`` builds its own (a few
thousand rows, one scan batch a table), dispatch what the parent commit
dispatched. Each records ``last_query_metrics()["programs"]`` of the warm-up
and of the executions after it; every family and every dispatch count has
to equal the map recorded from the parent,
``tests/data/coalesce_bypass_programs.json`` (commit 6d798cf; made by
``python tests/test_coalesce_bypass_cells.py <checkout of the parent>``,
which runs this module's ``record`` against that tree). Counts only: nothing
here is a device number.

What the rehearsal cannot show: ON THE CHIP ``tpch_sf1.q1`` is no bypassing
cell. The autotuned target of its 107-byte rows is 4 Mi rows, so SF1's
6 000 000 lines are TWO scan batches there (4 Mi and 1.8 M), which the parent
concatenated every query and the change aggregates where they lie (PERF.md
section 6, PR 35); ``tests/test_coalesce_target.py`` holds that shape (a full
batch and a short one under a keyed aggregate)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PINNED = os.path.join(ROOT, "tests", "data", "coalesce_bypass_programs.json")
#: cell -> (rows_scale, executions after the warm-up); the mesh cell as
#: tests/perfbench/test_mesh4_q3.py rehearses it
CELLS = {"tpch_sf1.q1": (0.002, 2), "tpch_sf1.q6": (0.002, 2),
         "tpch_sf1_mesh4.q3": (0.01, 1)}
SEED = 2147483731
MESH_WORKERS = 2


def record(cell, root=ROOT):
    """``{"dispatches": [{family: n} per execution], "coalesce": [...]}`` of
    ``cell``'s rehearsal against the tree at ``root`` (the ``coalesce`` report
    is absent from a tree that has none)."""
    if root not in sys.path:
        sys.path.insert(0, root)
    from perfbench import run
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.parallel import mesh as M
    scale, more = CELLS[cell]
    workload = run.load_json("workloads", cell + ".json")
    config = run.load_json("configs", workload["config"] + ".json")
    env = {run.conf_env(k): str(v) for k, v in config["conf"].items()}
    make_mesh = M.make_mesh
    if config["chips"] > 1:
        # small tables would be broadcast: keep the plan SF1 gets, on two
        # of the tests' virtual devices
        env[run.conf_env(
            "spark.rapids.tpu.sql.autoBroadcastJoinThreshold")] = "-1"
        M.make_mesh = lambda n=None: make_mesh(n or MESH_WORKERS)
    before = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        session = TpuSession.builder.config(config["conf"]).getOrCreate()
        traffic = run.Traffic(workload, SEED)
        tables, _ = run.make_tables(config, traffic.query.TABLES, SEED, scale)
        for name, cols in tables.items():
            session.createDataFrame(run.to_arrow(cols)) \
                .createOrReplaceTempView(name)
        dispatches, coalesce = [], []
        for _ in range(int(workload["warmup_executions"]) + more):
            _params, text = traffic.next()
            run.execute(session, text)
            m = session.last_query_metrics()
            dispatches.append({k: v["dispatches"]
                               for k, v in sorted(m["programs"].items())})
            coalesce.append(m.get("coalesce"))
        # the worker as found: the views replaced, the scan cache drained
        for view in tables:
            session.createDataFrame({"x": [0]}).createOrReplaceTempView(view)
            session.sql(f"SELECT count(*) FROM {view}").collect()
        return {"dispatches": dispatches, "coalesce": coalesce}
    finally:
        M.make_mesh = make_mesh
        for k, v in before.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        TpuSession.builder.config(
            {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_bypassing_cell_dispatches_what_the_parent_did(cell):
    with open(PINNED) as f:
        parent = json.load(f)[cell]
    got = record(cell)
    assert len(got["dispatches"]) == len(parent)
    for i, (mine, theirs) in enumerate(zip(got["dispatches"], parent)):
        if i == 0:
            # a family with 0 dispatches is an eager op XLA rebuilt: which
            # ones the FIRST execution rebuilds is the process's history
            mine, theirs = ({k: n for k, n in m.items() if n}
                            for m in (mine, theirs))
        assert sorted(mine) == sorted(theirs), f"execution {i}: families"
        assert mine == theirs, f"execution {i}: dispatches"
    # and the mechanism is bypassed, not merely harmless: no batch is at
    # the target, every coalesce hands on the one batch it was given
    for report in got["coalesce"]:
        assert report["passed"] == 0
        assert report["concatenated"] == report["outputs"] >= 1


if __name__ == "__main__":      # record the pinned maps from a checkout
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    tree = os.path.abspath(sys.argv[1])
    maps = {cell: record(cell, tree)["dispatches"] for cell in CELLS}
    json.dump(maps, sys.stdout, indent=1, sort_keys=True)
