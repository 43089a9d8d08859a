"""The per-query counters, child spans and scopes of the SPMD mesh stages
(``parallel/mesh.run_stage`` / ``gather_stage``, ``exec/tracing.
MESH_COUNTERS``, docs/observability.md §9) on a hand-sized exchange over
the tests' virtual CPU mesh, and the capacity rule of a stage's input
(``parallel/mesh_exec.stage_capacity`` / ``shard_for_mesh``)."""

import re

import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec import tracing
from spark_rapids_tpu.parallel import mesh as M
from spark_rapids_tpu.parallel import mesh_exec
from spark_rapids_tpu.shuffle.exchange import plane_totals

WORKERS = 4
ROWS = 100                  # a worker's live rows; its capacity is 128
CAP = 128
#: int64 data + validity, float64 data + validity: bytes of one row
ROW_BYTES = 8 + 1 + 8 + 1


def shards(rows=ROWS):
    rng = np.random.default_rng(5)
    return [ColumnarBatch.from_pydict({
        "k": [int(x) for x in rng.integers(0, 50, rows)],
        "v": [float(x) for x in rng.normal(5, 2, rows)]})
        for _ in range(WORKERS)]


def recorded(run, warm=True):
    """``run()`` inside a query's recorders, after one run outside them
    (the call that traces a program reads its constants back): (result,
    mesh counters, span report, sync report)."""
    if warm:
        run()
    rec = tracing.QueryRecording().open()
    try:
        result = run()
    finally:
        rec.close()
    return result, dict(rec.spans.mesh), rec.spans.report(), rec.sync.report()


def test_copartition_exchange_counts_its_bytes_steps_and_one_sync():
    mesh = M.make_mesh(WORKERS)
    M.run_copartition_exchange(mesh, shards(), [0])
    before = plane_totals()
    out, mesh_counters, spans, sync = recorded(
        lambda: M.run_copartition_exchange(mesh, shards(), [0]), warm=False)
    assert sum(b.num_rows for b in out) == WORKERS * ROWS
    # every worker hands the all_to_all one CAP-row slot and one int32
    # count per worker, and all but its own cross a link: rows x widths x
    # workers x (workers - 1)
    ici = WORKERS * (WORKERS - 1) * (CAP * ROW_BYTES + 4)
    assert mesh_counters["stages"] == mesh_counters["iciExchanges"] == 1
    assert mesh_counters["iciBytes"] == ici
    # the home device is one of the workers: three of four shards are
    # copied out (and the four int32 counts are not counted), three of
    # the four WORKERS * CAP-row receive windows are copied back
    assert mesh_counters["placeBytes"] == 3 * CAP * ROW_BYTES
    assert mesh_counters["gatherBytes"] == 3 * WORKERS * CAP * ROW_BYTES
    for step, key in (("mesh_place", "placeS"), ("mesh_spmd", "spmdS"),
                      ("mesh_gather", "gatherS")):
        assert spans[step]["count"] == 1
        assert mesh_counters[key] >= spans[step]["selfS"] > 0
    # the stage's ONE readback, and nothing the counters added
    assert sync["hostSyncs"] == 1
    after = plane_totals()
    assert after["ici_exchanges"] - before["ici_exchanges"] == 1
    assert after["ici_bytes"] - before["ici_bytes"] == ici
    assert after["ici_seconds"] > before["ici_seconds"]


def test_groupby_and_sort_stages_count_like_the_join_exchange():
    mesh = M.make_mesh(WORKERS)
    _, grouped, _, sync = recorded(lambda: M.run_distributed_groupby(
        mesh, shards(), key_idx=[0], val_idx=[1, 1], agg_ops=["sum", "avg"]))
    # payload: the key, and the partials sum, (sum, count) of avg
    partials = (8 + 1) * 3
    assert grouped["iciBytes"] == WORKERS * (WORKERS - 1) * (
        CAP * (8 + 1 + partials) + 4)
    assert (grouped["stages"], sync["hostSyncs"]) == (1, 1)
    _, ordered, _, sync = recorded(lambda: M.run_distributed_sort(
        mesh, shards(), [1], [False], [False]))
    assert ordered["iciBytes"] == WORKERS * (WORKERS - 1) * (
        CAP * ROW_BYTES + 4)
    assert (ordered["stages"], sync["hostSyncs"]) == (1, 1)


def test_counters_read_zero_for_a_query_without_a_stage():
    _, counters, _, _ = recorded(lambda: None)
    assert counters == dict.fromkeys(tracing.MESH_COUNTERS, 0)


def test_spmd_programs_carry_operator_and_stage_scopes():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = M.make_mesh(WORKERS)
    sharded = NamedSharding(mesh, P("workers"))

    def structs(dtypes):
        out = []
        for t in dtypes:
            out += [jax.ShapeDtypeStruct((WORKERS, CAP), t.numpy_dtype,
                                         sharding=sharded),
                    jax.ShapeDtypeStruct((WORKERS, CAP), jnp.bool_,
                                         sharding=sharded)]
        return out + [jax.ShapeDtypeStruct((WORKERS,), jnp.int32,
                                           sharding=sharded)]

    def scopes(fn, dtypes):
        text = fn.lower(*structs(dtypes)).compile().as_text()
        return {m for m in re.findall(r'op_name="[^"]*?/(TpuMesh\w+/\w+)',
                                      text)}

    both = [dt.INT64, dt.FLOAT64]
    assert scopes(M.copartition_exchange_fn(mesh, both, [0], CAP), both) == {
        f"TpuMeshJoinExec/{s}" for s in ("bucket", "all_to_all", "flatten")}
    assert scopes(M.distributed_groupby_fn(
        mesh, [dt.INT64], [dt.FLOAT64], ["sum"], CAP), both) == {
        f"TpuMeshGroupByExec/{s}" for s in (
            "partial_agg", "bucket", "all_to_all", "flatten", "merge_agg")}
    assert scopes(M.distributed_sort_fn(
        mesh, both, [1], (False,), (False,), CAP), both) == {
        f"TpuMeshSortExec/{s}" for s in (
            "sample", "bucket", "all_to_all", "flatten", "local_sort")}


@pytest.mark.parametrize("static_cap, live, cap", [
    (2 << 20, 7_000, 32_768),       # TPC-H Q3's group-by input, SF1 ...
    (2 << 20, 8_200, 32_768),       # ... over every draw: one class
    (2 << 20, 16_384, 32_768),      # the last count that still shrinks
    (2 << 20, 16_385, 2 << 20),     # a filter that keeps more keeps it all
    (2 << 20, 1_500_000, 2 << 20),
    (2 << 20, 200, 512),            # two steps
    (16_384, 75, 256),
    (1_024, 25, 1_024),             # no class below the smallest capacity
    (128, 0, 128),
])
def test_stage_capacity_follows_the_static_bound_in_wide_steps(
        static_cap, live, cap):
    assert mesh_exec.stage_capacity(static_cap, live) == cap


class _Child:
    """A child that hands out prepared partitions."""

    def __init__(self, schema, partitions):
        self.schema = schema
        self._partitions = partitions

    def execute(self):
        return [iter(p) for p in self._partitions]


def test_shards_take_an_n_partition_child_as_it_comes():
    parts = shards()
    parts[2] = ColumnarBatch.from_pydict({"k": [1] * 300, "v": [1.0] * 300})
    child = _Child(parts[0].schema, [[p] for p in parts])
    out = mesh_exec.shard_for_mesh(child, WORKERS)
    assert [b.num_rows for b in out] == [ROWS, ROWS, 300, ROWS]
    assert {b.capacity for b in out} == {512}      # the widest partition's
    assert [b.to_pydict() for b in out] == [p.to_pydict() for p in parts]


def test_shards_cut_any_other_child_into_equal_runs_at_a_static_class():
    parts = shards()
    child = _Child(parts[0].schema, [[parts[0], parts[1]], [parts[2]]])
    out = mesh_exec.shard_for_mesh(child, WORKERS)
    assert [b.num_rows for b in out] == [75] * 4
    assert {b.capacity for b in out} == {128}
    rows = [r for b in out for r in zip(*b.to_pydict().values())]
    assert rows == [r for p in parts[:3]
                    for r in zip(*p.to_pydict().values())]
