"""The chip's compiler, asked before the chip is: the main path's programs
compiled for a DESCRIBED TPU v5e (no device attached, nothing runs).

The suite runs on the CPU backend, where the accelerator-only branches —
HBM-sized batch capacities, 64-bit emulation — never build. What the TPU
compiler refuses (a 64-bit float bitcast, a program that does not fit
HBM, a collective it cannot partition) shows here at no chip time. A
compile that passes is not a chip run: ``chip_smoke.py`` is.

Programs WITHOUT a large sort compile at the capacities the chip run
uses (the batch autotuner's pick for a 16 GB chip). Each large sort costs
the TPU compiler 30-100 s whatever else the program holds, so programs
built around one compile here at a capacity under its threshold: a
refusal is a matter of ops and dtypes, not of rows.

The topology is described inside a module-scoped fixture — never at
import, never in a child process: one process at a time may load the
TPU's library, and every xdist worker imports this file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.column import Column

HBM_BYTES = 16 << 30            # one v5e chip
SMALL_SORT = 1 << 12            # under the TPU compiler's big-sort path


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology executable can be written to the persistent
    # cache but not read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    mesh = Mesh(np.array(topo.devices[:4]), ("workers",))
    return mesh, NamedSharding(mesh, P("workers"))


def _column_structs(dtype: dt.DType, cap: int, sharding, lead=()):
    """ShapeDtypeStructs of one Column's flat arrays (data, validity,
    + lengths for strings) at ``cap`` rows."""
    def s(shape, npdt):
        return jax.ShapeDtypeStruct(tuple(lead) + shape, npdt,
                                    sharding=sharding)
    if dtype == dt.STRING:
        return [s((cap, 8), jnp.uint8), s((cap,), jnp.bool_),
                s((cap,), jnp.int32)]
    return [s((cap,), dtype.numpy_dtype), s((cap,), jnp.bool_)]


def _compile(fn, *structs, static_argnums=()):
    return jax.jit(fn, static_argnums=static_argnums).lower(
        *structs).compile()


def _fits_hbm(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes +
             m.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total} bytes do not fit one chip"
    return total


# ---------------------------------------------------------------------------
# The scan: every query starts by carving columns out of one staging buffer
# ---------------------------------------------------------------------------

# lineitem as benchmarks/datagen makes it: 4 int64, 3 float64, 3 dates,
# 3 short strings (data uint8[cap, W] + validity + int32 lengths)
_LINEITEM = ([dt.INT64] * 4 + [dt.FLOAT64] * 3 + [dt.STRING] * 2 +
             [dt.DATE] * 3 + [dt.STRING])


def _autotuned_rows(schema: dt.Schema, monkeypatch) -> int:
    """tuned_batch_rows as the chip run sees it: a 16 GB device."""
    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.plan import stage_compiler as sc
    conf = cfg.TpuConf()
    monkeypatch.setattr(
        sc, "_device_budget_bytes",
        lambda: int(HBM_BYTES * float(conf.get(cfg.ALLOC_FRACTION))))
    sc.reset_tuning_cache()
    try:
        return sc.tuned_batch_rows(conf, schema)
    finally:
        sc.reset_tuning_cache()


@pytest.mark.parametrize("rows", [1 << 20, "autotuned"])
def test_scan_unpack_of_lineitem_compiles(one_chip, monkeypatch, rows):
    """bytes -> int64 / float64 / date / string columns: the one
    direction of 64-bit bitcast the TPU's x64 rewrite implements."""
    from spark_rapids_tpu.columnar.batch import (_staging_spec,
                                                 _unpack_program)
    schema = dt.Schema([dt.Field(f"c{i}", t)
                        for i, t in enumerate(_LINEITEM)])
    if rows == "autotuned":
        rows = _autotuned_rows(schema, monkeypatch)
        assert rows >= 1 << 21           # the chip-sized branch, not CPU's
    metas = [(a.dtype, a.shape) for t in _LINEITEM
             for a in _column_structs(t, rows, None)]
    spec, total = _staging_spec(metas)
    program = _unpack_program(spec, total)._fn       # the jitted unpack
    buf = jax.ShapeDtypeStruct((total,), jnp.uint8, sharding=one_chip)
    compiled = program.lower(buf).compile()
    assert _fits_hbm(compiled) >= total


# ---------------------------------------------------------------------------
# Aggregation: the one group-by, ``groupby_aggregate``
# ---------------------------------------------------------------------------

def test_graft_entry_stage_compiles_and_fits_hbm(one_chip):
    """filter -> project -> group-by, the stage ``__graft_entry__.entry()``
    builds, at the autotuner's ceiling (1 << 23 rows; int64 keys, float64
    values): the sort, both sides of the choice by the group count."""
    import __graft_entry__
    cap = 1 << 23
    fused, example = __graft_entry__.entry()
    structs = [jax.ShapeDtypeStruct((cap,) * a.ndim, a.dtype,
                                    sharding=one_chip) for a in example]
    _fits_hbm(_compile(fused, *structs))


def test_many_group_scatter_side_of_q1s_group_by_compiles(one_chip):
    """q1's shape (two string keys, bigint and double sums, an average,
    two counts) through ``groupby_aggregate`` above FEW_GROUPS_MAX slots:
    the program holds the choice, and its many-group side is scatters."""
    from spark_rapids_tpu.ops import aggregates as agg_k
    cap = SMALL_SORT
    assert cap > agg_k.FEW_GROUPS_MAX

    def q1_kernel(num_rows, *arrays):
        flag = Column(dt.STRING, *arrays[0:3])
        status = Column(dt.STRING, *arrays[3:6])
        qty = Column(dt.INT64, *arrays[6:8])
        price = Column(dt.FLOAT64, *arrays[8:10])
        specs = [agg_k.AggSpec(op, col) for op, col in (
            ("sum", qty), ("sum", price), ("avg", price), ("count", qty),
            ("count_star", None))]
        _keys, aggs, n_groups = agg_k.groupby_aggregate(
            [flag, status], specs, num_rows, cap)
        return tuple(a.data for a in aggs) + (n_groups,)

    structs = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)]
    for t in (dt.STRING, dt.STRING, dt.INT64, dt.FLOAT64):
        structs += _column_structs(t, cap, one_chip)
    text = _compile(q1_kernel, *structs).as_text()
    assert " conditional(" in text
    assert sum(" scatter(" in line for line in text.splitlines()) > 1


def test_few_group_masked_reductions_compile_with_no_scatter(one_chip):
    """q1's exact aggregates (float64 sum and avg, bigint sum, count) as
    the few-group branch of ``groupby_aggregate`` runs them, at the chip
    run's 8 Mi-row batch and in ROW order (PR 28): the columns come as
    they lie, the segment ids are carried to the rows. The one scatter the
    TPU compiler builds is that of the int32 ids — no scatter-add — and
    nothing of FEW_GROUPS_MAX x rows is among its temporaries (128 x 8 Mi
    float64 would be 8 GB), nor a sorted copy of a column."""
    from spark_rapids_tpu.ops import aggregates as agg_k
    from spark_rapids_tpu.ops import kernels as K
    cap = 1 << 23

    def few(order, sorted_ids, n_groups, num_rows, qty, qty_valid, price,
            price_valid):
        seg_ids = K.segment_ids_by_row(sorted_ids, order)
        live = jnp.arange(cap) < num_rows
        qty = Column(dt.INT64, qty, qty_valid)
        price = Column(dt.FLOAT64, price, price_valid)
        outs = []
        for op, col in (("sum", qty), ("sum", price), ("avg", price),
                        ("count", qty), ("count_star", None),
                        ("min", price)):
            agg = agg_k.segment_aggregate(
                agg_k.AggSpec(op, col), seg_ids, live, cap,
                num_segments=agg_k.FEW_GROUPS_MAX, n_groups=n_groups)
            outs += agg.arrays()
        return tuple(outs)

    def s(npdt, shape=(cap,)):
        return jax.ShapeDtypeStruct(shape, npdt, sharding=one_chip)
    compiled = _compile(few, s(jnp.int32), s(jnp.int32), s(jnp.int32, ()),
                        s(jnp.int32, ()), s(jnp.int64), s(jnp.bool_),
                        s(jnp.float64), s(jnp.bool_))
    scatters = [line for line in compiled.as_text().splitlines()
                if " scatter(" in line]                 # the HLO op
    assert len(scatters) == 1 and "s32[8388608]" in scatters[0], scatters
    # 136.6 MB; with the four arrays gathered into sort order first, as
    # before PR 28, 153.8 MB (this compiler, sandbox)
    assert compiled.memory_analysis().temp_size_in_bytes < 18 * cap


# ---------------------------------------------------------------------------
# Join, sort, hash partitioning
# ---------------------------------------------------------------------------

def test_sort_merge_join_kernels_compile(one_chip):
    """join_match (sort the build side, rank the stream keys among the
    build keys by one stable sort of both) and join_gather (expand the
    matches) on int64 keys."""
    from spark_rapids_tpu.ops import joins as J
    cap = SMALL_SORT

    def join(n_build, n_stream, bk, bv, sk, sv, pay, payv):
        build, stream = Column(dt.INT64, bk, bv), Column(dt.INT64, sk, sv)
        m = J.join_match([build], n_build, [stream], n_stream, cap)
        s_out, b_out, total = J.join_gather(
            m, [stream, Column(dt.FLOAT64, pay, payv)], [build], 2 * cap)
        return (s_out[0].data, s_out[1].data, b_out[0].data, total)

    def s(npdt, shape=(cap,)):
        return jax.ShapeDtypeStruct(shape, npdt, sharding=one_chip)
    _compile(join, s(jnp.int32, ()), s(jnp.int32, ()),
             s(jnp.int64), s(jnp.bool_), s(jnp.int64), s(jnp.bool_),
             s(jnp.float64), s(jnp.bool_))


def test_lexsort_over_int64_and_float64_keys_compiles(one_chip):
    """ORDER BY revenue DESC, orderdate: the orderable-word encoding
    keeps floats AS floats (no f64 bitcast) and sign-flips ints."""
    from spark_rapids_tpu.ops import kernels as K
    cap = SMALL_SORT

    def order_by(num_rows, f, fv, i, iv):
        keys = [K.SortKey(Column(dt.FLOAT64, f, fv), False, False),
                K.SortKey(Column(dt.INT64, i, iv), True, True)]
        return K.sort_indices(keys, num_rows, cap)

    def s(npdt, shape=(cap,)):
        return jax.ShapeDtypeStruct(shape, npdt, sharding=one_chip)
    _compile(order_by, s(jnp.int32, ()), s(jnp.float64), s(jnp.bool_),
             s(jnp.int64), s(jnp.bool_))


def test_f64_to_int_bitcast_is_what_the_chip_refuses(one_chip):
    """The hazard itself, pinned: should a later compiler accept it, this
    fails and the arithmetic ``_float64_bits`` can go."""
    x = jax.ShapeDtypeStruct((1024,), jnp.float64, sharding=one_chip)
    with pytest.raises(Exception, match="X64"):
        _compile(lambda v: jax.lax.bitcast_convert_type(v, jnp.int64), x)


def test_murmur3_partitioning_of_int64_and_float64_keys_compiles(one_chip):
    """Hash partitioning of a (bigint, double, string) key at a chip-sized
    batch: the double hashes its IEEE bits, taken by arithmetic."""
    from spark_rapids_tpu.ops.hashing import murmur3_batch
    cap = 1 << 20

    def pids(*arrays):
        cols = [Column(dt.INT64, *arrays[0:2]),
                Column(dt.FLOAT64, *arrays[2:4]),
                Column(dt.STRING, *arrays[4:7])]
        return jnp.mod(murmur3_batch(cols, cap), 8)

    structs = []
    for t in (dt.INT64, dt.FLOAT64, dt.STRING):
        structs += _column_structs(t, cap, one_chip)
    _fits_hbm(_compile(pids, *structs))


def test_float64_bits_match_numpy_on_the_cpu():
    """The arithmetic the chip compiles is bit-exact where f64 is IEEE."""
    from spark_rapids_tpu.ops.hashing import _float64_bits
    rng = np.random.default_rng(0)
    x = np.concatenate([
        rng.integers(-2 ** 63, 2 ** 63 - 1, 20000).view(np.float64),
        rng.normal(0, 1e6, 2000),
        [0.0, 1.0, -1.0, 0.5, 0.07, 123456.78, np.inf, -np.inf, np.nan,
         2.2250738585072014e-308, 1.7976931348623157e308]])
    x = x[~((np.abs(x) < 2.0 ** -1022) & (x != 0))]     # XLA flushes these
    want = x.view(np.int64).copy()
    want[np.isnan(x)] = 0x7FF8_0000_0000_0000           # canonical NaN
    got = np.asarray(jax.jit(_float64_bits)(jnp.asarray(x)))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Four chips: the SPMD stages, one program across the 2x2 mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stage", ["groupby", "copartition", "sort",
                                   "partition_exchange",
                                   "copartition_lineitem"])
def test_mesh_stage_compiles_with_an_all_to_all(four_chips, stage):
    """Each mesh pipeline is ONE program whose exchange is an all-to-all
    over ICI, sharded over the four chips, within each chip's HBM. The
    exchange itself (bucket, all_to_all, flatten: PR 33) holds no sort, so
    the join's stage compiles at the chip run's own shape too: lineitem's
    four columns of Q3 at 2 Mi rows a worker, an 8 Mi-row window."""
    from spark_rapids_tpu.parallel import mesh as M
    mesh, sharded = four_chips
    cap = SMALL_SORT // 4                # receive windows are 4 x cap
    dtypes = [dt.INT64, dt.FLOAT64, dt.STRING]
    extra = []
    if stage == "groupby":
        fn = M.distributed_groupby_fn(mesh, [dt.INT64, dt.STRING],
                                      [dt.FLOAT64, dt.INT64],
                                      ["sum", "avg"], cap)
        dtypes = [dt.INT64, dt.STRING, dt.FLOAT64, dt.INT64]
    elif stage == "copartition":
        fn = M.copartition_exchange_fn(mesh, dtypes, [0], cap)
    elif stage == "copartition_lineitem":
        cap = 1 << 21
        dtypes = [dt.INT64, dt.FLOAT64, dt.FLOAT64, dt.DATE]
        fn = M.copartition_exchange_fn(mesh, dtypes, [0], cap)
    elif stage == "partition_exchange":
        fn = M.partition_exchange_fn(mesh, dtypes, cap, 16)
        extra = [jax.ShapeDtypeStruct((4, cap), jnp.int32,
                                      sharding=sharded)]      # the pids
    else:
        fn = M.distributed_sort_fn(mesh, dtypes, [1, 0], (False, True),
                                   (False, True), cap)
    structs = [a for t in dtypes
               for a in _column_structs(t, cap, sharded, lead=(4,))]
    structs += extra
    structs.append(jax.ShapeDtypeStruct((4,), jnp.int32, sharding=sharded))
    compiled = fn.lower(*structs).compile()
    text = compiled.as_text()
    assert "all-to-all" in text
    if stage.startswith("copartition"):
        assert " sort(" not in text          # a counting pass, not a sort
    _fits_hbm(compiled)                      # memory_analysis is per device
