"""Float64 predicates (ROADMAP M1): a comparison of a float64 column with a
float64 literal gives what IEEE float64 gives on the host, whatever path the
literal takes onto the device: a Parameter bound by the plan cache, a plain
Literal with the cache off, the fused stage or the eager per-op filter, a
scanned column or a computed one.

On the CPU backend every route is exact, so the answers here pin the
semantics; what made the TPU wrong was the ROUTE (a float64 column is bytes
bitcast on the device, a float64 scalar argument or constant is split into
its float32 pair by the host or the compiler, and the two splits differ).
``test_route_*`` therefore assert on the traced programs themselves, which
needs no chip: the literal reaches the compare as eight bytes through the
same ``bitcast_convert_type`` as the scan unpack's columns, never as a bare
float64 scalar argument or constant.
"""

import re

import jax
import numpy as np
import pytest

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (Column, Scalar, device_scalar,
                                              float64_words)
from spark_rapids_tpu.ops import expressions as ex
from spark_rapids_tpu.ops import predicates as pr
from spark_rapids_tpu.plan.physical import FusedStage

_FUSION_ENV = "SPARK_RAPIDS_TPU_CONF__SPARK__RAPIDS__TPU__SQL__" \
              "WHOLESTAGEFUSION__ENABLED"
MAX = 1.7976931348623157e308
TINY = 2.2250738585072014e-308           # least normal; XLA:CPU flushes below
HUNDREDTHS = [i / 100 for i in range(11)]
#: SQL text of a literal -> its float64
SPECIALS = {"-0.0": -0.0, f"{MAX!r}": MAX, f"-{MAX!r}": -MAX,
            f"{TINY!r}": TINY, "CAST('NaN' AS DOUBLE)": float("nan"),
            "CAST('Infinity' AS DOUBLE)": float("inf"),
            "CAST('-Infinity' AS DOUBLE)": float("-inf")}
LITERALS = {**{f"{v:.2f}": v for v in HUNDREDTHS}, **SPECIALS}
#: off the default path (cache off: a program per literal; eager: a dispatch
#: per node) fewer of them
FEW = {k: LITERALS[k] for k in ("0.05", "0.10", "-0.0", f"{MAX!r}",
                                "CAST('NaN' AS DOUBLE)")}


def _column(seed=11, n=400):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 11, n) / 100.0
    x[:12] = [0.0, -0.0, np.nan, np.inf, -np.inf, MAX, -MAX, TINY, -TINY,
              1.0 / 3, 0.05, 0.07]
    return x


def _spark(op, col, v):
    """numpy's answer under Spark's NaN rules (NaN = NaN, NaN greatest);
    without a NaN on either side it IS numpy's comparison."""
    isn, vn = np.isnan(col), np.isnan(v)
    with np.errstate(invalid="ignore"):
        eq = (col == v) | (isn & vn)
        lt = (col < v) | (vn & ~isn)
    return {"=": eq, "<>": ~eq, "<": lt, "<=": lt | eq, ">": ~(lt | eq),
            ">=": ~lt}[op]


@pytest.fixture(params=["cache_on-fused", "cache_on-eager",
                        "cache_off-fused", "cache_off-eager"])
def mode(request, monkeypatch):
    cache, fused = request.param.split("-")
    if fused == "eager":
        # operators read a default conf, which sees the environment
        monkeypatch.setenv(_FUSION_ENV, "false")
    x = _column()
    session = TpuSession.builder.config({
        "spark.rapids.tpu.sql.explain": "NONE",
        "spark.rapids.tpu.sql.wholeStageFusion.enabled":
            "true" if fused == "fused" else "false",
        "spark.rapids.tpu.sql.planCache.enabled":
            "true" if cache == "cache_on" else "false"}).getOrCreate()
    session.createDataFrame(
        {"x": x, "z": np.zeros(len(x)), "k": np.arange(len(x))}
    ).createOrReplaceTempView("t")
    return session, x, (LITERALS if request.param == "cache_on-fused"
                        else FEW), cache == "cache_on", fused == "fused"


def _count(session, where):
    return session.sql(f"SELECT count(*) FROM t WHERE {where}").collect()[0][0]


@pytest.mark.parametrize("op", ["=", "<>", "<", "<=", ">", ">="])
@pytest.mark.parametrize("column", ["x", "(x + z)"],
                         ids=["scanned", "computed"])
def test_comparison_equals_numpy(mode, op, column):
    session, x, literals, cached, fused = mode
    for text, v in literals.items():
        want = int(_spark(op, x, v).sum())
        assert _count(session, f"{column} {op} {text}") == want, (op, text)
    assert _count(session, f"{column} {op} 0.05") == \
        int(_spark(op, x, 0.05).sum())
    m = session.last_query_metrics()
    assert m["planCache"]["params"] == (1 if cached else 0)
    assert m["planCache"]["hit"] == (1 if cached else 0)
    # the filter ran inside the aggregate's fused program, or eagerly
    assert any("pre_stage" in k and v["dispatches"]
               for k, v in m["programs"].items()) == fused, m["programs"]
    session.assert_on_tpu()


@pytest.mark.parametrize("column", ["x", "(x + z)"],
                         ids=["scanned", "computed"])
def test_between_and_in_equal_numpy(mode, column):
    session, x, literals, _cached, _fused = mode
    plain = [(t, v) for t, v in literals.items() if not np.isnan(v)]
    for (lo_t, lo), (hi_t, hi) in zip(plain, plain[2:]):
        with np.errstate(invalid="ignore"):
            want = int(((x >= lo) & (x <= hi)).sum())
        got = _count(session, f"{column} BETWEEN {lo_t} AND {hi_t}")
        assert got == want, (lo_t, hi_t)
    # IN is IEEE equality (a NaN matches nothing), as numpy.isin
    for texts in (("0.05",), ("0.00", "0.10", "0.07"), ("-0.0", f"{MAX!r}")):
        want = int(np.isin(x, [LITERALS[t] for t in texts]).sum())
        got = _count(session, f"{column} IN ({', '.join(texts)})")
        assert got == want, texts
    session.assert_on_tpu()


def test_product_against_literal_equals_numpy(mode):
    session, x, _literals, _cached, _fused = mode
    rng = np.random.default_rng(5)
    price = rng.integers(90000, 10500000, 300) / 100.0
    disc = rng.integers(0, 11, 300) / 100.0
    session.createDataFrame({"p": price, "d": disc}
                            ).createOrReplaceTempView("li")
    for bound in (100.0, 2500.0, 0.0):
        got = session.sql("SELECT count(*) FROM li WHERE p * d > "
                          f"{bound}").collect()[0][0]
        assert got == int((price * disc > bound).sum())


def test_q6_boundary_rows_are_kept():
    """BETWEEN's boundary rows: what the fault dropped on the chip."""
    from perfbench.queries import q6
    from perfbench.run import load_json, make_tables, to_arrow
    config = load_json("configs", "tpch_sf1.json")
    tables, _rows = make_tables(config, q6.TABLES, 2147483999, 0.001)
    session = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    session.createDataFrame(to_arrow(tables["lineitem"])
                            ).createOrReplaceTempView("lineitem")
    seen = []
    for params in ({"year": 1994, "discount_pct": 6, "quantity": 24},
                   {"year": 1997, "discount_pct": 2, "quantity": 25}):
        got = session.sql(q6.sql(params)).collect()[0][0]
        want = q6.reference(tables, params)[0][0]
        assert abs(got - want) <= 1e-12 * abs(want)
        m = session.last_query_metrics()
        seen.append((m["planCache"], m["scan"]))
    # two dates, two discounts and the quantity ride as bound parameters;
    # the second draw is served by the plan cache from the resident table
    assert seen == [({"hit": 0, "params": 5},
                     {"batches": 1, "uploadedBatches": 1}),
                    ({"hit": 1, "params": 5},
                     {"batches": 1, "uploadedBatches": 0})]


# -- the route, readable without a chip --------------------------------------

def _batch(n=128):
    x = np.zeros(n)
    return ColumnarBatch(
        dt.Schema([dt.Field("x", dt.FLOAT64)]),
        [Column.from_numpy(x, dt.FLOAT64, capacity=n)], n)


def _lowered(stage, batch):
    args = (np.int32(batch.num_rows), *batch.flat_arrays(),
            *ex.param_arg_values(stage._params))
    return stage._build().lower(*args).as_text()


def _main_signature(text):
    return re.search(r"func\.func public @main\((.*?)\)\s*->", text,
                     re.S).group(1)


_F64_SCALAR_CONSTANT = re.compile(
    r"stablehlo\.constant dense<(?!0\.0+e\+00>)[^>]*> : tensor<f64>")


@pytest.mark.parametrize("literal", ["parameter", "literal", "in_list"])
def test_route_literal_reaches_compare_as_bytes(literal):
    """The column is float64 bitcast from bytes by the scan unpack; the
    literal must be made the same way inside the fused program: eight bytes
    (an argument for a bound Parameter, a constant behind a barrier for a
    plain Literal), then ``bitcast_convert``. A float64 scalar argument or
    a float64 constant is the route that split the pair differently."""
    ref = ex.BoundReference(0, dt.FLOAT64, col_name="x")
    if literal == "parameter":
        cond = pr.GreaterThanOrEqual(ref, ex.Parameter(0.05, dt.FLOAT64,
                                                       slot=0))
    elif literal == "literal":
        cond = pr.EqualTo(ref, ex.Literal(0.05))
    else:
        cond = pr.In(ref, [0.05, 0.07])
    batch = _batch()
    stage = FusedStage([cond], batch.schema, batch.schema, mode="filter")
    text = _lowered(stage, batch)
    signature = _main_signature(text)
    assert "tensor<f64>" not in signature, signature
    assert not _F64_SCALAR_CONSTANT.search(text), \
        _F64_SCALAR_CONSTANT.search(text).group(0)
    assert re.search(r"stablehlo\.bitcast_convert.*tensor<8xui8>\) -> "
                     r"tensor<f64>", text)
    if literal == "parameter":
        assert "tensor<8xui8>" in signature
        assert [a.dtype for a in ex.param_arg_values(stage._params)] == \
            [np.uint8]
    else:
        assert "optimization_barrier" in text
        assert "tensor<8xui8>" not in signature
    # and the program still answers
    cols, count = stage(batch)
    assert int(count) == (0 if literal != "parameter" else 0)


def test_route_eager_scalar_is_bitcast_from_bytes():
    """The eager filter's scalar: bytes uploaded, float64 made on the
    device; a traced value (a Parameter inside a fused trace) passes."""
    jaxpr = jax.make_jaxpr(lambda: device_scalar(0.05, np.float64))()
    prims = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert prims == ["optimization_barrier", "bitcast_convert_type"], prims
    assert float(device_scalar(0.05, np.float64)) == 0.05
    traced = jax.make_jaxpr(lambda v: device_scalar(v, np.float64))(
        np.float64(0.05))
    assert [e.primitive.name for e in traced.jaxpr.eqns] == []
    assert device_scalar(7, np.int64).dtype == np.int64
    data, valid = ex.data_validity(Scalar(0.07, dt.FLOAT64), dt.FLOAT64)
    assert float(data) == 0.07 and valid is True
    assert float64_words(0.05).tobytes() == np.float64(0.05).tobytes()


def test_route_host_built_column_takes_the_unpack():
    """A float64 column the host builds (from_pydict, a parsed string cast)
    reaches the device through the staging buffer's unpack, as a scanned
    one does; other dtypes upload as they were."""
    from spark_rapids_tpu.columnar import batch as cb
    calls = []
    real = cb._upload_packed

    def spy(hosts):
        calls.append([str(a.dtype) for _d, arrs in hosts for a in arrs])
        return real(hosts)
    cb._upload_packed = spy
    try:
        c = Column.from_numpy(np.array([0.05, 1.0 / 3, np.nan]), dt.FLOAT64)
        i = Column.from_numpy(np.array([1, 2, 3]), dt.INT64)
    finally:
        cb._upload_packed = real
    assert calls == [["float64", "bool"]]
    assert c.to_pylist(3)[:2] == [0.05, 1.0 / 3] and i.to_pylist(3) == [1, 2, 3]
