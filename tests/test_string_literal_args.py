"""A string literal that a comparison holds against a column rides as an
argument of the fused program (``ops/expressions.ordered_params``): another
literal of the same width class runs the program the first one built."""

import pytest

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.ops import expressions as ex

ROWS = [("AIR", 1.0), ("RAIL", 2.0), ("AIR", 3.0), ("TRUCK", 4.0),
        (None, 5.0), ("REG AIR", 6.0), ("", 7.0)]


@pytest.fixture(scope="module")
def session():
    s = TpuSession.builder.config(
        {"spark.rapids.tpu.sql.explain": "NONE"}).getOrCreate()
    s.createDataFrame({"m": [m for m, _ in ROWS],
                       "v": [v for _, v in ROWS]}) \
        .createOrReplaceTempView("ship")
    return s


def built(session):
    """Funnel families the last query traced or compiled anything for."""
    return {family for family, e in
            session.last_query_metrics()["programs"].items()
            if not family.startswith("<eager>") and (
                e["traces"] or e["compiles"] or e["cacheLoads"])}


@pytest.mark.parametrize("op, keep", [
    ("=", lambda m, lit: m == lit), ("<", lambda m, lit: m < lit),
    (">=", lambda m, lit: m >= lit), ("!=", lambda m, lit: m != lit)])
def test_another_literal_runs_the_program_the_first_built(session, op, keep):
    def ask(lit):
        got = session.sql(
            f"SELECT v FROM ship WHERE m {op} '{lit}'").collect()
        want = [(v,) for m, v in ROWS if m is not None and keep(m, lit)]
        assert sorted(got) == want, lit
    ask("AIR")
    # eight bytes, then nine and more (TPC-H Q3's BUILDING, then MACHINERY,
    # travelled as uint8[12] and uint8[20] before): one width class to 32
    for lit in ("RAIL", "TRUCK", "", "REG AIR", "ZZZZZZZZ", "ZZZZZZZZZ",
                "REG AIR, BY 10", "x" * 32):
        ask(lit)
        assert built(session) == set(), lit
    ask("a literal wider than the thirty-two bytes")   # next class: correct
    ask("RAIL")
    assert built(session) == set()


def test_null_safe_equal_and_literal_on_the_left(session):
    from spark_rapids_tpu.api.functions import col, lit
    assert sorted(session.sql(
        "SELECT v FROM ship WHERE 'AIR' = m").collect()) == [(1.0,), (3.0,)]
    ship = session.table("ship")
    for value, want in (("RAIL", [(2.0,)]), ("AIR", [(1.0,), (3.0,)])):
        got = ship.filter(col("m").eqNullSafe(lit(value))).select("v")
        assert sorted(got.collect()) == want
    assert built(session) == set()


def test_only_comparisons_against_the_batch_take_their_literal_traced():
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.ops import predicates as pr
    col = ex.BoundReference(0, dt.STRING, True, "m")
    lit, other = ex.Literal("AIR"), ex.Literal("RAIL")
    folded = pr.EqualTo(ex.Literal("A"), ex.Literal("B"))
    number = pr.EqualTo(ex.BoundReference(1, dt.FLOAT64, True, "v"),
                        ex.Literal(1.0))
    tree = pr.And(pr.And(pr.EqualTo(col, lit), pr.LessThan(other, col)),
                  pr.And(folded, number))
    params = ex.ordered_params([tree])
    assert params == [lit, other] and [p.trace_pos for p in params] == [0, 1]
    assert ex.traced_literal_ids(params) == {id(lit), id(other)}
    first, second = ex.param_arg_values(params)
    assert bytes(first[:3]) == b"AIR" and list(first[3:32]) == [0] * 29
    assert list(first[32:]) == [3, 0, 0, 0]
    assert list(second[32:]) == [4, 0, 0, 0]
    # Q3's five market segments travel in one shape, whatever their length
    assert {ex.string_literal_array(seg).shape for seg in (
        "BUILDING", "AUTOMOBILE", "FURNITURE", "MACHINERY", "HOUSEHOLD")} \
        == {(32 + 4,)}
    assert ex.string_literal_array("x" * 33).shape == (64 + 4,)
    # outside a fused trace the literal is the plan constant it was
    assert lit.eval(None).value == "AIR"
