"""TPC-H Q3, shipping priority (clause 2.4.3): customer of one market
segment joined to their orders before a date and to those orders' lines
shipped after it, revenue summed per order, the ten largest."""

import numpy as np

from . import column_bytes, days, iso

TABLES = ("customer", "orders", "lineitem")
COLUMNS = {"customer": {"c_custkey": 8, "c_mktsegment": 10},
           "orders": {"o_orderkey": 8, "o_custkey": 8, "o_orderdate": 4,
                      "o_shippriority": 4},
           "lineitem": {"l_orderkey": 8, "l_extendedprice": 8,
                        "l_discount": 8, "l_shipdate": 4}}
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
LIMIT = 10


def draw(rng):
    """2.4.3.3: SEGMENT one of the five market segments, DATE a day of
    March 1995."""
    return {"segment": SEGMENTS[int(rng.integers(0, len(SEGMENTS)))],
            "date": days(1995, 3, 1) + int(rng.integers(0, 31))}


def sql(p):
    """2.4.3.2 with its comma joins written as JOIN ... ON (the engine's
    dialect plans ``FROM a, b`` as a cross product), and 2.1.2.9's "first
    10 rows" as LIMIT."""
    date = iso(p["date"])
    return (
        "SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) "
        "AS revenue,\n       o_orderdate, o_shippriority\n"
        "FROM customer JOIN orders ON c_custkey = o_custkey\n"
        "JOIN lineitem ON l_orderkey = o_orderkey\n"
        f"WHERE c_mktsegment = '{p['segment']}'\n"
        f"  AND o_orderdate < DATE '{date}' AND l_shipdate > DATE '{date}'\n"
        "GROUP BY l_orderkey, o_orderdate, o_shippriority\n"
        "ORDER BY revenue DESC, o_orderdate\n"
        f"LIMIT {LIMIT}")


def reference(tables, p, dtype=np.float64):
    cu, od, li = tables["customer"], tables["orders"], tables["lineitem"]
    segment = np.asarray(cu["c_mktsegment"]).astype(str)
    buyers = cu["c_custkey"][segment == p["segment"]]
    open_order = (od["o_orderdate"] < p["date"]) \
        & np.isin(od["o_custkey"], buyers)
    by_key = np.argsort(od["o_orderkey"], kind="stable")
    keys = od["o_orderkey"][by_key]
    at = np.minimum(np.searchsorted(keys, li["l_orderkey"]), len(keys) - 1)
    line = np.flatnonzero((li["l_shipdate"] > p["date"])
                          & (keys[at] == li["l_orderkey"])
                          & open_order[by_key][at])
    if not len(line):
        return []
    order = by_key[at[line]]                 # the line's row of orders
    rank = np.argsort(order, kind="stable")
    order, line = order[rank], line[rank]
    starts = np.flatnonzero(np.r_[True, order[1:] != order[:-1]])
    price = li["l_extendedprice"][line].astype(dtype)
    disc = li["l_discount"][line].astype(dtype)
    revenue = np.add.reduceat(price * (1 - disc), starts, dtype=dtype)
    order = order[starts]
    # revenue descending, then the order's date
    top = np.lexsort((od["o_orderdate"][order],
                      -revenue.astype(np.float64)))[:LIMIT]
    return [(int(od["o_orderkey"][o]), float(r), int(od["o_orderdate"][o]),
             int(od["o_shippriority"][o]))
            for o, r in zip(order[top], revenue[top])]


def bytes_read(rows):
    return column_bytes(COLUMNS, rows)
