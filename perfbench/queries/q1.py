"""TPC-H Q1, pricing summary report (clause 2.4.1): one scan, a group-by on
two string keys with eight aggregates, a sort of six rows."""

import numpy as np

from . import column_bytes, days, iso, total

TABLES = ("lineitem",)
COLUMNS = {"lineitem": {"l_returnflag": 1, "l_linestatus": 1,
                        "l_quantity": 8, "l_extendedprice": 8,
                        "l_discount": 8, "l_tax": 8, "l_shipdate": 4}}


def draw(rng):
    """2.4.1.3: DELTA in 60..120 days before 1998-12-01."""
    return {"delta": int(rng.integers(60, 121))}


def sql(p):
    return (
        "SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty,\n"
        "       sum(l_extendedprice) AS sum_base_price,\n"
        "       sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,\n"
        "       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) "
        "AS sum_charge,\n"
        "       avg(l_quantity) AS avg_qty, avg(l_extendedprice) "
        "AS avg_price,\n"
        "       avg(l_discount) AS avg_disc, count(*) AS count_order\n"
        f"FROM lineitem WHERE l_shipdate <= DATE "
        f"'{iso(days(1998, 12, 1) - p['delta'])}'\n"
        "GROUP BY l_returnflag, l_linestatus\n"
        "ORDER BY l_returnflag, l_linestatus")


def reference(tables, p, dtype=np.float64):
    li = tables["lineitem"]
    keep = np.flatnonzero(li["l_shipdate"] <= days(1998, 12, 1) - p["delta"])
    # one-character keys as code points: a group is (flag, status); 16-bit
    # codes, which numpy's stable sort takes by radix
    code = (li["l_returnflag"].view(np.int32)[keep] * 128
            + li["l_linestatus"].view(np.int32)[keep]).astype(np.uint16)
    order = np.argsort(code, kind="stable")
    code, kept = code[order], keep[order]
    starts = np.flatnonzero(np.r_[True, code[1:] != code[:-1]])
    ends = np.r_[starts[1:], len(code)]
    qty_i = li["l_quantity"][kept]
    qty = qty_i.astype(dtype)
    price = li["l_extendedprice"][kept].astype(dtype)
    disc = li["l_discount"][kept].astype(dtype)
    tax = li["l_tax"][kept].astype(dtype)
    disc_price = price * (1 - disc)
    charge = disc_price * (1 + tax)
    rows = []
    for a, b in zip(starts, ends):
        n = int(b - a)

        def of(x):
            return total(x[a:b], dtype)

        rows.append((chr(code[a] // 128), chr(code[a] % 128),
                     int(qty_i[a:b].sum()), of(price), of(disc_price),
                     of(charge), of(qty) / n, of(price) / n, of(disc) / n,
                     n))
    return rows


def bytes_read(rows):
    return column_bytes(COLUMNS, rows)
