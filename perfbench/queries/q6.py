"""TPC-H Q6, forecasting revenue change (clause 2.4.6): one scan, one
filter, one global sum."""

import numpy as np

from . import column_bytes, days, total

TABLES = ("lineitem",)
COLUMNS = {"lineitem": {"l_shipdate": 4, "l_discount": 8, "l_quantity": 8,
                        "l_extendedprice": 8}}


def draw(rng):
    """2.4.6.3: DATE the first of January of a year in 1993..1997, DISCOUNT
    in 0.02..0.09, QUANTITY 24 or 25. The discount is kept in hundredths:
    ``0.06 - 0.01`` in float64 is not 0.05, and qgen writes the bounds."""
    return {"year": int(rng.integers(1993, 1998)),
            "discount_pct": int(rng.integers(2, 10)),
            "quantity": int(rng.integers(24, 26))}


def sql(p):
    return (
        "SELECT sum(l_extendedprice * l_discount) AS revenue\n"
        "FROM lineitem\n"
        f"WHERE l_shipdate >= DATE '{p['year']}-01-01' "
        f"AND l_shipdate < DATE '{p['year'] + 1}-01-01'\n"
        f"  AND l_discount BETWEEN {(p['discount_pct'] - 1) / 100:.2f} "
        f"AND {(p['discount_pct'] + 1) / 100:.2f} "
        f"AND l_quantity < {p['quantity']}")


def reference(tables, p, dtype=np.float64):
    li = tables["lineitem"]
    lo = float(f"{(p['discount_pct'] - 1) / 100:.2f}")
    hi = float(f"{(p['discount_pct'] + 1) / 100:.2f}")
    keep = ((li["l_shipdate"] >= days(p["year"], 1, 1))
            & (li["l_shipdate"] < days(p["year"] + 1, 1, 1))
            & (li["l_discount"] >= lo) & (li["l_discount"] <= hi)
            & (li["l_quantity"] < p["quantity"]))
    if not keep.any():
        return [(None,)]
    price = li["l_extendedprice"][keep].astype(dtype)
    disc = li["l_discount"][keep].astype(dtype)
    return [(total(price * disc, dtype),)]


def bytes_read(rows):
    return column_bytes(COLUMNS, rows)
