"""TPC-H ``lineitem``: the 16 columns of clause 4.2.3, by dbgen's rules.
Money is float64 (two exact decimals), not DECIMAL(15,2); L_COMMENT is
pseudo-text of the spec's lengths, not dbgen's grammar."""

import numpy as np

from . import (CURRENTDATE, choice, lines_per_order, order_dates, order_keys,
               stream, words)

INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]


def retail_price_cents(partkey):
    """P_RETAILPRICE of a part, in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def generate(rows, seed):
    n = rows["lineitem"]
    n_orders, n_part, n_supp = rows["orders"], rows["part"], rows["supplier"]
    rng = stream(seed, "lineitem")
    counts = lines_per_order(n_orders, n, seed)
    order = np.repeat(np.arange(n_orders), counts)
    first = np.cumsum(counts) - counts
    orderdate = order_dates(n_orders, seed)[order]
    partkey = rng.integers(1, n_part + 1, n).astype(np.int64)
    quantity = rng.integers(1, 51, n).astype(np.int64)
    shipdate = (orderdate + rng.integers(1, 122, n)).astype(np.int32)
    receiptdate = (shipdate + rng.integers(1, 31, n)).astype(np.int32)
    returned = np.array(["R", "A"])[rng.integers(0, 2, n)]
    return {
        "l_orderkey": order_keys(n_orders)[order],
        "l_partkey": partkey,
        "l_suppkey": (partkey + rng.integers(0, 4, n)
                      * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1,
        "l_linenumber": (np.arange(n) - first[order] + 1).astype(np.int32),
        "l_quantity": quantity,
        "l_extendedprice": quantity * retail_price_cents(partkey) / 100.0,
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        "l_returnflag": np.where(receiptdate <= CURRENTDATE, returned, "N"),
        "l_linestatus": np.where(shipdate > CURRENTDATE, "O", "F"),
        "l_shipdate": shipdate,
        "l_commitdate": (orderdate + rng.integers(30, 91, n)
                         ).astype(np.int32),
        "l_receiptdate": receiptdate,
        "l_shipinstruct": choice(rng, INSTRUCTIONS, n),
        "l_shipmode": choice(rng, MODES, n),
        "l_comment": words(rng, rng.integers(10, 44, n)),
    }
