"""TPC-H ``orders`` by clause 4.2.3, the columns TPC-H Q3 reads: the key,
the customer, the date and the ship priority. The keys and dates are the
shared streams ``lineitem`` draws its orders from, so the tables agree.
O_ORDERSTATUS and O_TOTALPRICE (functions of the order's lines), the
priority, the clerk and the comment are not generated: no cell reads them
(the configuration's ``assumed``)."""

import numpy as np

from . import order_dates, order_keys, stream


def customer_keys(rng, n_orders, n_customers):
    """O_CUSTKEY: uniform over the customers whose key is not a multiple of
    three (a third of the customers never order)."""
    ordering = n_customers - n_customers // 3
    j = rng.integers(0, ordering, n_orders).astype(np.int64)
    return j + j // 2 + 1


def generate(rows, seed):
    n = rows["orders"]
    rng = stream(seed, "orders", "columns")
    return {
        "o_orderkey": order_keys(n),
        "o_custkey": customer_keys(rng, n, rows["customer"]),
        "o_orderdate": order_dates(n, seed),
        "o_shippriority": np.zeros(n, dtype=np.int32),
    }
