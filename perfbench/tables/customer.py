"""TPC-H ``customer`` by clause 4.2.3, the columns TPC-H Q3 reads: the key
(dense, 1..rows) and the market segment (uniform over the five of clause
4.2.2.13). Name, address, nation, phone, balance and comment are not
generated: no cell reads them (the configuration's ``assumed``)."""

import numpy as np

from . import choice, stream

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]


def generate(rows, seed):
    n = rows["customer"]
    rng = stream(seed, "customer")
    return {
        "c_custkey": np.arange(1, n + 1, dtype=np.int64),
        "c_mktsegment": choice(rng, SEGMENTS, n),
    }
