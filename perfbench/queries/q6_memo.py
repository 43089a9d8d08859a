"""TPC-H Q6 as ``q6`` has it (text, draw, columns, bytes), with the plain
reference evaluated ONCE per parameter set.

The harness compares every answer of a run with ``reference(tables,
params)`` after the window. Q6 draws from 5 years x 8 discounts x 2
quantities = 80 parameter sets (clause 2.4.6.3), and ``q6.reference`` is a
pure function of the tables and the set: at SF10 it costs ~0.9 s a call over
60 M rows, so a window of ~320 answers spent 288 s comparing, past the
driver's time limit. Here each answer is still compared with the reference's
answer for its own parameters; equal inputs are computed once. The tables'
identity is part of what is remembered, so a second run in one process (the
tests) never reads another table's answers.

Nothing here imports the engine or ``benchmarks``."""

import numpy as np

from . import q6
from .q6 import COLUMNS, TABLES, bytes_read, draw, sql  # noqa: F401

#: the lineitem the remembered answers belong to, and those answers
_of = {"lineitem": None, "answers": {}}


def reference(tables, p, dtype=np.float64):
    if _of["lineitem"] is not tables["lineitem"]:
        _of["lineitem"], _of["answers"] = tables["lineitem"], {}
    key = (p["year"], p["discount_pct"], p["quantity"], np.dtype(dtype).str)
    if key not in _of["answers"]:
        _of["answers"][key] = q6.reference(tables, p, dtype)
    return _of["answers"][key]
