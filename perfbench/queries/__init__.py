"""One file per query, found by the query's name. Each holds

* ``TABLES``: the tables the SQL reads;
* ``COLUMNS``: table -> column -> declared bytes per value, for every column
  the SQL text names (the work the query asks for, whatever implements it);
* ``draw(rng)``: the TPC-H substitution parameters of one execution;
* ``sql(params)``: the SQL text handed to ``TpuSession.sql``;
* ``reference(tables, params, dtype)``: the plain reference in numpy on the
  host arrays, ``dtype`` being the float type of its arithmetic: the one
  the cell's file states, or for the control the one below it;
* ``bytes_read(rows)``: bytes the query must read at the given row counts.

Nothing here imports the engine or ``benchmarks``."""

import datetime

_EPOCH = datetime.date(1970, 1, 1)


def days(year, month, day):
    """Days since 1970-01-01, the tables' date representation."""
    return (datetime.date(year, month, day) - _EPOCH).days


def iso(d):
    """``DATE '...'`` literal body for a day number."""
    return (_EPOCH + datetime.timedelta(days=int(d))).isoformat()


def column_bytes(columns, rows):
    return sum(rows[t] * sum(widths.values()) for t, widths in columns.items())


def total(x, dtype):
    """Sum of ``x`` (already of ``dtype``) accumulated in ``dtype``."""
    return float(x.sum(dtype=dtype))
