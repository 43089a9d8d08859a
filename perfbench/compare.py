"""The comparison that decides ``correct``: every answer of the window, as
fetched, against the plain reference's rows for the same parameters.

Two numbers, each with a limit from the configuration's file:

* ``rows_wrong``: answers whose row count, or any key, integer, date or
  string cell, differs from the reference (or that never came). Limit 0.
* ``max_rel_gap``: the widest gap of a float cell, |got - ref| / |ref|,
  over every row of every answer."""

import datetime
import math

_EPOCH = datetime.date(1970, 1, 1)


def _plain(v):
    """A cell as a plain python value; dates as days since 1970-01-01."""
    if v is None:
        return None
    if isinstance(v, datetime.datetime):
        return (v.date() - _EPOCH).days
    if isinstance(v, datetime.date):
        return (v - _EPOCH).days
    if hasattr(v, "item"):
        v = v.item()
    if isinstance(v, bytes):
        return v.decode()
    return v


def compare_rows(got, ref):
    """(wrong, widest float gap) of one answer against its reference."""
    if got is None or len(got) != len(ref):
        return True, 0.0
    gap = 0.0
    for g_row, r_row in zip(got, ref):
        if len(g_row) != len(r_row):
            return True, gap
        for g, r in zip(g_row, r_row):
            g, r = _plain(g), _plain(r)
            if isinstance(r, float):
                if not isinstance(g, (int, float)) or isinstance(g, bool) \
                        or math.isnan(g) != math.isnan(r):
                    return True, gap
                if not math.isnan(r):
                    gap = max(gap, abs(g - r) / (abs(r) or 1.0))
            elif g != r or isinstance(g, float):
                return True, gap
    return False, gap


def judge(answers, references, limits):
    """``answers`` and ``references`` are parallel lists of row lists (an
    answer that never came is ``None``). Returns (correct, checks): each
    check a short name with its number and its limit."""
    wrong, gap = 0, 0.0
    for got, ref in zip(answers, references):
        w, g = compare_rows(got, ref)
        wrong += bool(w)
        gap = max(gap, g)
    checks = {
        "rows_wrong": {"value": wrong, "limit": limits["rows_wrong"]},
        "max_rel_gap": {"value": gap, "limit": limits["max_rel_gap"]}}
    correct = bool(answers) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return correct, checks
