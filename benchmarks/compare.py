"""The comparison that decides `correct`.

`compare(got, want, ordered)` holds the rows the timed path produced against
the plain reference's and returns three numbers, each with a limit of its own
(configs/*.json, "limits"; PERF.md gives the readings each was set from):

  exact_mismatches   cells whose reference value is an integer, a string, a
                     date or NULL and that differ at all, plus one per
                     missing or extra row.  Limit 0.
  decimal_rel_err    the widest relative gap over the cells whose reference
                     value is a decimal (the exact value, from integer sums).
  double_rel_err     the widest relative gap over the cells whose reference
                     value is a double.

`decimal_cells_inexact` counts the decimal cells that are not equal as values:
information, printed in every run (the fused scan kernel sums money in
compensated float32 and is off in the tenth digit at SF1).

Program values arrive as the entry hands them over: the wire protocol gives
decimals and dates as strings, the library gives Decimal and date objects.
"""

from __future__ import annotations

import datetime
import decimal
import math


def _canon(v):
    """A total order over mixed cells, for comparing unordered results."""
    if v is None:
        return (0, "")
    if isinstance(v, (int, float, decimal.Decimal)):
        return (1, float(v))
    return (2, str(v))


def _cell(got, want) -> tuple[int, float, float]:
    """-> (exact mismatch 0/1, relative gap of a decimal cell, relative gap
    of a double cell)."""
    if want is None or got is None:
        return (int(got is not want), 0.0, 0.0)
    try:
        if isinstance(want, float):
            g = float(got)
            if math.isnan(g) or math.isinf(g):
                return (1, 0.0, 0.0)
            return (0, 0.0, abs(g - want) / max(abs(want), 1e-300))
        if isinstance(want, decimal.Decimal):
            g = decimal.Decimal(str(got))
            if not g.is_finite():
                return (1, 0.0, 0.0)
            floor = decimal.Decimal(1).scaleb(want.as_tuple().exponent)  # one unit
            return (0, float(abs(g - want) / max(abs(want), floor)), 0.0)
        if isinstance(want, bool) or isinstance(got, bool):
            return (int(got is not want), 0.0, 0.0)
        if isinstance(want, int):
            return (int(isinstance(got, float) or int(got) != want), 0.0, 0.0)
        if isinstance(want, datetime.date):
            text = got.isoformat() if hasattr(got, "isoformat") else str(got)
            return (int(text != want.isoformat()), 0.0, 0.0)
    except (TypeError, ValueError, decimal.InvalidOperation):
        return (1, 0.0, 0.0)
    return (int(str(got) != str(want)), 0.0, 0.0)


def compare(got, want, ordered: bool) -> dict:
    got = [tuple(r) for r in got]
    want = [tuple(r) for r in want]
    if not ordered:
        key = lambda r: tuple(_canon(v) for v in r)  # noqa: E731
        got, want = sorted(got, key=key), sorted(want, key=key)
    mismatches = abs(len(got) - len(want))
    dec = dbl = 0.0
    inexact = 0
    for g, w in zip(got, want):
        if len(g) != len(w):
            mismatches += 1
            continue
        for gv, wv in zip(g, w):
            m, d, f = _cell(gv, wv)
            mismatches += m
            dec, dbl = max(dec, d), max(dbl, f)
            inexact += int(d > 0)
    return {"exact_mismatches": mismatches, "decimal_rel_err": dec,
            "double_rel_err": dbl, "decimal_cells_inexact": inexact,
            "rows": len(want)}
