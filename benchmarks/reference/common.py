"""Helpers shared by the plain numpy references.

Nothing here imports the program.  Money is int64 hundredths (cents), a
product of two money columns is int64 at the summed scale, and every sum is
an exact integer sum.  `lowered=True` is the precision control (PERF.md,
"How correct is decided"): the same query with its arithmetic in float32,
the step a later PR would be tempted by.  It exists to be caught.
"""

from __future__ import annotations

import datetime
import decimal

import numpy as np

EPOCH = datetime.date(1970, 1, 1)


def date_of(days: int) -> datetime.date:
    return EPOCH + datetime.timedelta(days=int(days))


def dec(unscaled: int, scale: int) -> decimal.Decimal:
    return decimal.Decimal(int(unscaled)).scaleb(-scale)


def product(a: np.ndarray, b: np.ndarray, lowered: bool) -> np.ndarray:
    if lowered:
        return a.astype(np.float32) * b.astype(np.float32)
    return a.astype(np.int64) * b.astype(np.int64)


def total(x: np.ndarray, lowered: bool) -> int:
    """Sum of an integer-valued column: exact, or accumulated in float32."""
    if lowered:
        return int(np.rint(np.sum(x.astype(np.float32), dtype=np.float32)))
    return int(np.sum(x.astype(np.int64), dtype=np.int64))


def mean(x: np.ndarray, scale: int, lowered: bool) -> float:
    """AVG of a decimal column as the engine types it: a double."""
    if lowered:
        s = np.sum(x.astype(np.float32), dtype=np.float32)
        return float(np.float32(s) / np.float32(len(x)) / np.float32(10 ** scale))
    return int(np.sum(x, dtype=np.int64)) / len(x) / 10 ** scale


def group_totals(codes: np.ndarray, x: np.ndarray, n_groups: int,
                 lowered: bool) -> np.ndarray:
    """Per-group sums of an integer-valued column over dense group codes.

    Exact path: float64 bincount is exact while every partial sum stays
    under 2**53, which is checked, not assumed."""
    if lowered:
        out = np.zeros((n_groups,), np.float32)
        np.add.at(out, codes, x.astype(np.float32))
        return np.rint(out).astype(np.int64)
    if float(np.sum(np.abs(x), dtype=np.float64)) >= 2.0 ** 53:
        raise OverflowError("group_totals: sums too large for exact bincount")
    return np.bincount(codes, weights=x, minlength=n_groups).astype(np.int64)


def string_codes(col: np.ndarray) -> tuple[list, np.ndarray]:
    """Sorted distinct values of a low-cardinality string column, and each
    row's index into them."""
    values = sorted(set(col.tolist()))
    codes = np.zeros((len(col),), np.int8)
    for i, v in enumerate(values[1:], 1):
        codes[col == v] = i
    return values, codes


def key_lookup(keys: np.ndarray) -> np.ndarray:
    """Dense array mapping a unique integer key to its row, -1 elsewhere."""
    out = np.full((int(keys.max()) + 1,), -1, np.int64)
    out[keys] = np.arange(len(keys), dtype=np.int64)
    return out
