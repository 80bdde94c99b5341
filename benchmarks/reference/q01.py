"""TPC-H Q1 (clause 2.4.1), pricing summary report: plain numpy."""

import numpy as np

from common import dec, mean, product, string_codes, total

TABLES = {"lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                       "l_extendedprice", "l_discount", "l_tax", "l_shipdate"]}
_codes: dict = {}  # id(column) -> (values, codes): the string pass is made once


def _coded(col):
    key = id(col)
    if key not in _codes:
        _codes[key] = (col, *string_codes(col))
    return _codes[key][1:]


def reference(data, ship_cutoff, lowered=False):
    """Site: shipdate <= ship_cutoff (days since 1970-01-01)."""
    li = data["lineitem"]
    flags, fcode = _coded(li["l_returnflag"])
    stats, scode = _coded(li["l_linestatus"])
    keep = li["l_shipdate"] <= ship_cutoff
    group = fcode.astype(np.int64) * len(stats) + scode
    rows = []
    for g in range(len(flags) * len(stats)):
        m = keep & (group == g)
        n = int(m.sum())
        if n == 0:
            continue
        qty, ext = li["l_quantity"][m], li["l_extendedprice"][m]
        disc, tax = li["l_discount"][m], li["l_tax"][m]
        disc_price = product(ext, 100 - disc, lowered)
        charge = product(disc_price, 100 + tax, lowered)
        rows.append((
            flags[g // len(stats)], stats[g % len(stats)],
            dec(total(qty, lowered), 2), dec(total(ext, lowered), 2),
            dec(total(disc_price, lowered), 4), dec(total(charge, lowered), 6),
            mean(qty, 2, lowered), mean(ext, 2, lowered), mean(disc, 2, lowered),
            n,
        ))
    return rows
