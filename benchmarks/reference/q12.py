"""TPC-H Q12 (clause 2.4.12), shipping modes and order priority: plain numpy,
validation values.  Counts only: float32 counts it exactly, so this query
alone does not catch the precision control (q03 beside it does)."""

import datetime

import numpy as np

from common import EPOCH, key_lookup

TABLES = {
    "orders": ["o_orderkey", "o_orderpriority"],
    "lineitem": ["l_orderkey", "l_shipmode", "l_commitdate", "l_receiptdate",
                 "l_shipdate"],
}
MODES = ("MAIL", "SHIP")
HIGH = ("1-URGENT", "2-HIGH")
LO = (datetime.date(1994, 1, 1) - EPOCH).days
HI = (datetime.date(1995, 1, 1) - EPOCH).days


def reference(data, lowered=False):
    od, li = data["orders"], data["lineitem"]
    keep = np.flatnonzero(
        (li["l_commitdate"] < li["l_receiptdate"])
        & (li["l_shipdate"] < li["l_commitdate"])
        & (li["l_receiptdate"] >= LO) & (li["l_receiptdate"] < HI)
    )
    mode = li["l_shipmode"][keep]
    prio = od["o_orderpriority"][key_lookup(od["o_orderkey"])[li["l_orderkey"][keep]]]
    high = np.isin(prio, HIGH)
    count = np.float32 if lowered else np.int64
    rows = []
    for m in sorted(MODES):
        sel = mode == m
        if sel.any():
            rows.append((m, int(np.sum(sel & high, dtype=count)),
                         int(np.sum(sel & ~high, dtype=count))))
    return rows
