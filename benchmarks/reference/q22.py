"""TPC-H Q22 (clause 2.4.22), global sales opportunity: plain numpy,
validation values (I1..I7 = 13 31 23 29 30 18 17).  The uncorrelated scalar
subquery `c_acctbal > avg(c_acctbal)` is taken in integers: with `total` the
exact sum of the positive balances of the seven codes and `n` their count,
the test is `c_acctbal * n > total`.  `not exists (an order of the customer)`
is "the customer's key is in no order".  `sum(c_acctbal)` is the answer's one
decimal: `lowered=True` accumulates it in float32 (the control), and moves
neither the test nor a count."""

import numpy as np

from common import dec, group_totals

TABLES = {
    "customer": ["c_custkey", "c_phone", "c_acctbal"],
    "orders": ["o_custkey"],
}
CODES = ("13", "31", "23", "29", "30", "18", "17")


def reference(data, lowered=False):
    cu, od = data["customer"], data["orders"]
    prefix = np.fromiter((int(p[:2]) for p in cu["c_phone"]), np.int64, len(cu["c_phone"]))
    in_codes = np.isin(prefix, [int(c) for c in CODES])
    balance = np.asarray(cu["c_acctbal"]).astype(np.int64)
    positive = in_codes & (balance > 0)
    total, n = int(balance[positive].sum()), int(positive.sum())
    has_order = np.zeros((int(max(cu["c_custkey"].max(), od["o_custkey"].max())) + 1,), np.bool_)
    has_order[od["o_custkey"]] = True
    keep = in_codes & (balance * n > total) & ~has_order[cu["c_custkey"]]
    count = np.bincount(prefix[keep], minlength=100)
    totals = group_totals(prefix[keep], balance[keep], 100, lowered)
    # order by cntrycode
    return [(f"{g:02d}", int(count[g]), dec(totals[g], 2)) for g in np.flatnonzero(count)]
