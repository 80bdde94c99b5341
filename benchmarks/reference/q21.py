"""TPC-H Q21 (clause 2.4.21), suppliers who kept orders waiting: plain numpy,
validation value (NATION SAUDI ARABIA).  The two correlated subqueries are
counts per order: `exists (another supplier's line on the order)` is "the
order has lines of two or more suppliers", and `not exists (another
supplier's LATE line on the order)` is, for a line that is itself late, "the
order's late lines are all one supplier's".  Counts and strings only:
float32 moves nothing here, `lowered` is accepted and unused."""

import numpy as np

from common import key_lookup

TABLES = {
    "supplier": ["s_suppkey", "s_name", "s_nationkey"],
    "lineitem": ["l_orderkey", "l_suppkey", "l_commitdate", "l_receiptdate"],
    "orders": ["o_orderkey", "o_orderstatus"],
    "nation": ["n_nationkey", "n_name"],
}
NATION = "SAUDI ARABIA"
LIMIT = 100


def _suppliers_per_order(order: np.ndarray, supp: np.ndarray, width: int,
                         n_orders: int) -> np.ndarray:
    """Distinct suppliers among the given lines, by order."""
    pairs = np.unique(order * width + supp)
    return np.bincount(pairs // width, minlength=n_orders)


def reference(data, lowered=False):
    su, li, od, na = data["supplier"], data["lineitem"], data["orders"], data["nation"]
    n_orders = len(od["o_orderkey"])
    order = key_lookup(od["o_orderkey"])[li["l_orderkey"]]
    supp = np.asarray(li["l_suppkey"]).astype(np.int64)
    width = int(supp.max()) + 1
    late = np.asarray(li["l_receiptdate"]) > np.asarray(li["l_commitdate"])
    suppliers = _suppliers_per_order(order, supp, width, n_orders)
    late_suppliers = _suppliers_per_order(order[late], supp[late], width, n_orders)
    finished = np.asarray(od["o_orderstatus"]) == "F"
    nation = na["n_nationkey"][[str(n) == NATION for n in na["n_name"]]]
    of_nation = np.zeros((width,), np.bool_)
    of_nation[su["s_suppkey"][np.isin(su["s_nationkey"], nation)]] = True
    waiting = late & finished[order] & of_nation[supp] \
        & (suppliers[order] > 1) & (late_suppliers[order] == 1)
    numwait = np.bincount(supp[waiting], minlength=width)
    s_row = key_lookup(su["s_suppkey"])
    rows = [(str(su["s_name"][s_row[s]]), int(numwait[s])) for s in np.flatnonzero(numwait)]
    # order by numwait desc, s_name; limit 100
    return sorted(rows, key=lambda r: (-r[1], r[0]))[:LIMIT]

