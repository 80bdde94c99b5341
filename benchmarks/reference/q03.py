"""TPC-H Q3 (clause 2.4.3), shipping priority: plain numpy, validation values."""

import datetime

import numpy as np

from common import EPOCH, date_of, dec, group_totals, key_lookup, product

TABLES = {
    "customer": ["c_custkey", "c_mktsegment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"],
}
SEGMENT = "BUILDING"
DATE = (datetime.date(1995, 3, 15) - EPOCH).days


def reference(data, lowered=False):
    cu, od, li = data["customer"], data["orders"], data["lineitem"]
    building = np.zeros((int(cu["c_custkey"].max()) + 1,), np.bool_)
    building[cu["c_custkey"][cu["c_mktsegment"] == SEGMENT]] = True
    o_keep = (od["o_orderdate"] < DATE) & building[od["o_custkey"]]
    o_rows = np.flatnonzero(o_keep)
    by_key = key_lookup(od["o_orderkey"])
    l_keep = li["l_shipdate"] > DATE
    l_order = by_key[li["l_orderkey"][l_keep]]
    joined = o_keep[l_order]
    l_order = l_order[joined]
    revenue_rows = product(
        li["l_extendedprice"][l_keep][joined],
        100 - li["l_discount"][l_keep][joined], lowered,
    )
    revenue = group_totals(l_order, revenue_rows, len(od["o_orderkey"]), lowered)
    has = np.zeros((len(od["o_orderkey"]),), np.bool_)
    has[l_order] = True
    cand = o_rows[has[o_rows]]
    # order by revenue desc, o_orderdate; limit 10
    top = cand[np.lexsort((od["o_orderdate"][cand], -revenue[cand]))][:10]
    return [
        (int(od["o_orderkey"][i]), dec(revenue[i], 4),
         date_of(od["o_orderdate"][i]), int(od["o_shippriority"][i]))
        for i in top
    ]
