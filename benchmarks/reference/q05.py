"""TPC-H Q5 (clause 2.4.5), local supplier volume: plain numpy, validation
values (REGION ASIA, DATE 1994-01-01).  Six relations; the customer's nation
has to be the supplier's, so the join graph is a cycle and the reference
walks it from the fact table: each lineitem row finds its order, the order
its customer, the row its supplier, and the two nations are compared."""

import datetime

import numpy as np

from common import EPOCH, dec, group_totals, key_lookup, product

TABLES = {
    "customer": ["c_custkey", "c_nationkey"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
    "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "region": ["r_regionkey", "r_name"],
}
REGION = "ASIA"
LO = (datetime.date(1994, 1, 1) - EPOCH).days
HI = (datetime.date(1995, 1, 1) - EPOCH).days


def reference(data, lowered=False):
    cu, od, li = data["customer"], data["orders"], data["lineitem"]
    su, na, re = data["supplier"], data["nation"], data["region"]
    regions = re["r_regionkey"][re["r_name"] == REGION]
    n_rows = np.flatnonzero(np.isin(na["n_regionkey"], regions))
    # nation key -> its place among the region's nations, -1 outside it
    n_place = np.full((int(na["n_nationkey"].max()) + 1,), -1, np.int64)
    n_place[na["n_nationkey"][n_rows]] = np.arange(len(n_rows))
    o_row = key_lookup(od["o_orderkey"])[li["l_orderkey"]]
    in_year = (od["o_orderdate"] >= LO) & (od["o_orderdate"] < HI)
    keep = np.flatnonzero(in_year[o_row])
    o_row = o_row[keep]
    c_nation = cu["c_nationkey"][key_lookup(cu["c_custkey"])[od["o_custkey"][o_row]]]
    s_nation = su["s_nationkey"][key_lookup(su["s_suppkey"])[li["l_suppkey"][keep]]]
    local = (c_nation == s_nation) & (n_place[s_nation] >= 0)
    keep, place = keep[local], n_place[s_nation[local]]
    revenue_rows = product(
        li["l_extendedprice"][keep], 100 - li["l_discount"][keep], lowered)
    revenue = group_totals(place, revenue_rows, len(n_rows), lowered)
    seen = np.bincount(place, minlength=len(n_rows)) > 0
    # order by revenue desc
    rows = [(str(na["n_name"][n_rows[g]]), dec(revenue[g], 4))
            for g in np.flatnonzero(seen)]
    return sorted(rows, key=lambda r: -r[1])
