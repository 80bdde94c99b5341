"""TPC-H Q18 (clause 2.4.18), large volume customer: plain numpy, validation
value (QUANTITY 300)."""

import numpy as np

from common import date_of, dec, group_totals, key_lookup

TABLES = {
    "customer": ["c_custkey", "c_name"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
    "lineitem": ["l_orderkey", "l_quantity"],
}
QUANTITY = 300


def reference(data, lowered=False):
    cu, od, li = data["customer"], data["orders"], data["lineitem"]
    l_order = key_lookup(od["o_orderkey"])[li["l_orderkey"]]
    qty = group_totals(l_order, li["l_quantity"], len(od["o_orderkey"]), lowered)
    big = np.flatnonzero(qty > QUANTITY * 100)
    price = od["o_totalprice"][big]
    if lowered:  # a money column carried through float32
        price = np.rint(price.astype(np.float32)).astype(np.int64)
    # order by o_totalprice desc, o_orderdate; limit 100
    pick = np.lexsort((od["o_orderdate"][big], -price))[:100]
    c_row = key_lookup(cu["c_custkey"])
    rows = []
    for j in pick:
        i = big[j]
        c = c_row[od["o_custkey"][i]]
        rows.append((
            str(cu["c_name"][c]), int(cu["c_custkey"][c]), int(od["o_orderkey"][i]),
            date_of(od["o_orderdate"][i]), dec(price[j], 2), dec(qty[i], 2),
        ))
    return rows
