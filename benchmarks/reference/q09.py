"""TPC-H Q9 (clause 2.4.9), product type profit measure: plain numpy,
validation value (COLOR green).  Six relations; partsupp is found by the
composite key (partkey, suppkey), folded into one integer as
ps_partkey * (largest suppkey + 1) + ps_suppkey; `%green%` is a pass over
the part names."""

import numpy as np

from common import dec, group_totals, key_lookup, product

TABLES = {
    "part": ["p_partkey", "p_name"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
                 "l_extendedprice", "l_discount"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
    "orders": ["o_orderkey", "o_orderdate"],
    "nation": ["n_nationkey", "n_name"],
}
COLOR = "green"


def reference(data, lowered=False):
    pa, su, li = data["part"], data["supplier"], data["lineitem"]
    ps, od, na = data["partsupp"], data["orders"], data["nation"]
    green = np.zeros((int(pa["p_partkey"].max()) + 1,), np.bool_)
    green[pa["p_partkey"]] = np.fromiter(
        (COLOR in name for name in pa["p_name"]), np.bool_, len(pa["p_name"]))
    keep = np.flatnonzero(green[li["l_partkey"]])
    part, supp = li["l_partkey"][keep], li["l_suppkey"][keep]
    # partsupp by (partkey, suppkey): the pair as one integer, sorted, searched
    width = int(max(ps["ps_suppkey"].max(), supp.max())) + 1
    pair = ps["ps_partkey"].astype(np.int64) * width + ps["ps_suppkey"]
    by_pair = np.argsort(pair, kind="stable")
    want = part.astype(np.int64) * width + supp
    at = np.minimum(np.searchsorted(pair[by_pair], want), len(pair) - 1)
    found = pair[by_pair][at] == want  # every lineitem row has its partsupp row
    keep, supp, ps_row = keep[found], supp[found], by_pair[at[found]]
    nation = su["s_nationkey"][key_lookup(su["s_suppkey"])[supp]]
    o_date = od["o_orderdate"][key_lookup(od["o_orderkey"])[li["l_orderkey"][keep]]]
    year = o_date.astype("datetime64[D]").astype("datetime64[Y]").astype(np.int64) + 1970
    amount = (
        product(li["l_extendedprice"][keep], 100 - li["l_discount"][keep], lowered)
        - product(ps["ps_supplycost"][ps_row], li["l_quantity"][keep], lowered)
    )
    n_row = key_lookup(na["n_nationkey"])[nation]
    y0 = int(year.min()) if len(year) else 0
    span = (int(year.max()) - y0 + 1) if len(year) else 1
    codes = n_row * span + (year - y0)
    profit = group_totals(codes, amount, len(na["n_nationkey"]) * span, lowered)
    seen = np.bincount(codes, minlength=len(profit)) > 0
    rows = [(str(na["n_name"][g // span]), int(y0 + g % span), dec(profit[g], 4))
            for g in np.flatnonzero(seen)]
    # order by nation, o_year desc
    return sorted(rows, key=lambda r: (r[0], -r[1]))
