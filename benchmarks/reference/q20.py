"""TPC-H Q20 (clause 2.4.20), potential part promotion: plain numpy,
validation values (COLOR forest, DATE 1994-01-01, NATION CANADA).  The
correlated scalar subquery `ps_availqty > 0.5 * sum(l_quantity)` is taken in
integers: l_quantity is hundredths, so the test is
`200 * ps_availqty > sum(l_quantity)`, over the (part, supplier) pairs that
shipped anything that year — a pair that shipped nothing compares with NULL
and is not kept.  Strings only come out: float32 moves nothing here,
`lowered` is accepted and unused."""

import datetime

import numpy as np

from common import EPOCH, key_lookup

TABLES = {
    "supplier": ["s_suppkey", "s_name", "s_address", "s_nationkey"],
    "nation": ["n_nationkey", "n_name"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_availqty"],
    "part": ["p_partkey", "p_name"],
    "lineitem": ["l_partkey", "l_suppkey", "l_quantity", "l_shipdate"],
}
COLOR = "forest"
YEAR = 1994
NATION = "CANADA"


def reference(data, lowered=False):
    su, na, ps = data["supplier"], data["nation"], data["partsupp"]
    pa, li = data["part"], data["lineitem"]
    forest = np.zeros((int(pa["p_partkey"].max()) + 1,), np.bool_)
    forest[pa["p_partkey"]] = np.fromiter(
        (name.startswith(COLOR) for name in pa["p_name"]), np.bool_, len(pa["p_name"]))
    d0 = (datetime.date(YEAR, 1, 1) - EPOCH).days
    d1 = (datetime.date(YEAR + 1, 1, 1) - EPOCH).days
    ship = np.asarray(li["l_shipdate"])
    part = np.asarray(li["l_partkey"])
    shipped = np.flatnonzero((ship >= d0) & (ship < d1) & forest[part])
    width = int(max(ps["ps_suppkey"].max(), li["l_suppkey"].max())) + 1
    pair = part[shipped].astype(np.int64) * width + np.asarray(li["l_suppkey"])[shipped]
    pairs, which = np.unique(pair, return_inverse=True)
    quantity = np.bincount(which.reshape(-1), weights=np.asarray(li["l_quantity"])[shipped],
                           minlength=len(pairs)).astype(np.int64)  # exact: far under 2**53
    cand = np.flatnonzero(forest[ps["ps_partkey"]])
    want = ps["ps_partkey"][cand].astype(np.int64) * width + ps["ps_suppkey"][cand]
    at = np.minimum(np.searchsorted(pairs, want), max(len(pairs) - 1, 0))
    found = (pairs[at] == want) if len(pairs) else np.zeros((len(want),), np.bool_)
    excess = found & (200 * ps["ps_availqty"][cand].astype(np.int64) > quantity[at])
    suppliers = np.unique(ps["ps_suppkey"][cand][excess])
    nation = na["n_nationkey"][[str(n) == NATION for n in na["n_name"]]]
    s_row = key_lookup(su["s_suppkey"])[suppliers]
    s_row = s_row[np.isin(su["s_nationkey"][s_row], nation)]
    rows = [(str(su["s_name"][i]), str(su["s_address"][i])) for i in s_row]
    return sorted(rows)  # order by s_name: a name holds its supplier's key
