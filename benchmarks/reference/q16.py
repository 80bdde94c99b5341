"""TPC-H Q16 (clause 2.4.16), parts/supplier relationship: plain numpy,
validation values (BRAND Brand#45, TYPE MEDIUM POLISHED, SIZES 49 14 23 45
19 3 36 9).  `ps_suppkey NOT IN (suppliers with a complaint)` is written as
the standard's three-valued rule (`not_in`): the generated columns hold no
NULL, the rule does not lean on that.  `count(distinct ps_suppkey)` is the
number of distinct (group, supplier) pairs a group.  Counts and strings
only: float32 moves nothing here, `lowered` is accepted and unused."""

import numpy as np

from common import key_lookup

TABLES = {
    "partsupp": ["ps_partkey", "ps_suppkey"],
    "part": ["p_partkey", "p_brand", "p_type", "p_size"],
    "supplier": ["s_suppkey", "s_comment"],
}
BRAND = "Brand#45"
TYPE = "MEDIUM POLISHED"
SIZES = (49, 14, 23, 45, 19, 3, 36, 9)
WORDS = ("Customer", "Complaints")


def not_in(keys, members, keys_null=None, members_null=None) -> np.ndarray:
    """`key NOT IN (members)` as a filter, by SQL's three-valued logic: TRUE
    only where the key is not NULL, equals no member, and no member is NULL;
    over an empty member list every key passes, a NULL one too."""
    members = np.asarray(members)
    if len(members) == 0:
        return np.ones((len(keys),), np.bool_)
    if members_null is not None and np.any(members_null):
        return np.zeros((len(keys),), np.bool_)
    keep = ~np.isin(keys, members)
    if keys_null is not None:
        keep &= ~np.asarray(keys_null)
    return keep


def _complains(comment: str) -> bool:
    """like '%Customer%Complaints%'"""
    at = comment.find(WORDS[0])
    return at >= 0 and comment.find(WORDS[1], at + len(WORDS[0])) >= 0


def reference(data, lowered=False):
    ps, pa, su = data["partsupp"], data["part"], data["supplier"]
    brands = np.asarray(pa["p_brand"])
    types = np.asarray(pa["p_type"])
    ok = (brands != BRAND) & np.isin(pa["p_size"], SIZES)
    ok &= ~np.fromiter((t.startswith(TYPE) for t in types), np.bool_, len(types))
    wanted = np.zeros((int(pa["p_partkey"].max()) + 1,), np.bool_)
    wanted[pa["p_partkey"]] = ok
    complaints = su["s_suppkey"][np.fromiter(
        (_complains(c) for c in su["s_comment"]), np.bool_, len(su["s_comment"]))]
    keep = wanted[ps["ps_partkey"]] & not_in(ps["ps_suppkey"], complaints)
    part, supp = ps["ps_partkey"][keep], ps["ps_suppkey"][keep].astype(np.int64)
    # a group is (brand, type, size): one integer of the three columns' codes
    brand_names, brand = np.unique(brands.astype(str), return_inverse=True)
    type_names, kind = np.unique(types.astype(str), return_inverse=True)
    size = np.asarray(pa["p_size"]).astype(np.int64)
    sizes = int(size.max()) + 1
    group_of_part = (brand.reshape(-1) * len(type_names) + kind.reshape(-1)) * sizes + size
    width = int(supp.max()) + 1 if len(supp) else 1
    # distinct (group, supplier) pairs
    pairs = np.unique(group_of_part[key_lookup(pa["p_partkey"])[part]] * width + supp)
    groups, counts = np.unique(pairs // width, return_counts=True)
    rows = [(str(brand_names[g // sizes // len(type_names)]),
             str(type_names[g // sizes % len(type_names)]), int(g % sizes), int(n))
            for g, n in zip(groups, counts)]
    # order by supplier_cnt desc, p_brand, p_type, p_size
    return sorted(rows, key=lambda r: (-r[3], r[0], r[1], r[2]))
