"""Deterministic TPC-H data generator (numpy, vectorized).

The reference ships TPC-H as a connector over a deterministic generator
(plugin/trino-tpch: TpchMetadata, TpchSplitManager, TpchPageSource) and uses it
as the benchmark/test workhorse.  This is a from-scratch numpy implementation
of the same idea: spec-shaped schemas, cardinalities and value distributions
(TPC-H v3 clause 4.2), seeded PCG64 so every run -- and every split of every
run -- produces identical data.  Correctness testing is differential (engine
vs sqlite over the *same* generated rows), so spec-exact dbgen bit-equality is
not required; distribution shape is, because the 22 queries' selectivities
depend on it.

`generate_columns` is the way in that scales: a string column is made as a
vocabulary and codes (`Coded`), never as one Python object per row, and the
strings that cost a pass of their own are made only when asked for
(connectors/tpch/columns.py keeps what it returns as files).
`generate_table` is the same data with every string column decoded.

Money/rate/quantity columns are DECIMAL(12,2), the spec types (the reference
offers both mappings via plugin/trino-tpch TpchMetadata DecimalTypeMapping;
here decimals are the default because scaled-int64 lanes are the only way a
TPU — which computes "f64" at f32 — can honor SQL comparison boundaries and
exact money sums).
"""

from __future__ import annotations

import zlib

import numpy as np

from ...data.types import BIGINT, DATE, DOUBLE, DecimalType, INTEGER, VARCHAR, Type, date_to_days
from ...utils import metrics as _metrics

PASSES = _metrics.GLOBAL.counter(
    "trino_tpu_tpch_lineitem_passes_total",
    "Generation passes for lineitem columns, by what each had to draw:"
    " orders_and_lines = the whole orders-and-lines stream (every numeric and"
    " flag column falls out of it), second_stream = ship instructions, modes"
    " and comments alone, which need only the table's length",
    ("pass",),
)

# TPC-H money/rate/quantity columns are DECIMAL(12,2) per spec; scaled
# int64 lanes make comparisons and sums exact on TPU (no native f64).
MONEY = DecimalType(12, 2)

__all__ = [
    "TPCH_SCHEMAS", "Coded", "generate_columns", "generate_table",
    "table_row_count", "SCALE_TINY",
]

SCALE_TINY = 0.01

_SEED = 0x7C9E_2025

TPCH_SCHEMAS: dict[str, list[tuple[str, Type]]] = {
    "region": [("r_regionkey", BIGINT), ("r_name", VARCHAR), ("r_comment", VARCHAR)],
    "nation": [
        ("n_nationkey", BIGINT),
        ("n_name", VARCHAR),
        ("n_regionkey", BIGINT),
        ("n_comment", VARCHAR),
    ],
    "supplier": [
        ("s_suppkey", BIGINT),
        ("s_name", VARCHAR),
        ("s_address", VARCHAR),
        ("s_nationkey", BIGINT),
        ("s_phone", VARCHAR),
        ("s_acctbal", MONEY),
        ("s_comment", VARCHAR),
    ],
    "part": [
        ("p_partkey", BIGINT),
        ("p_name", VARCHAR),
        ("p_mfgr", VARCHAR),
        ("p_brand", VARCHAR),
        ("p_type", VARCHAR),
        ("p_size", INTEGER),
        ("p_container", VARCHAR),
        ("p_retailprice", MONEY),
        ("p_comment", VARCHAR),
    ],
    "partsupp": [
        ("ps_partkey", BIGINT),
        ("ps_suppkey", BIGINT),
        ("ps_availqty", INTEGER),
        ("ps_supplycost", MONEY),
        ("ps_comment", VARCHAR),
    ],
    "customer": [
        ("c_custkey", BIGINT),
        ("c_name", VARCHAR),
        ("c_address", VARCHAR),
        ("c_nationkey", BIGINT),
        ("c_phone", VARCHAR),
        ("c_acctbal", MONEY),
        ("c_mktsegment", VARCHAR),
        ("c_comment", VARCHAR),
    ],
    "orders": [
        ("o_orderkey", BIGINT),
        ("o_custkey", BIGINT),
        ("o_orderstatus", VARCHAR),
        ("o_totalprice", MONEY),
        ("o_orderdate", DATE),
        ("o_orderpriority", VARCHAR),
        ("o_clerk", VARCHAR),
        ("o_shippriority", INTEGER),
        ("o_comment", VARCHAR),
    ],
    "lineitem": [
        ("l_orderkey", BIGINT),
        ("l_partkey", BIGINT),
        ("l_suppkey", BIGINT),
        ("l_linenumber", INTEGER),
        ("l_quantity", MONEY),
        ("l_extendedprice", MONEY),
        ("l_discount", MONEY),
        ("l_tax", MONEY),
        ("l_returnflag", VARCHAR),
        ("l_linestatus", VARCHAR),
        ("l_shipdate", DATE),
        ("l_commitdate", DATE),
        ("l_receiptdate", DATE),
        ("l_shipinstruct", VARCHAR),
        ("l_shipmode", VARCHAR),
        ("l_comment", VARCHAR),
    ],
}

_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_NATIONS = [  # (name, regionkey) -- TPC-H spec fixed table
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
    ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
    ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0),
    ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
_MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
_CONTAINERS1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
_CONTAINERS2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
_TYPES1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
_TYPES2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
_TYPES3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
# P_NAME words: TPC-H colors list (subset incl. ones queries filter on).
_COLORS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black", "blanched",
    "blue", "blush", "brown", "burlywood", "burnished", "chartreuse", "chiffon",
    "chocolate", "coral", "cornflower", "cornsilk", "cream", "cyan", "dark", "deep",
    "dim", "dodger", "drab", "firebrick", "floral", "forest", "frosted", "gainsboro",
    "ghost", "goldenrod", "green", "grey", "honeydew", "hot", "indian", "ivory",
    "khaki", "lace", "lavender", "lawn", "lemon", "light", "lime", "linen", "magenta",
    "maroon", "medium", "metallic", "midnight", "mint", "misty", "moccasin", "navajo",
    "navy", "olive", "orange", "orchid", "pale", "papaya", "peach", "peru", "pink",
    "plum", "powder", "puff", "purple", "red", "rose", "rosy", "royal", "saddle",
    "salmon", "sandy", "seashell", "sienna", "sky", "slate", "smoke", "snow",
    "spring", "steel", "tan", "thistle", "tomato", "turquoise", "violet", "wheat",
    "white", "yellow",
]
_WORDS = [  # comment vocabulary
    "carefully", "furiously", "quickly", "slyly", "blithely", "final", "special",
    "express", "regular", "unusual", "ironic", "pending", "bold", "even", "silent",
    "requests", "deposits", "packages", "accounts", "instructions", "theodolites",
    "foxes", "pinto", "beans", "dependencies", "excuses", "platelets", "asymptotes",
    "courts", "dolphins", "multipliers", "sauternes", "warthogs", "frets", "dinos",
]

_STARTDATE = date_to_days("1992-01-01")
_CURRENTDATE = date_to_days("1995-06-17")
_ENDDATE = date_to_days("1998-12-31")


def table_row_count(table: str, scale: float) -> int:
    base = {
        "region": 5,
        "nation": 25,
        "supplier": 10_000,
        "part": 200_000,
        "partsupp": 800_000,
        "customer": 150_000,
        "orders": 1_500_000,
    }
    if table in ("region", "nation"):
        return base[table]
    if table == "lineitem":
        # lines are generated per-order (1..7); callers should not rely on an
        # exact count -- use generate_table and read the arrays' length.
        return int(base["orders"] * scale) * 4
    return max(1, int(base[table] * scale))


def _rng(table: str, scale: float, part: int = 0) -> np.random.Generator:
    # zlib.crc32 is stable across processes (unlike hash(), which PYTHONHASHSEED
    # randomizes) -- determinism across runs is part of the generator contract.
    table_tag = zlib.crc32(table.encode())
    return np.random.Generator(np.random.PCG64([_SEED, table_tag, int(scale * 1e6), part]))


class Coded:
    """A string column as its makers have it: `values[codes]`, never 60M
    Python objects.  `values` is whatever vocabulary the column was drawn
    from, in any order, used or not; `normalised` gives the form a scan
    wants (data/page.py Dictionary.encode's: the sorted distinct values
    that occur, and int32 codes into them)."""

    __slots__ = ("values", "codes")

    def __init__(self, values, codes: np.ndarray):
        self.values = list(values)
        self.codes = codes

    def __len__(self) -> int:
        return len(self.codes)

    def decode(self) -> np.ndarray:
        return np.asarray(self.values, dtype=object)[self.codes]

    def replace(self, mask: np.ndarray, value: str) -> "Coded":
        """The column with `value` in the rows of `mask`."""
        codes = np.array(self.codes)
        codes[mask] = len(self.values)
        return Coded(self.values + [value], codes)

    def normalised(self) -> tuple[np.ndarray, np.ndarray]:
        used = np.flatnonzero(np.bincount(self.codes, minlength=len(self.values)))
        distinct, place = np.unique(
            np.asarray([self.values[i] for i in used], dtype=object), return_inverse=True)
        remap = np.zeros(len(self.values), dtype=np.int32)
        remap[used] = place
        return distinct, remap[self.codes]


def _formatted(fmt: str, numbers: np.ndarray) -> Coded:
    """`fmt.format(k)` per row, formatted once per distinct k."""
    distinct, codes = np.unique(numbers, return_inverse=True)
    return Coded([fmt.format(k) for k in distinct.tolist()], codes)


def _joined(*parts: Coded) -> Coded:
    """The parts joined by single spaces, row by row; a string is built only
    for a combination that occurs."""
    combined = np.zeros(len(parts[0]), dtype=np.int64)
    for part in parts:
        combined = combined * len(part.values) + part.codes
    distinct, codes = np.unique(combined, return_inverse=True)
    values = []
    for c in distinct.tolist():
        words = []
        for part in reversed(parts):
            c, digit = divmod(c, len(part.values))
            words.append(part.values[digit])
        values.append(" ".join(reversed(words)))
    return Coded(values, codes)


def _comment_picks(rng: np.random.Generator, n: int, nwords: int = 4) -> np.ndarray:
    return rng.integers(0, len(_WORDS), size=(n, nwords))


def _comments(picks: np.ndarray) -> Coded:
    return _joined(*[Coded(_WORDS, picks[:, i]) for i in range(picks.shape[1])])


def _money(rng: np.random.Generator, n: int, lo: float, hi: float) -> np.ndarray:
    """Cents-quantized uniform doubles (all TPC-H money is 2-decimal)."""
    cents = rng.integers(int(lo * 100), int(hi * 100) + 1, size=n)
    return cents / 100.0


def _retail_price(partkey: np.ndarray) -> np.ndarray:
    # TPC-H spec 4.2.3: (90000 + ((partkey/10) mod 20001) + 100*(partkey mod 1000)) / 100
    return (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0


def _supp_for_part(partkey: np.ndarray, i: np.ndarray, num_supp: int, scale: float) -> np.ndarray:
    # spec 4.2.3 partsupp: ps_suppkey = (ps_partkey + (i * (S/4 + (ps_partkey-1)/S))) mod S + 1
    s = num_supp
    return (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1


_ALL = lambda column: True  # noqa: E731


def generate_columns(table: str, scale: float, columns=None) -> dict:
    """{column: numpy array, or Coded for a string column} holding at least
    `columns` (None: all of them).  One table is a few seeded streams, each
    drawn in a fixed order, so every numeric column and every small-vocabulary
    string column falls out of the pass that makes any one of them and is
    returned whether asked for or not; the strings that cost a pass of their
    own (names, addresses, phones, comments) are built only when asked.
    Value for value what `generate_table` returns.

    Money/quantity columns generate as f64 (exact multiples of 0.01 at these
    magnitudes) and are scaled to DECIMAL(12,2) int64 lanes here, matching
    the schema types."""
    fn = {
        "region": _gen_region,
        "nation": _gen_nation,
        "supplier": _gen_supplier,
        "part": _gen_part,
        "partsupp": _gen_partsupp,
        "customer": _gen_customer,
        "orders": _gen_orders,
        "lineitem": _gen_lineitem,
    }[table]
    data = fn(scale, _ALL if columns is None else set(columns).__contains__)
    schema = dict(TPCH_SCHEMAS[table])
    for c, arr in data.items():
        t = schema[c]
        if t.is_decimal and np.issubdtype(arr.dtype, np.floating):
            data[c] = np.round(arr * (10.0**t.scale)).astype(np.int64)
    return data


def generate_table(table: str, scale: float) -> dict[str, np.ndarray]:
    """Generate a full table as {column_name: numpy array} (object dtype for strings)."""
    data = generate_columns(table, scale)
    return {
        c: data[c].decode() if isinstance(data[c], Coded) else data[c]
        for c, _t in TPCH_SCHEMAS[table]
    }


def _gen_region(scale: float, want=_ALL) -> dict:
    rng = _rng("region", scale)
    return {
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": Coded(_REGIONS, np.arange(5)),
        "r_comment": _comments(_comment_picks(rng, 5)),
    }


def _gen_nation(scale: float, want=_ALL) -> dict:
    rng = _rng("nation", scale)
    return {
        "n_nationkey": np.arange(25, dtype=np.int64),
        "n_name": Coded([n for n, _ in _NATIONS], np.arange(25)),
        "n_regionkey": np.asarray([r for _, r in _NATIONS], dtype=np.int64),
        "n_comment": _comments(_comment_picks(rng, 25)),
    }


def _gen_supplier(scale: float, want=_ALL) -> dict:
    n = table_row_count("supplier", scale)
    rng = _rng("supplier", scale)
    key = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, size=n).astype(np.int64)
    comment_picks = _comment_picks(rng, n)
    # Q16: some suppliers have 'Customer ... Complaints' comments (spec: 5 per SF*10000/2... keep ~0.05%)
    bad = rng.random(n) < 0.0005
    phone = _phone_draws(rng, nation)
    address_picks = _comment_picks(rng, n, 2)
    out = {
        "s_suppkey": key,
        "s_nationkey": nation,
        "s_acctbal": _money(rng, n, -999.99, 9999.99),
    }
    if want("s_name"):
        out["s_name"] = _formatted("Supplier#{:09d}", key)
    if want("s_address"):
        out["s_address"] = _comments(address_picks)
    if want("s_phone"):
        out["s_phone"] = _phones(*phone)
    if want("s_comment"):
        out["s_comment"] = _comments(comment_picks).replace(
            bad, "take Customer heed Complaints carefully")
    return out


def _phone_draws(rng: np.random.Generator, nation: np.ndarray) -> tuple:
    n = len(nation)
    cc = (nation + 10).astype(np.int64)
    a = rng.integers(100, 1000, size=n)
    b = rng.integers(100, 1000, size=n)
    c = rng.integers(1000, 10000, size=n)
    return cc, a, b, c


def _phones(cc, a, b, c) -> np.ndarray:
    return np.asarray(
        [f"{cc[i]}-{a[i]}-{b[i]}-{c[i]}" for i in range(len(cc))], dtype=object)


def _gen_part(scale: float, want=_ALL) -> dict:
    n = table_row_count("part", scale)
    rng = _rng("part", scale)
    key = np.arange(1, n + 1, dtype=np.int64)
    name_picks = rng.integers(0, len(_COLORS), size=(n, 5))
    mfgr_i = rng.integers(1, 6, size=n)
    brand_i = mfgr_i * 10 + rng.integers(1, 6, size=n)
    types = [Coded(v, rng.integers(0, len(v), size=n)) for v in (_TYPES1, _TYPES2, _TYPES3)]
    containers = [Coded(v, rng.integers(0, len(v), size=n)) for v in (_CONTAINERS1, _CONTAINERS2)]
    size = rng.integers(1, 51, size=n).astype(np.int32)
    comment_picks = _comment_picks(rng, n, 2)
    out = {
        "p_partkey": key,
        "p_mfgr": _formatted("Manufacturer#{}", mfgr_i),
        "p_brand": _formatted("Brand#{}", brand_i),
        "p_size": size,
        "p_retailprice": _retail_price(key),
    }
    if want("p_name"):
        out["p_name"] = _joined(*[Coded(_COLORS, name_picks[:, i]) for i in range(5)])
    if want("p_type"):
        out["p_type"] = _joined(*types)
    if want("p_container"):
        out["p_container"] = _joined(*containers)
    if want("p_comment"):
        out["p_comment"] = _comments(comment_picks)
    return out


def _gen_partsupp(scale: float, want=_ALL) -> dict:
    nparts = table_row_count("part", scale)
    nsupp = table_row_count("supplier", scale)
    rng = _rng("partsupp", scale)
    partkey = np.repeat(np.arange(1, nparts + 1, dtype=np.int64), 4)
    i = np.tile(np.arange(4, dtype=np.int64), nparts)
    suppkey = _supp_for_part(partkey, i, nsupp, scale)
    n = len(partkey)
    out = {
        "ps_partkey": partkey,
        "ps_suppkey": suppkey,
        "ps_availqty": rng.integers(1, 10_000, size=n).astype(np.int32),
        "ps_supplycost": _money(rng, n, 1.00, 1000.00),
    }
    if want("ps_comment"):
        out["ps_comment"] = _comments(_comment_picks(rng, n, 3))
    return out


def _gen_customer(scale: float, want=_ALL) -> dict:
    n = table_row_count("customer", scale)
    rng = _rng("customer", scale)
    key = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, size=n).astype(np.int64)
    address_picks = _comment_picks(rng, n, 2)
    phone = _phone_draws(rng, nation)
    out = {
        "c_custkey": key,
        "c_nationkey": nation,
        "c_acctbal": _money(rng, n, -999.99, 9999.99),
        "c_mktsegment": Coded(_SEGMENTS, rng.integers(0, 5, size=n)),
    }
    if want("c_name"):
        out["c_name"] = _formatted("Customer#{:09d}", key)
    if want("c_address"):
        out["c_address"] = _comments(address_picks)
    if want("c_phone"):
        out["c_phone"] = _phones(*phone)
    if want("c_comment"):
        out["c_comment"] = _comments(_comment_picks(rng, n, 4))
    return out


# What orders and lineitem share is kept between their passes while it is
# small (the tests' scales); at SF10 it is 5 GB, made again by the pass that
# needs it and let go.
_ORDER_LINES_CACHE: dict[float, dict] = {}
_ORDER_LINES_CACHE_MAX_ORDERS = 2_000_000


def _order_lines(scale: float):
    """Shared orders+lineitem generation (o_totalprice / o_orderstatus are
    aggregates of the order's lines, TPC-H spec 4.2.3).  Cached per scale:
    both tables derive from one generation pass."""
    if scale in _ORDER_LINES_CACHE:
        return _ORDER_LINES_CACHE[scale]
    g = _order_lines_uncached(scale)
    if g["norders"] <= _ORDER_LINES_CACHE_MAX_ORDERS:
        _ORDER_LINES_CACHE[scale] = g
    return g


def _order_heads(scale: float):
    """What the orders stream draws before any line: (orderkey, custkey,
    orderdate, lines an order).  15M draws each at SF10, about a second —
    all that lineitem's second stream needs to know its length."""
    norders = table_row_count("orders", scale)
    ncust = table_row_count("customer", scale)
    rng = _rng("orders", scale)

    # sparse orderkeys: 8 used out of each 32-key block (spec 4.2.3)
    i = np.arange(norders, dtype=np.int64)
    orderkey = (i // 8) * 32 + (i % 8) + 1
    # custkey skips every third customer (spec: c_custkey % 3 != 0)
    ck = rng.integers(1, ncust + 1, size=norders).astype(np.int64)
    ck = np.where(ck % 3 == 0, (ck % ncust) + 1, ck)
    ck = np.where(ck % 3 == 0, (ck % ncust) + 2, ck)
    ck = np.where(ck % 3 == 0, 1 if ncust < 3 else 2, ck)
    orderdate = rng.integers(_STARTDATE, _ENDDATE - 151 + 1, size=norders).astype(np.int32)
    nlines = rng.integers(1, 8, size=norders)
    return orderkey, ck, orderdate, nlines


def _order_lines_uncached(scale: float):
    norders = table_row_count("orders", scale)
    npart = table_row_count("part", scale)
    nsupp = table_row_count("supplier", scale)
    orderkey, ck, orderdate, nlines = _order_heads(scale)
    total_lines = int(nlines.sum())
    oidx = np.repeat(np.arange(norders), nlines)  # order index per line
    linenumber = (np.arange(total_lines) - np.repeat(np.cumsum(nlines) - nlines, nlines) + 1).astype(np.int32)

    lrng = _rng("lineitem", scale)
    partkey = lrng.integers(1, npart + 1, size=total_lines).astype(np.int64)
    suppkey = _supp_for_part(partkey, lrng.integers(0, 4, size=total_lines).astype(np.int64), nsupp, scale)
    quantity = lrng.integers(1, 51, size=total_lines).astype(np.float64)
    extprice = np.round(quantity * _retail_price(partkey), 2)
    discount = lrng.integers(0, 11, size=total_lines) / 100.0
    tax = lrng.integers(0, 9, size=total_lines) / 100.0
    l_orderdate = orderdate[oidx].astype(np.int64)
    shipdate = (l_orderdate + lrng.integers(1, 122, size=total_lines)).astype(np.int32)
    commitdate = (l_orderdate + lrng.integers(30, 91, size=total_lines)).astype(np.int32)
    receiptdate = (shipdate + lrng.integers(1, 31, size=total_lines)).astype(np.int32)
    returned = lrng.random(total_lines) < 0.5
    returnflag = Coded(
        ["A", "N", "R"],
        np.where(receiptdate <= _CURRENTDATE, np.where(returned, 2, 0), 1).astype(np.int8),
    )
    linestatus = Coded(["F", "O"], (shipdate > _CURRENTDATE).astype(np.int8))

    return {
        "norders": norders,
        "orderkey": orderkey,
        "custkey": ck,
        "orderdate": orderdate,
        "nlines": nlines,
        "oidx": oidx,
        "linenumber": linenumber,
        "partkey": partkey,
        "suppkey": suppkey,
        "quantity": quantity,
        "extprice": extprice,
        "discount": discount,
        "tax": tax,
        "shipdate": shipdate,
        "commitdate": commitdate,
        "receiptdate": receiptdate,
        "returnflag": returnflag,
        "linestatus": linestatus,
    }


def _gen_orders(scale: float, want=_ALL) -> dict:
    g = _order_lines(scale)
    norders = g["norders"]
    # fresh stream (part=1): the cached _order_lines dict must stay free of
    # live RNG state so repeated generation is idempotent
    rng = _rng("orders", scale, part=1)
    line_total = np.round(g["extprice"] * (1 + g["tax"]) * (1 - g["discount"]), 2)
    totalprice = np.round(np.bincount(g["oidx"], weights=line_total, minlength=norders), 2)
    open_lines = np.bincount(g["oidx"], weights=(g["linestatus"].codes == 1).astype(float), minlength=norders)
    status = Coded(
        ["F", "O", "P"],
        np.where(open_lines == 0, 0, np.where(open_lines == g["nlines"], 1, 2)).astype(np.int8),
    )
    comment_picks = _comment_picks(rng, norders, 4)
    # Q13 filters o_comment NOT LIKE '%special%requests%'
    has_special = rng.random(norders) < 0.01
    clerk = rng.integers(1, max(2, int(1000 * scale)) + 1, size=norders)
    out = {
        "o_orderkey": g["orderkey"],
        "o_custkey": g["custkey"],
        "o_orderstatus": status,
        "o_totalprice": totalprice,
        "o_orderdate": g["orderdate"],
        "o_orderpriority": Coded(_PRIORITIES, rng.integers(0, 5, size=norders)),
        "o_shippriority": np.zeros(norders, dtype=np.int32),
    }
    if want("o_clerk"):
        out["o_clerk"] = _formatted("Clerk#{:09d}", clerk)
    if want("o_comment"):
        out["o_comment"] = _comments(comment_picks).replace(
            has_special, "blithely special packages requests sleep")
    return out


_LINE_STREAM_2 = ("l_shipinstruct", "l_shipmode", "l_comment")


def _gen_lineitem(scale: float, want=_ALL) -> dict:
    second = any(want(c) for c in _LINE_STREAM_2)
    if second and not any(
            want(c) for c, _t in TPCH_SCHEMAS["lineitem"] if c not in _LINE_STREAM_2):
        # the second stream alone: it needs the table's length and nothing
        # else of the orders-and-lines pass (30 s and 5 GB at SF10)
        PASSES.labels("second_stream").inc()
        return _line_stream_2(scale, int(_order_heads(scale)[3].sum()), want)
    g = _order_lines(scale)
    PASSES.labels("orders_and_lines").inc()
    out = {
        "l_orderkey": g["orderkey"][g["oidx"]],
        "l_partkey": g["partkey"],
        "l_suppkey": g["suppkey"],
        "l_linenumber": g["linenumber"],
        "l_quantity": g["quantity"],
        "l_extendedprice": g["extprice"],
        "l_discount": g["discount"],
        "l_tax": g["tax"],
        "l_returnflag": g["returnflag"],
        "l_linestatus": g["linestatus"],
        "l_shipdate": g["shipdate"],
        "l_commitdate": g["commitdate"],
        "l_receiptdate": g["receiptdate"],
    }
    if second:
        out.update(_line_stream_2(scale, len(g["partkey"]), want))
    return out


def _line_stream_2(scale: float, total_lines: int, want) -> dict:
    """lineitem's second stream, drawn in this order: instructions, modes,
    comments."""
    lrng = _rng("lineitem", scale, part=1)
    out = {
        "l_shipinstruct": Coded(_INSTRUCTS, lrng.integers(0, 4, size=total_lines)),
        "l_shipmode": Coded(_MODES, lrng.integers(0, 7, size=total_lines)),
    }
    if want("l_comment"):
        out["l_comment"] = _comments(_comment_picks(lrng, total_lines, 2))
    return out
