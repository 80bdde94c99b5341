"""TPC-H Q6 (clause 2.4.6), forecasting revenue change: plain numpy."""

from common import dec, product, total

TABLES = {"lineitem": ["l_extendedprice", "l_discount", "l_quantity", "l_shipdate"]}


def reference(data, ship_lo, ship_hi, disc_lo, disc_hi, quantity, lowered=False):
    """Sites: shipdate >= ship_lo, < ship_hi (days); discount between
    disc_lo and disc_hi (hundredths); quantity < `quantity` (units)."""
    li = data["lineitem"]
    keep = (
        (li["l_shipdate"] >= ship_lo) & (li["l_shipdate"] < ship_hi)
        & (li["l_discount"] >= disc_lo) & (li["l_discount"] <= disc_hi)
        & (li["l_quantity"] < quantity * 100)
    )
    if not keep.any():
        return [(None,)]
    revenue = total(
        product(li["l_extendedprice"][keep], li["l_discount"][keep], lowered),
        lowered,
    )
    return [(dec(revenue, 4),)]
