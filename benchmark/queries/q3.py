"""TPC-H Q3 (shipping priority): customer joined to orders joined to
lineitem, grouped by order, the ten orders with most revenue.

Substitution parameters (TPC-H 2.4.3.3): SEGMENT is one of the five
market segments, DATE is a day in [1995-03-01, 1995-03-31]. The
validation values (BUILDING, 1995-03-15) warm the cell up and are never
drawn for the window.
"""

import numpy as np

NAME = "q3"
TABLES = {"customer": ("c_custkey", "c_mktsegment"),
          "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                     "o_shippriority"),
          "lineitem": ("l_orderkey", "l_shipdate", "l_extendedprice",
                       "l_discount")}
COLUMNS = (("l_orderkey", "int"), ("revenue", ("decimal", 4)),
           ("o_orderdate", "date"), ("o_shippriority", "int"))
VALIDATION = {"segment": "BUILDING", "day": 15}
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD",
            "MACHINERY")

SQL = """
SELECT l_orderkey,
       sum(l_extendedprice * (1 - l_discount)) AS revenue,
       o_orderdate, o_shippriority
FROM {s}.customer, {s}.orders, {s}.lineitem
WHERE c_mktsegment = '{segment}'
  AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey
  AND o_orderdate < DATE '1995-03-{day:02d}'
  AND l_shipdate > DATE '1995-03-{day:02d}'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate, l_orderkey
LIMIT 10
"""


def domain():
    return [{"segment": s, "day": d} for s in SEGMENTS
            for d in range(1, 32)]


def render(params, schema):
    return SQL.format(s=schema, segment=params["segment"],
                      day=params["day"])


def _days(iso):
    return int((np.datetime64(iso) - np.datetime64("1970-01-01"))
               .astype(int))


def reference(tables, params, narrow=False):
    """`narrow` computes and sums revenue in float32, the control that
    must not pass."""
    cust, orders = tables["customer"], tables["orders"]
    li = tables["lineitem"]["columns"]
    cut = _days(f"1995-03-{params['day']:02d}")
    seg = cust["dictionary"]["c_mktsegment"].index(params["segment"])
    cc, oc = cust["columns"], orders["columns"]
    in_seg = np.zeros(int(cc["c_custkey"].max()) + 1, dtype=bool)
    in_seg[cc["c_custkey"][cc["c_mktsegment"] == seg]] = True
    keep = (oc["o_orderdate"] < cut) & in_seg[oc["o_custkey"]]
    okey, odate = oc["o_orderkey"][keep], oc["o_orderdate"][keep]
    oprio = oc["o_shippriority"][keep]
    order = np.argsort(okey, kind="stable")
    okey, odate, oprio = okey[order], odate[order], oprio[order]
    lm = li["l_shipdate"] > cut
    lk = li["l_orderkey"][lm]
    pos = np.clip(np.searchsorted(okey, lk), 0, len(okey) - 1)
    hit = okey[pos] == lk
    price = li["l_extendedprice"][lm][hit]
    disc = li["l_discount"][lm][hit]
    if narrow:
        rev = price.astype(np.float32) * (100 - disc).astype(np.float32)
        sums = np.bincount(pos[hit], weights=rev, minlength=len(okey)) \
            .astype(np.float32).astype(np.int64)
    else:
        rev = price.astype(np.int64) * (100 - disc)     # scaled 1e4
        # per-order sums stay far below 2^53: float64 weights are exact
        sums = np.bincount(pos[hit], weights=rev,
                           minlength=len(okey)).astype(np.int64)
    live = np.nonzero(sums > 0)[0]
    top = live[np.lexsort((okey[live], odate[live], -sums[live]))][:10]
    return [(int(okey[i]), int(sums[i]), int(odate[i]), int(oprio[i]))
            for i in top]
