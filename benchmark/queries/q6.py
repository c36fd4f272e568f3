"""TPC-H Q6 (forecasting revenue change): one scan of lineitem, a
conjunctive filter and one global sum.

Substitution parameters (TPC-H 2.4.6.3): DATE is the first of January
of a year in [1993, 1997], DISCOUNT is in [0.02, 0.09], QUANTITY is 24
or 25. The validation values (2.4.6.4) warm the cell up and are never
drawn for the window.
"""

import numpy as np

NAME = "q6"
TABLES = {"lineitem": ("l_shipdate", "l_discount", "l_quantity",
                       "l_extendedprice")}
# (name, kind) of each answer column; ("decimal", scale) compares exactly
COLUMNS = (("revenue", ("decimal", 4)),)
VALIDATION = {"year": 1994, "discount": 6, "quantity": 24}

SQL = """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM {s}.lineitem
WHERE l_shipdate >= DATE '{year}-01-01'
  AND l_shipdate < DATE '{year}-01-01' + INTERVAL '1' YEAR
  AND l_discount BETWEEN {lo} AND {hi}
  AND l_quantity < {quantity}
"""


def domain():
    return [{"year": y, "discount": d, "quantity": q}
            for y in range(1993, 1998) for d in range(2, 10)
            for q in (24, 25)]


def render(params, schema):
    d = params["discount"]
    return SQL.format(s=schema, year=params["year"],
                      lo=f"0.{d - 1:02d}", hi=f"0.{d + 1:02d}",
                      quantity=params["quantity"])


def _days(iso):
    return int((np.datetime64(iso) - np.datetime64("1970-01-01"))
               .astype(int))


def reference(tables, params, narrow=False):
    """Rows as the engine must give them. decimal(12,2) columns hold
    integers scaled by 100 and dates are days since 1970-01-01.
    `narrow` accumulates in float32, the control that must not pass."""
    li = tables["lineitem"]["columns"]
    ship, disc = li["l_shipdate"], li["l_discount"]
    qty, price = li["l_quantity"], li["l_extendedprice"]
    y, d = params["year"], params["discount"]
    m = (ship >= _days(f"{y}-01-01")) & (ship < _days(f"{y + 1}-01-01")) \
        & (disc >= d - 1) & (disc <= d + 1) \
        & (qty < params["quantity"] * 100)
    if narrow:
        total = int((price[m].astype(np.float32) *
                     disc[m].astype(np.float32)).sum(dtype=np.float32))
    else:
        total = int((price[m].astype(np.int64) * disc[m]).sum())
    return [(total,)]
