"""TPC-H Q18 (large volume customer): the orders whose lineitems add up
to more than QUANTITY, with their customer, the hundred largest by total
price. The inner GROUP BY l_orderkey has as many groups as `orders` has
rows: the high-cardinality aggregate of the 22.

Substitution parameter (TPC-H 2.4.18.3): QUANTITY is in [312, 315], four
sets in all. The validation value 300 (2.4.18.4) warms the cell up and
is never drawn for the window.
"""

import numpy as np

NAME = "q18"
TABLES = {"customer": ("c_custkey", "c_name"),
          "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                     "o_totalprice"),
          "lineitem": ("l_orderkey", "l_quantity")}
COLUMNS = (("c_name", "str"), ("c_custkey", "int"), ("o_orderkey", "int"),
           ("o_orderdate", "date"), ("o_totalprice", ("decimal", 2)),
           ("sum_quantity", ("decimal", 2)))
VALIDATION = {"quantity": 300}

SQL = """
SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
       sum(l_quantity)
FROM {s}.customer, {s}.orders, {s}.lineitem
WHERE o_orderkey IN (
        SELECT l_orderkey
        FROM {s}.lineitem
        GROUP BY l_orderkey
        HAVING sum(l_quantity) > {quantity})
  AND c_custkey = o_custkey
  AND o_orderkey = l_orderkey
GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
ORDER BY o_totalprice DESC, o_orderdate, o_orderkey
LIMIT 100
"""


def domain():
    return [{"quantity": q} for q in range(312, 316)]


def render(params, schema):
    return SQL.format(s=schema, quantity=params["quantity"])


def reference(tables, params, narrow=False):
    """Rows as the engine must give them: decimal(12,2) columns hold
    integers scaled by 100, dates are days since 1970-01-01, c_name is a
    dictionary code. `narrow` holds o_totalprice and the quantity sums in
    float32, the control that must not pass: a total price passes 2^24
    cents, where float32 no longer holds every cent."""
    cust, oc = tables["customer"], tables["orders"]["columns"]
    li = tables["lineitem"]["columns"]
    okey = oc["o_orderkey"]
    assert np.all(okey[1:] > okey[:-1]), "o_orderkey is not ascending"
    lk = li["l_orderkey"]
    pos = np.clip(np.searchsorted(okey, lk), 0, len(okey) - 1)
    hit = okey[pos] == lk
    # a sum is at most seven lineitems of 50.00: float64 weights are exact
    qty = np.bincount(pos[hit], weights=li["l_quantity"][hit],
                      minlength=len(okey))
    tot = oc["o_totalprice"]
    if narrow:
        qty = qty.astype(np.float32).astype(np.int64)
        tot = tot.astype(np.float32).astype(np.int64)
    else:
        qty = qty.astype(np.int64)
    big = np.nonzero(qty > params["quantity"] * 100)[0]
    odate = oc["o_orderdate"]
    top = big[np.lexsort((okey[big], odate[big], -tot[big]))][:100]
    ckey = cust["columns"]["c_custkey"]
    by_key = np.argsort(ckey, kind="stable")
    crow = by_key[np.searchsorted(ckey[by_key], oc["o_custkey"][top])]
    assert np.all(ckey[crow] == oc["o_custkey"][top])
    names = cust["dictionary"]["c_name"]
    codes = cust["columns"]["c_name"][crow]
    return [(names[int(codes[j])], int(oc["o_custkey"][i]), int(okey[i]),
             int(odate[i]), int(tot[i]), int(qty[i]))
            for j, i in enumerate(top)]
