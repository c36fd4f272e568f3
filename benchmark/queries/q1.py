"""TPC-H Q1 (pricing summary report): one scan of lineitem, a date
filter that keeps nearly every row, eight aggregates in four groups.

Substitution parameter (TPC-H 2.4.1.3): DELTA is in [60, 120] days; the
validation value 90 warms the cell up and is never drawn for the window.
"""

import numpy as np

NAME = "q1"
TABLES = {"lineitem": ("l_shipdate", "l_returnflag", "l_linestatus",
                       "l_quantity", "l_extendedprice", "l_discount",
                       "l_tax")}
COLUMNS = (("l_returnflag", "str"), ("l_linestatus", "str"),
           ("sum_qty", ("decimal", 2)), ("sum_base_price", ("decimal", 2)),
           ("sum_disc_price", ("decimal", 4)), ("sum_charge", ("decimal", 6)),
           ("avg_qty", ("decimal", 2)), ("avg_price", ("decimal", 2)),
           ("avg_disc", ("decimal", 2)), ("count_order", "int"))
VALIDATION = {"delta": 90}

SQL = """
SELECT l_returnflag, l_linestatus,
       sum(l_quantity) AS sum_qty,
       sum(l_extendedprice) AS sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
       avg(l_quantity) AS avg_qty,
       avg(l_extendedprice) AS avg_price,
       avg(l_discount) AS avg_disc,
       count(*) AS count_order
FROM {s}.lineitem
WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '{delta}' DAY
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus
"""


def domain():
    return [{"delta": d} for d in range(60, 121)]


def render(params, schema):
    return SQL.format(s=schema, delta=params["delta"])


def _days(iso):
    return int((np.datetime64(iso) - np.datetime64("1970-01-01"))
               .astype(int))


CHUNK = 1 << 15     # rows: 32,768 values under 2^37 sum to under 2^53


def reference(tables, params, narrow=False):
    """Exact group sums: bincount adds float64 weights, which is exact
    while a sum stays under 2^53, so rows go through in chunks of 32,768
    and the chunks' sums add up as Python integers. Rows the filter
    drops go to one group past the last. avg(decimal(12,2)) keeps scale
    2 and rounds HALF_UP. `narrow` holds values and sums in float32, the
    control that must not pass."""
    li = tables["lineitem"]
    c = li["columns"]
    rf_pool = li["dictionary"]["l_returnflag"]
    ls_pool = li["dictionary"]["l_linestatus"]
    n_groups = len(rf_pool) * len(ls_pool)
    cut = _days("1998-12-01") - params["delta"]
    sums = [[0] * (n_groups + 1) for _ in range(6)]
    for lo in range(0, len(c["l_shipdate"]), CHUNK):
        sl = slice(lo, lo + CHUNK)
        gid = c["l_returnflag"][sl].astype(np.int64) * len(ls_pool) + \
            c["l_linestatus"][sl]
        gid[c["l_shipdate"][sl] > cut] = n_groups
        price = c["l_extendedprice"][sl].astype(np.int64)
        disc = c["l_discount"][sl].astype(np.int64)
        disc_price = price * (100 - disc)                 # scaled 1e4
        charge = disc_price * (100 + c["l_tax"][sl])      # scaled 1e6
        for acc, v in zip(sums, (c["l_quantity"][sl], price, disc,
                                 disc_price, charge, None)):
            if v is None:
                part = np.bincount(gid, minlength=n_groups + 1)
            elif narrow:
                part = np.bincount(gid, weights=v.astype(np.float32),
                                   minlength=n_groups + 1)
            else:
                part = np.bincount(gid, weights=v, minlength=n_groups + 1)
            for g in range(n_groups):
                acc[g] += float(part[g]) if narrow else int(part[g])
    if narrow:
        sums = [[int(np.float32(x)) for x in acc] for acc in sums]
    s_qty, s_price, s_disc, s_disc_price, s_charge, cnt = sums

    def avg(total, n):
        return (2 * int(total) + int(n)) // (2 * int(n))
    rows = []
    for g in range(n_groups):
        if cnt[g] == 0:
            continue
        rows.append((rf_pool[g // len(ls_pool)], ls_pool[g % len(ls_pool)],
                     int(s_qty[g]), int(s_price[g]), int(s_disc_price[g]),
                     int(s_charge[g]), avg(s_qty[g], cnt[g]),
                     avg(s_price[g], cnt[g]), avg(s_disc[g], cnt[g]),
                     int(cnt[g])))
    return sorted(rows)
