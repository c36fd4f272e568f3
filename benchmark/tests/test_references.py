"""Each plain reference against small inputs whose answer is worked out
here by hand or by plain Python loops, and the float32 control against
inputs on which 32 bits must show."""

import datetime
from decimal import Decimal

import numpy as np
import pytest

import compare
import traffic

EPOCH = datetime.date(1970, 1, 1)


def day(iso):
    return (datetime.date.fromisoformat(iso) - EPOCH).days


def table(columns, dictionary=None):
    return {"columns": {k: np.asarray(v) for k, v in columns.items()},
            "dictionary": dictionary or {}, "rows": len(next(iter(
                columns.values())))}


def test_q6_by_hand():
    q6 = traffic.load_template("q6")
    li = table({
        "l_shipdate": [day("1994-01-01"), day("1994-12-31"),
                       day("1995-01-01"), day("1994-06-01"),
                       day("1994-06-01"), day("1993-12-31")],
        "l_discount": [5, 7, 6, 4, 6, 6],           # 0.05 .. (scaled 100)
        "l_quantity": [2300, 2399, 100, 100, 2400, 100],
        "l_extendedprice": [100000, 20050, 999, 999, 999, 999]})
    got = q6.reference({"lineitem": li},
                       {"year": 1994, "discount": 6, "quantity": 24})
    # rows 0 and 1 pass; 2 is a year late, 3 is under the discount band,
    # 4 is not under the quantity, 5 is a day early
    assert got == [(100000 * 5 + 20050 * 7,)]


def test_q1_against_python_loops():
    q1 = traffic.load_template("q1")
    rng = np.random.default_rng(7)
    n = 5000
    cols = {
        "l_shipdate": rng.integers(day("1998-06-01"), day("1998-12-01"), n),
        "l_returnflag": rng.integers(0, 3, n).astype(np.int32),
        "l_linestatus": rng.integers(0, 2, n).astype(np.int32),
        "l_quantity": rng.integers(100, 5001, n),
        "l_extendedprice": rng.integers(90000, 10500000, n),
        "l_discount": rng.integers(0, 11, n),
        "l_tax": rng.integers(0, 9, n)}
    li = table(cols, {"l_returnflag": ("A", "N", "R"),
                      "l_linestatus": ("F", "O")})
    got = q1.reference({"lineitem": li}, {"delta": 75})
    cut = day("1998-12-01") - 75
    groups = {}
    for i in range(n):
        if cols["l_shipdate"][i] > cut:
            continue
        key = ("ANR"[cols["l_returnflag"][i]], "FO"[cols["l_linestatus"][i]])
        g = groups.setdefault(key, [0, 0, 0, 0, 0, 0])
        q, p = int(cols["l_quantity"][i]), int(cols["l_extendedprice"][i])
        d, t = int(cols["l_discount"][i]), int(cols["l_tax"][i])
        g[0] += q
        g[1] += p
        g[2] += p * (100 - d)
        g[3] += p * (100 - d) * (100 + t)
        g[4] += d
        g[5] += 1

    def half_up(total, cnt):            # avg keeps scale 2, HALF_UP
        return int((Decimal(total) / Decimal(cnt)).quantize(
            Decimal(1), rounding="ROUND_HALF_UP"))
    want = sorted((k[0], k[1], g[0], g[1], g[2], g[3],
                   half_up(g[0], g[5]), half_up(g[1], g[5]),
                   half_up(g[4], g[5]), g[5]) for k, g in groups.items())
    assert got == want


def test_q1_group_sums_are_exact_beyond_53_bits():
    q1 = traffic.load_template("q1")
    n = 200_000
    cols = {"l_shipdate": np.full(n, day("1995-01-01")),
            "l_returnflag": np.zeros(n, np.int32),
            "l_linestatus": np.zeros(n, np.int32),
            "l_quantity": np.full(n, 5000),
            "l_extendedprice": np.full(n, 10_494_951),
            "l_discount": np.full(n, 1), "l_tax": np.full(n, 8)}
    li = table(cols, {"l_returnflag": ("A",), "l_linestatus": ("F",)})
    (row,) = q1.reference({"lineitem": li}, {"delta": 90})
    charge = 10_494_951 * 99 * 108 * n            # 2.2e16 > 2**53
    assert charge > 2**53 and row[5] == charge


def q3_tables():
    cust = table({"c_custkey": [1, 2, 3, 4],
                  "c_mktsegment": np.array([0, 1, 1, 0], np.int32)},
                 {"c_mktsegment": ("AUTOMOBILE", "BUILDING")})
    orders = table({
        "o_orderkey": [10, 11, 12, 13, 14],
        "o_custkey": [2, 3, 1, 2, 3],
        "o_orderdate": [day("1995-03-01"), day("1995-03-14"),
                        day("1995-03-01"), day("1995-03-15"),
                        day("1995-02-01")],
        "o_shippriority": [0, 0, 0, 0, 0]})
    li = table({
        "l_orderkey": [10, 10, 11, 12, 13, 14, 14],
        "l_shipdate": [day("1995-03-16"), day("1995-03-15"),
                       day("1995-03-20"), day("1995-03-20"),
                       day("1995-03-20"), day("1995-03-16"),
                       day("1995-03-17")],
        "l_extendedprice": [100000, 5000000, 250000, 777, 888, 300, 400],
        "l_discount": [10, 0, 4, 0, 0, 0, 5]})
    return {"customer": cust, "orders": orders, "lineitem": li}


def test_q3_by_hand():
    q3 = traffic.load_template("q3")
    got = q3.reference(q3_tables(), {"segment": "BUILDING", "day": 15})
    # order 10: only the line shipped after the 15th counts; order 12's
    # customer is no BUILDING; order 13 is dated the 15th, not before it
    want = [(11, 250000 * 96, day("1995-03-14"), 0),
            (10, 100000 * 90, day("1995-03-01"), 0),
            (14, 300 * 100 + 400 * 95, day("1995-02-01"), 0)]
    assert got == want


def as_protocol(rows, columns):
    from prove import _text
    return [[_text(v, kind) for v, (_, kind) in zip(row, columns)]
            for row in rows]


def test_exact_reference_passes_its_own_comparison():
    q3 = traffic.load_template("q3")
    rows = q3.reference(q3_tables(), {"segment": "BUILDING", "day": 15})
    assert compare.mismatched_cells(as_protocol(rows, q3.COLUMNS), rows,
                                    q3.COLUMNS) == (0, None)


@pytest.mark.parametrize("name,params", [
    ("q6", {"year": 1994, "discount": 6, "quantity": 24}),
    ("q1", {"delta": 90}),
    ("q3", {"segment": "BUILDING", "day": 15})])
def test_float32_control_is_rejected(name, params):
    """The control at a size a test can hold: 32-bit accumulation in the
    reference's place must come out as not correct."""
    t = traffic.load_template(name)
    rng = np.random.default_rng(3)
    n = 40_000
    okeys = np.arange(1, n // 4 + 1) * 4
    tables = {
        "customer": table({"c_custkey": np.arange(1, 1001),
                           "c_mktsegment": rng.integers(0, 5, 1000)
                           .astype(np.int32)},
                          {"c_mktsegment": ("AUTOMOBILE", "BUILDING",
                                            "FURNITURE", "HOUSEHOLD",
                                            "MACHINERY")}),
        "orders": table({"o_orderkey": okeys,
                         "o_custkey": rng.integers(1, 1001, len(okeys)),
                         "o_orderdate": rng.integers(
                             day("1995-01-01"), day("1995-03-31"),
                             len(okeys)),
                         "o_shippriority": np.zeros(len(okeys), np.int64)}),
        "lineitem": table({
            "l_orderkey": np.repeat(okeys, 4),
            "l_shipdate": rng.integers(day("1994-01-01"),
                                       day("1995-06-30"), n),
            "l_returnflag": rng.integers(0, 3, n).astype(np.int32),
            "l_linestatus": rng.integers(0, 2, n).astype(np.int32),
            "l_quantity": rng.integers(100, 5001, n),
            "l_extendedprice": rng.integers(90000, 10500000, n),
            "l_discount": rng.integers(0, 11, n),
            "l_tax": rng.integers(0, 9, n)},
            {"l_returnflag": ("A", "N", "R"), "l_linestatus": ("F", "O")})}
    exact = t.reference(tables, params)
    narrow = t.reference(tables, params, narrow=True)
    assert exact and compare.mismatched_cells(
        as_protocol(exact, t.COLUMNS), exact, t.COLUMNS)[0] == 0
    assert compare.mismatched_cells(
        as_protocol(narrow, t.COLUMNS), exact, t.COLUMNS)[0] > 0
