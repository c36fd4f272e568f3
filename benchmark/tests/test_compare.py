"""The comparison is exact: decimals as text against scaled integers,
never through a binary float."""

import compare

COLS = (("k", "int"), ("s", "str"), ("d", "date"), ("v", ("decimal", 2)))


def test_equal_rows_in_both_date_forms():
    want = [(7, "A", 9204, 123456), (8, "B", 9205, -5)]
    got = [[7, "A", "1995-03-15", "1234.56"], [8, "B", 9205, "-0.05"]]
    assert compare.mismatched_cells(got, want, COLS) == (0, None)


def test_one_unit_in_the_last_place_is_a_mismatch():
    want = [(7, "A", 9204, 123456789012345678)]
    got = [[7, "A", 9204, "1234567890123456.79"]]
    n, first = compare.mismatched_cells(got, want, COLS)
    assert n == 1 and "v" in first


def test_a_float_never_passes_for_a_decimal_or_an_int():
    assert compare.mismatched_cells([[7.0, "A", 9204, 1234.56]],
                                    [(7, "A", 9204, 123456)], COLS)[0] == 2


def test_missing_extra_and_reordered_rows_count():
    want = [(1, "A", 1, 100), (2, "B", 2, 200)]
    assert compare.mismatched_cells([], want, COLS)[0] == 8
    assert compare.mismatched_cells(
        [[1, "A", 1, "1.00"], [2, "B", 2, "2.00"], [3, "C", 3, "3.00"]],
        want, COLS)[0] == 4
    assert compare.mismatched_cells(
        [[2, "B", 2, "2.00"], [1, "A", 1, "1.00"]], want, COLS)[0] == 8


def test_text_that_is_no_number_is_a_mismatch_not_a_crash():
    assert compare.mismatched_cells([[1, "A", "soon", "n/a"]],
                                    [(1, "A", 1, 100)], COLS)[0] == 2
