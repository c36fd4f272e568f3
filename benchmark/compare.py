"""The comparison that decides `correct`: the engine's rows against the
plain reference's, exactly. Every limit is 0."""

import datetime
from decimal import Decimal

_EPOCH = datetime.date(1970, 1, 1)


def _same(kind, got, want) -> bool:
    if kind == "str":
        return got == want
    if kind == "int":
        return not isinstance(got, float) and int(got) == int(want)
    if kind == "date":          # the protocol sends ISO text or days
        if isinstance(got, str):
            got = (datetime.date.fromisoformat(got) - _EPOCH).days
        return int(got) == int(want)
    scale = kind[1]             # ("decimal", scale): want is the scaled
    # integer; the protocol's decimal text is parsed as a decimal, never
    # through a binary float
    if isinstance(got, float):
        return False
    return Decimal(str(got)) == Decimal(int(want)).scaleb(-scale)


def mismatched_cells(got_rows, want_rows, columns):
    """(count, first) over rows in order: how many cells differ, and a
    description of the first. A missing or extra row counts each of its
    cells."""
    count, first = 0, None
    width = len(columns)
    for r in range(max(len(got_rows), len(want_rows))):
        if r >= len(got_rows) or r >= len(want_rows) or \
                len(got_rows[r]) != width:
            count += width
            first = first or f"row {r}: got " \
                f"{got_rows[r] if r < len(got_rows) else None!r}, want " \
                f"{want_rows[r] if r < len(want_rows) else None!r}"
            continue
        for c, (name, kind) in enumerate(columns):
            g, w = got_rows[r][c], want_rows[r][c]
            try:
                ok = _same(kind, g, w)
            except (ValueError, ArithmeticError, TypeError):
                ok = False
            if not ok:
                count += 1
                first = first or f"row {r} {name}: got {g!r}, want {w!r}"
    return count, first
