"""The readers of the single-node route's spans and counters
(`execute`, `scan`, `scanPutBytes`, `residentBytes`) and of the
benchmark's own `hbm_in_use` readings, on hand-built statements: a
program that writes them, and one that does not (a parent commit: the
`execute` span alone, no `scan`, no counters), where each reader that
has nothing to read returns None.
"""

import importlib

import pytest

MS = 1_000_000          # ns
NEW = ("execute_ms", "scan_ms", "scan_put_mb", "resident_gb",
       "hbm_kept_mb")


def span(name, sid, parent, start_ms, dur_ms, **attrs):
    return {"name": name, "spanId": sid, "parentSpanId": parent,
            "startTimeUnixNano": start_ms * MS, "durationMs": float(dur_ms),
            "attributes": attrs}


def statement(n, execute_ms, scans, put_bytes, resident, hbm):
    """Statement `n` of a window: `scans` are (wall ms, putBytes)."""
    spans = [span("query", "q", None, 0, execute_ms + 20),
             span("exec-lock-wait", "x", "q", 0, 1),
             span("plan", "p", "q", 1, 3),
             span("execute", "e", "q", 5, execute_ms,
                  scanPutBytes=put_bytes, residentBytes=resident,
                  residentEntries=12),
             span("decode", "d", "q", 5 + execute_ms, 2)]
    at = 6
    for i, (wall, put) in enumerate(scans):
        spans.append(span("scan", f"s{i}", "e", at, wall, table="t",
                          columns="a,b", resident="miss" if put else "hit",
                          putBytes=put, zonesPruned=0))
        at += wall
    return {"spans": spans, "t_done": float(n), "hbm_in_use": hbm}


def older(n, execute_ms, hbm):
    """The same statement from a program older than the spans."""
    return {"spans": [span("query", "q", None, 0, execute_ms + 20),
                      span("exec-lock-wait", "x", "q", 0, 1),
                      span("execute", "e", "q", 5, execute_ms)],
            "t_done": float(n), "hbm_in_use": hbm}


def read(metric, statements):
    reader = importlib.import_module(f"layers.{metric}")
    return reader.read({"statements": list(statements)})


WINDOW = [statement(0, 900, [(300, 2_000_000_000), (40, 400_000_000)],
                    2_400_000_000, 2_400_000_000, 3_000_000_000),
          statement(1, 500, [(2, 0), (1, 0)], 0, 2_400_000_000,
                    3_010_000_000),
          statement(2, 520, [(4, 0), (2, 0)], 0, 2_400_000_000,
                    3_010_000_000),
          # the window's last: something was evicted and put again
          statement(3, 700, [(90, 600_000_000), (1, 0)], 600_000_000,
                    2_300_000_000, 2_990_000_000)]


@pytest.mark.parametrize("metric,want", [
    ("execute_ms", (520 + 700) / 2),
    ("scan_ms", (6 + 91) / 2),
    ("scan_put_mb", 300.0),              # median of 2400, 0, 0, 600
    ("resident_gb", 2.3),                # the last statement's
    ("hbm_kept_mb", 0.0),                # median of +10, 0, -20
])
def test_readers_on_a_hand_built_window(metric, want):
    assert read(metric, WINDOW) == pytest.approx(want)


def test_the_window_is_read_in_order_of_completion():
    shuffled = [WINDOW[2], WINDOW[0], WINDOW[3], WINDOW[1]]
    assert read("resident_gb", shuffled) == pytest.approx(2.3)
    assert read("hbm_kept_mb", shuffled) == pytest.approx(0.0)


def test_a_leak_shows_as_kept_bytes():
    """Every statement leaves 2.44 GB more: what the parent's q3 does."""
    leak = [older(n, 8000, 2_480_000_000 + n * 2_440_000_000)
            for n in range(4)]
    assert read("hbm_kept_mb", leak) == pytest.approx(2440.0)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_spans_gives_nothing_or_its_own(metric):
    """The parent writes `execute` and the benchmark reads `hbm_in_use`
    itself, so those two read there; the rest return None, and never
    raise."""
    parent = [older(0, 8000, 5_000_000_000), older(1, 8200, 7_440_000_000)]
    got = read(metric, parent)
    if metric == "execute_ms":
        assert got == pytest.approx(8100.0)
    elif metric == "hbm_kept_mb":
        assert got == pytest.approx(2440.0)
    else:
        assert got is None


@pytest.mark.parametrize("metric", NEW)
def test_nothing_to_read(metric):
    assert read(metric, []) is None
    assert read(metric, [{"spans": None, "t_done": 0.0}]) is None
