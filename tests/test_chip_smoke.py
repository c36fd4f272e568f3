"""chip_smoke.py off-chip: it must refuse to run, and its parts must be
right before a chip call is spent on them.

Nothing here touches the TPU compiler (tests/test_chip_compile.py is the
one file that does); the child process is held to the CPU backend.
"""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_exits_nonzero_without_a_chip(argv, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TRINO_TPU_DATA_CACHE=str(tmp_path))
    p = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"), *argv],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT,
        timeout=300)
    assert p.returncode != 0, p.stdout + p.stderr
    assert "needs a TPU" in p.stderr and "cpu" in p.stderr, p.stderr
    assert '"ok"' not in p.stdout, p.stdout
    assert os.listdir(tmp_path) == []       # nothing was generated


def test_references_agree_with_the_engine_at_tiny():
    """The numpy references and the row comparison, against the local
    executor on the tiny schema (the chip run compares the served path
    at sf10 with the same code)."""
    from trino_tpu.exec.session import Session
    session = Session(default_schema="tiny")
    tables = {t: session.catalog.get_table("tpch", "tiny", t)
              for t in chip_smoke.TABLES}
    for name, sql, reference, date_cols in chip_smoke.QUERIES:
        want = reference(tables)
        assert want, f"{name}: empty reference at tiny"
        chip_smoke.check_rows(
            name, session.execute(sql.format(s="tpch.tiny")).rows, want,
            date_cols)
    with pytest.raises(AssertionError, match="q6: row 0 col 0"):
        chip_smoke.check_rows("q6", [(1.0,)], [(1.001,)])


def test_a_host_routed_answer_is_refused(monkeypatch, tmp_path):
    """At tiny the router answers on the host interpreter; the smoke must
    end there and not count a right answer from the wrong place."""
    monkeypatch.setenv("TRINO_TPU_DATA_CACHE", str(tmp_path))
    with pytest.raises(AssertionError, match="q6: not on the device"):
        chip_smoke.run_served(chip_smoke.CacheCounter(), "tiny")


@pytest.mark.parametrize("name,strategies,refused", [
    ("q6", {"worker": {"AggregateNode": "global"}}, None),
    ("q1", {"worker": {"AggregateNode": "direct"}}, None),
    # a strategy no executor has any more is not what q1 may report
    ("q1", {"worker": {"AggregateNode": "mxu"}}, "q1"),
    ("q3", {"worker": {"AggregateNode": "sort", "JoinNode": "dense-lut"},
            "coordinator": {"AggregateNode": "sort"}}, None),
    ("q18", {"worker": {"AggregateNode": "sort"}}, "no join strategy"),
])
def test_expected_strategies_of_the_defaults(name, strategies, refused):
    if refused is None:
        chip_smoke.expect_strategies(name, strategies)
    else:
        with pytest.raises(AssertionError, match=refused):
            chip_smoke.expect_strategies(name, strategies)
