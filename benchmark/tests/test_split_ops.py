"""The readers of what runs inside a worker's `split` lap
(layers/_split_ops.py and the five metrics that use it): on hand-built
span lists, and in traced CPU rehearsals of `rehearsal.worker.join` and
`rehearsal.single.join`, where every accepted reader is also held to
the value it gives with the new spans filtered out. Numbers read here
are the CPU's and never a device's.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from test_phase_metrics import MS, read, span, statement

NEW = ("split_join_ms", "split_agg_ms", "split_filter_ms",
       "split_unnamed_ms", "split_dispatches")
# the metrics the benchmark had before these five
ACCEPTED = 30
# read operator spans by name alone, on the single-node route's cells
# only (`workloads`): run.py never calls them on a worker cell's spans
SINGLE_ROUTE_ONLY = ("join_ms", "agg_ms")


def named_statement():
    """test_phase_metrics' statement (two splits of 10 and 40 ms, the
    second with compiles over [45, 75]) by a program that names the
    inside: split 0 filters 2 ms and aggregates 3; split 1 joins over
    [42, 60] (its dynamic filter inside, a subquery's aggregate too, and
    15 ms of the compiles), aggregates over [76, 80]."""
    spans = statement()
    for sp in spans:
        if sp["name"] == "split":
            sp["attributes"] = {"index": int(sp["spanId"][1]),
                                "dispatches": 2 + 5 * int(sp["spanId"][1])}
    return spans + [
        span("filter-project", "o0", "wt", 16, 2, split=0),
        span("aggregate", "o1", "wt", 19, 3, split=0),
        span("join", "o2", "wt", 42, 18, split=1),
        span("dynamic-filter", "o3", "o2", 43, 1, split=1),
        # an operator inside another: in its parent's wall already
        span("aggregate", "o6", "o2", 50, 6, split=1),
        span("aggregate", "o4", "wt", 76, 4, split=1),
        # under a span that has no lap: nobody's
        span("join", "o5", "other", 41, 40, split=1)]


def test_readers_on_a_hand_built_statement():
    s = named_statement()
    assert read("split_join_ms", s) == pytest.approx(18 / 2)
    assert read("split_agg_ms", s) == pytest.approx((3 + 4) / 2)
    assert read("split_filter_ms", s) == pytest.approx(2 / 2)
    assert read("split_dispatches", s) == pytest.approx((2 + 7) / 2)
    # split 0: 10 - 5. Split 1: 40 less the union of the join [42, 60],
    # the compiles [45, 75] and the aggregate [76, 80]: 40 - 37
    assert read("split_unnamed_ms", s) == pytest.approx((5 + 3) / 2)
    # the accepted reader of the lap is unmoved: (10 + 40 - 30) / 2
    assert read("split_run_ms", s) == pytest.approx(10.0)


def test_the_four_add_up_to_split_run_ms_where_nothing_compiled():
    s = [sp for sp in named_statement() if sp["name"] != "compile"]
    parts = sum(read(m, s) for m in NEW[:4])
    assert parts == pytest.approx(read("split_run_ms", s))
    assert read("split_unnamed_ms", s) == pytest.approx((5 + 18) / 2)


def test_median_is_taken_over_statements():
    a, b, c = named_statement(), named_statement(), named_statement()
    for spans, times in ((b, 3), (c, 5)):
        for sp in spans:
            if sp["name"] == "aggregate":
                sp["durationMs"] *= times
    assert read("split_agg_ms", a, b, c) == pytest.approx(3 * 3.5)


def test_no_join_reads_zero_not_none():
    s = [sp for sp in named_statement() if sp["name"] != "join"]
    assert read("split_join_ms", s) == 0.0


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_new_spans_gives_none(metric):
    # the parent's spans: the laps carry no `dispatches`, nothing
    # carries `split`
    assert read(metric, statement()) is None
    # whole-statement operator spans beside it are not a split's
    assert read(metric, statement() + [
        span("join", "j", "fs", 107, 20, kind="inner")]) is None
    # a statement that ran no split; none at all
    local = [span("query", "q", None, 0, 50),
             span("execute", "e", "q", 1, 40),
             span("join", "j", "e", 2, 30), span("aggregate", "a", "e", 32, 5)]
    assert read(metric, local) is None
    assert read(metric, []) is None
    assert read(metric) is None


def test_new_metrics_are_appended_beside_their_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"][ACCEPTED:]] == list(NEW)
    for m in bench["per_layer"][ACCEPTED:]:
        assert (m["better"], m["layer"], m["moves"]) == \
            ("lower", "worker tasks and executor", "query_geomean_s")
        assert (m["unit"], m["source"]) == (
            ("count", "program_counter") if m["name"] == "split_dispatches"
            else ("ms", "program_span"))
        assert m["workloads"] == ["worker.scan", "worker.join",
                                  "worker.streams3"]
        assert os.path.exists(os.path.join(BENCH, "layers",
                                           f"{m['name']}.py"))


# one traced window of a rehearsal cell, driven through run.Cell so that
# the readers can be called on its statements with and without the spans
# this file's metrics read
DRIVE = """
import importlib, json, sys
sys.path.insert(0, {bench!r})
import run
from layers import _split_ops
cell = run.Cell({rehearsal!r}, {cell!r}, True)
try:
    cell.setup()
    w = cell.window(2147483777, 1.0)
    out = cell.report(w, 0.0)
finally:
    cell.close()
NEW_NAMES = ("filter-project", "dynamic-filter")

def is_new(sp):
    return sp["name"] in NEW_NAMES or (
        sp["name"] in _split_ops.OPERATORS and "split" in sp["attributes"])

def old(sp):
    attributes = dict(sp["attributes"])
    attributes.pop("dispatches", None)
    return dict(sp, attributes=attributes)

def without(run_):
    return dict(run_, statements=[
        dict(s, spans=[old(sp) for sp in s.get("spans") or ()
                       if not is_new(sp)])
        for s in run_["statements"]])

names = [m["name"] for m in cell.bench["per_layer"]]
readers = {{n: importlib.import_module(f"layers.{{n}}") for n in names}}
bare = without(w)
each = []
for s in w["statements"]:
    one = dict(w, statements=[s])
    row = {{n: readers[n].read(one) for n in names
           if n.startswith("split_")}}
    row["sort"] = _split_ops.operator_ms(one, "sort")
    row["compiles"] = sum(sp["name"] == "compile" for sp in s["spans"])
    row["new_spans"] = sum(is_new(sp) for sp in s["spans"])
    row["spans"] = len(s["spans"])
    each.append(row)
print(json.dumps({{
    "reported": {{k: v["value"] for k, v in out["metrics"].items()}},
    "with": {{n: readers[n].read(w) for n in names}},
    "without": {{n: readers[n].read(bare) for n in names}},
    "each": each,
    "gap_labels": [g[0] for g in out["breakdown"]["idle_gaps"]]}}))
"""


def drive(rehearsal, cell):
    p = subprocess.run(
        [sys.executable, "-c", DRIVE.format(bench=BENCH, rehearsal=rehearsal,
                                            cell=cell)],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def worker_join(rehearsal):
    return drive(rehearsal, "rehearsal.worker.join")


@pytest.fixture(scope="module")
def single_join(rehearsal):
    return drive(rehearsal, "rehearsal.single.join")


def test_rehearsal_reads_all_five(worker_join):
    m = worker_join["reported"]
    for name in NEW:
        assert isinstance(m[name], (int, float)), (name, m)
    assert m["split_join_ms"] > 0 and m["split_agg_ms"] > 0
    assert m["split_filter_ms"] > 0 and m["split_unnamed_ms"] > 0
    # q3's probing splits dispatch their joins, the expression under
    # them and the aggregate; its build stages' splits one filter
    assert 1 <= m["split_dispatches"] <= 12
    assert m == {**m, **{n: worker_join["with"][n] for n in NEW}}


def test_rehearsal_parts_add_up_to_split_run_ms(worker_join):
    quiet = [s for s in worker_join["each"] if not s["compiles"]]
    assert quiet
    for s in quiet:
        parts = sum(s[n] for n in NEW[:4]) + s["sort"]
        assert parts == pytest.approx(s["split_run_ms"], rel=0.02), s
        assert s["new_spans"] > 0 and s["split_unnamed_ms"] >= 0


@pytest.mark.parametrize("route", ["worker", "single"])
def test_accepted_readers_read_what_they_read(worker_join, single_join,
                                              route):
    got = worker_join if route == "worker" else single_join
    assert any(s["new_spans"] for s in got["each"])
    accepted = list(got["with"])[:ACCEPTED]
    assert len(accepted) == ACCEPTED and not set(accepted) & set(NEW)
    for name in accepted:
        if route == "worker" and name in SINGLE_ROUTE_ONLY:
            continue
        assert got["with"][name] == got["without"][name], name
    # and with the new spans and `dispatches` filtered out (the parent's
    # spans) the five have nothing to read; on the single-node route
    # they never have
    assert all(got["without"][n] is None for n in NEW)
    if route == "single":
        assert all(got["with"][n] is None for n in NEW)


def test_gaps_take_the_operators_names(worker_join):
    labels = {g.split(":", 1)[1] for g in worker_join["gap_labels"]
              if ":" in g}
    assert labels & {"join", "aggregate", "filter-project",
                     "dynamic-filter"}, labels
