"""The two readers of how a worker task stages its splits' inputs
(layers/split_stage_ms.py, layers/split_ahead_share.py): on hand-built
span lists, and in a traced CPU rehearsal of `rehearsal.worker.join`
(folding and page-a-split tasks), where every other reader is also held
to the value it gives with the new attributes filtered out. Numbers
read here are the CPU's and never a device's.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from test_phase_metrics import read, span, statement

NEW = ("split_stage_ms", "split_ahead_share")
# the metrics the benchmark had before these two
ACCEPTED = 35
COUNTERS = ("stageMs", "prefetchedSplits", "prefetchStalls")


def staged_statement(stage_ms=6.0, prefetched=2, stalls=1):
    """test_phase_metrics' statement (one task, two splits) by a program
    whose task says how it staged them."""
    spans = statement()
    for sp in spans:
        if sp["name"] == "worker-task":
            sp["attributes"] = {"splits": 2, "stageMs": stage_ms,
                                "prefetchedSplits": prefetched,
                                "prefetchStalls": stalls}
    return spans


def test_readers_on_a_hand_built_statement():
    s = staged_statement()
    assert read("split_stage_ms", s) == pytest.approx(6.0 / 2)
    assert read("split_ahead_share", s) == pytest.approx(100.0 * 1 / 2)
    # the lap's own reader still reads the lap: (4 + 6) / 2
    assert read("split_put_ms", s) == pytest.approx(5.0)


def test_tasks_are_summed_over_the_statements_laps():
    """Two stages' tasks, three laps: one sum over one count."""
    s = staged_statement() + [
        span("worker-task", "wt2", "st", 95, 5, stageMs=1.5,
             prefetchedSplits=1, prefetchStalls=0),
        span("split", "s2", "wt2", 96, 3)]
    assert read("split_stage_ms", s) == pytest.approx(7.5 / 3)
    assert read("split_ahead_share", s) == pytest.approx(100.0 * 2 / 3)


def test_depth_zero_reads_no_split_ahead():
    s = staged_statement(stage_ms=10.0, prefetched=0, stalls=0)
    assert read("split_ahead_share", s) == 0.0
    assert read("split_stage_ms", s) == pytest.approx(5.0)


def test_median_is_taken_over_statements():
    a, b, c = (staged_statement(stage_ms=m) for m in (2.0, 6.0, 40.0))
    assert read("split_stage_ms", a, b, c) == pytest.approx(3.0)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_stage_ms_gives_none(metric):
    # the parent's spans: `worker-task` says nothing of staging
    assert read(metric, statement()) is None
    # a statement that ran no split; none at all
    local = [span("query", "q", None, 0, 50),
             span("execute", "e", "q", 1, 40)]
    assert read(metric, local) is None
    assert read(metric, []) is None
    assert read(metric) is None


def test_new_metrics_are_appended_beside_their_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    added = bench["per_layer"][ACCEPTED:ACCEPTED + len(NEW)]
    assert [m["name"] for m in added] == list(NEW)
    for m in added:
        assert (m["source"], m["layer"], m["moves"]) == \
            ("program_counter", "worker tasks and executor",
             "query_geomean_s")
        assert (m["unit"], m["better"]) == (
            ("ms", "lower") if m["name"] == "split_stage_ms"
            else ("%", "higher"))
        assert m["workloads"] == ["worker.scan", "worker.join",
                                  "worker.streams3"]
        assert os.path.exists(os.path.join(BENCH, "layers",
                                           f"{m['name']}.py"))


# one traced window of a rehearsal cell, driven through run.Cell so that
# the readers can be called on its statements with and without the
# attributes this file's metrics read
DRIVE = """
import importlib, json, sys
sys.path.insert(0, {bench!r})
import run
cell = run.Cell({rehearsal!r}, "rehearsal.worker.join", True)
try:
    cell.setup()
    w = cell.window(2147483779, 1.0)
    out = cell.report(w, 0.0)
finally:
    cell.close()
COUNTERS = {counters!r}

def old(sp):
    drop = COUNTERS if sp["name"] == "worker-task" else \
        ("ahead",) if sp["name"] == "split-put" else ()
    return dict(sp, attributes={{k: v for k, v in sp["attributes"].items()
                                if k not in drop}})

bare = dict(w, statements=[dict(s, spans=[old(sp) for sp in s["spans"]])
                           for s in w["statements"]])
names = [m["name"] for m in cell.bench["per_layer"]]
readers = {{n: importlib.import_module(f"layers.{{n}}") for n in names}}
tasks = [[sp["attributes"] for sp in s["spans"]
          if sp["name"] == "worker-task"] for s in w["statements"]]
puts = [[sp["attributes"] for sp in s["spans"] if sp["name"] == "split-put"]
        for s in w["statements"]]
print(json.dumps({{
    "reported": {{k: v["value"] for k, v in out["metrics"].items()}},
    "with": {{n: readers[n].read(w) for n in names}},
    "without": {{n: readers[n].read(bare) for n in names}},
    "tasks": tasks, "puts": puts}}))
"""


@pytest.fixture(scope="module")
def worker_join(rehearsal):
    p = subprocess.run(
        [sys.executable, "-c", DRIVE.format(bench=BENCH, rehearsal=rehearsal,
                                            counters=COUNTERS)],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_rehearsal_reads_both(worker_join):
    m = worker_join["reported"]
    assert m["split_stage_ms"] > 0
    assert 0 <= m["split_ahead_share"] <= 100
    assert m == {**m, **{n: worker_join["with"][n] for n in NEW}}
    for tasks, puts in zip(worker_join["tasks"], worker_join["puts"]):
        assert tasks and all(set(COUNTERS) <= set(t) for t in tasks)
        # every split was staged by the feeder or decoded by the loop,
        # and a lap says which
        assert sum(t["prefetchedSplits"] for t in tasks) <= \
            sum(t["splits"] for t in tasks) == len(puts)
        assert sum(p["ahead"] for p in puts) == sum(
            t["prefetchedSplits"] - t["prefetchStalls"] for t in tasks)
        assert all(p["bytes"] > 0 for p in puts)


def test_other_readers_read_what_they_read(worker_join):
    for name, value in worker_join["with"].items():
        if name not in NEW:
            assert value == worker_join["without"][name], name
    # and a program that stamps none of the counters has nothing for
    # the two to read
    assert all(worker_join["without"][n] is None for n in NEW)
