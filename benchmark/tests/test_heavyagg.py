"""`single.heavyagg` rehearsed on the CPU at sf1, the smallest scale at
which TPC-H's own QUANTITY sets 312-315 keep rows: the window ends when
q18's four sets are drawn, every statement is compared with the plain
reference, the traced run reads the three operator metrics, and the
float32 control is rejected. The three readers on spans of a program
that writes no operator span. Numbers here are the CPU's, never a
device's."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELL = "rehearsal.single.heavyagg"
NEW_METRICS = ("agg_ms", "join_ms", "agg_capacity_retries")


@pytest.fixture(scope="module")
def rehearsal_sf1(tmp_path_factory):
    """BENCHMARK.json with its configurations and cells swapped for the
    one rehearsal cell, so the metrics and their readers are the real
    ones."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert any(w["name"] == "single.heavyagg" and w["traffic"] == "heavyagg"
               for w in bench["workloads"])
    bench["configs"] = [{
        "name": "rehearsal_sf1_single",
        "file": "benchmark/configs/rehearsal_sf1_single.json"}]
    bench["workloads"] = [{"name": CELL, "config": "rehearsal_sf1_single",
                           "traffic": "heavyagg", "chips": 1}]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [f"rehearsal.{w}" for w in m["workloads"]]
    path = str(tmp_path_factory.mktemp("heavyagg") / "rehearsal.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def run_tool(tool, args):
    return subprocess.run([sys.executable, os.path.join(BENCH, tool)] + args,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
def test_window_ends_when_q18s_four_sets_are_drawn(rehearsal_sf1, trace):
    p = run_tool("run.py", ["--workload", CELL, "--seed", "3200000033",
                            "--seconds", "600", "--trace", str(trace),
                            "--benchmark-file", rehearsal_sf1])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0, p.stdout[-3000:]
    assert out["attempted"] == 4
    assert "by q18's domain: all 4 sets drawn" in p.stdout
    assert "warm-up q18 {'quantity': 300}" in p.stdout
    sent = sorted(int(ln.split('{"quantity": ')[1].split("}")[0])
                  for ln in p.stdout.splitlines() if "] statement " in ln)
    assert sent == [312, 313, 314, 315]
    assert "compiles in the window: 0 " in p.stdout
    assert all(v == [0, 0] for v in out["checks"].values())
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if trace == 0:
        assert set(out["metrics"]) == {m["name"] for m in
                                       bench["end_to_end"]}
        return
    listed = {m["name"] for m in bench["per_layer"]
              if "workloads" not in m or "single.heavyagg" in m["workloads"]}
    assert set(NEW_METRICS) <= listed
    # the CPU backend reports no peak memory; everything else reads
    assert set(out["metrics"]) == listed - {"peak_hbm_gb"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["agg_capacity_retries"] == 0 and m["compiles_in_window"] == 0
    assert m["agg_ms"] > 0 and m["join_ms"] > 0
    # an operator's own wall lies inside the statement's `execute` span;
    # that one is not listed for this cell yet, so the slice's length
    # stands in: the traced slice is one statement
    assert m["agg_ms"] + m["join_ms"] < out["device"]["window_s"] * 1e3
    # the idle gaps of a Q18 carry its operators' names
    labels = {label for label, _ in out["breakdown"]["idle_gaps"]}
    assert labels & {"q18:aggregate", "q18:join", "q18:sort"}, labels


def test_control_is_rejected_and_every_set_is_compared(rehearsal_sf1):
    p = run_tool("prove.py", ["--workload", CELL, "--seeds",
                              "3200000034,7", "--seconds", "600",
                              "--control", "1", "--benchmark-file",
                              rehearsal_sf1])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 2
    for ln in lines:
        assert ln["correct"] and ln["attempted"] == 4
        # at sf1 every total price of the answer is past 2^24 cents
        assert ln["control_mismatched_cells"] > 0
    # a window compares its first statement and two more: two seeds'
    # orders reach all four sets between them, or say which is left
    compared = {ln.split("control q18 ")[1].split(": mismatched")[0]
                for ln in p.stdout.splitlines() if "] control q18 " in ln}
    assert len(compared) >= 3, compared


def span(name, ms, **attributes):
    return {"name": name, "durationMs": ms, "attributes": attributes}


def test_readers_on_spans_with_and_without_the_operator_spans():
    import importlib
    readers = {n: importlib.import_module(f"layers.{n}")
               for n in NEW_METRICS}
    # the parent's shape: `execute` with its resident counters, `scan`
    old = {"statements": [{"spans": [
        span("execute", 2300.0, residentBytes=1, scanPutBytes=0),
        span("scan", 2.0, table="lineitem")]}]}
    assert [readers[n].read(old) for n in NEW_METRICS] == [None] * 3
    assert all(r.read({"statements": []}) is None
               for r in readers.values())
    new = {"statements": [
        {"spans": [span("execute", 5000.0, aggCapacityRetries=r),
                   span("aggregate", agg), span("aggregate", 10.0),
                   span("join", join), span("join", 5.0),
                   span("sort", 1.0)]}
        for agg, join, r in ((3000.0, 1500.0, 0), (3100.0, 1400.0, 0),
                             (2900.0, 1600.0, 2))]}
    assert readers["agg_ms"].read(new) == 3010.0
    assert readers["join_ms"].read(new) == 1505.0
    assert readers["agg_capacity_retries"].read(new) == 0
