"""`worker.heavyagg` rehearsed on the CPU at sf1, the smallest scale at
which TPC-H's own QUANTITY sets 312-315 keep rows, on a coordinator
plus one worker: the window ends when q18's four sets are drawn, every
statement ran as distributed worker tasks and is compared with the
plain reference, the traced run reads the three metrics the cell
brought, and the float32 control is rejected. The three readers on
spans with and without `subquery-fold`; the configuration's file; what
`BENCHMARK.json` gained. Numbers here are the CPU's, never a device's."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELL = "rehearsal.worker.heavyagg"
CONFIG = "tpch_sf10_worker_q18"
NEW_METRICS = ("subquery_fold_ms", "subquery_put_mb", "build_stage_ms")


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rehearsal_sf1(tmp_path_factory):
    """BENCHMARK.json with its configurations and cells swapped for the
    one rehearsal cell, so the metrics and their readers are the real
    ones."""
    bench = benchmark_json()
    assert any(w["name"] == "worker.heavyagg" and w["traffic"] == "heavyagg"
               for w in bench["workloads"])
    bench["configs"] = [{
        "name": "rehearsal_sf1_worker",
        "file": "benchmark/configs/rehearsal_sf1_worker.json"}]
    bench["workloads"] = [{"name": CELL, "config": "rehearsal_sf1_worker",
                           "traffic": "heavyagg", "chips": 1}]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [f"rehearsal.{w}" for w in m["workloads"]]
    path = str(tmp_path_factory.mktemp("worker_heavyagg") / "rehearsal.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def run_tool(tool, args):
    return subprocess.run([sys.executable, os.path.join(BENCH, tool)] + args,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=1500)


@pytest.mark.parametrize("trace", [0, 1])
def test_window_ends_when_q18s_four_sets_are_drawn(rehearsal_sf1, trace):
    p = run_tool("run.py", ["--workload", CELL, "--seed", "4100000041",
                            "--seconds", "600", "--trace", str(trace),
                            "--benchmark-file", rehearsal_sf1])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0, p.stdout[-3000:]
    assert out["attempted"] == 4
    assert "by q18's domain: all 4 sets drawn" in p.stdout
    assert "warm-up q18 {'quantity': 300}" in p.stdout
    statements = [ln for ln in p.stdout.splitlines() if "] statement " in ln]
    assert sorted(int(ln.split('{"quantity": ')[1].split("}")[0])
                  for ln in statements) == [312, 313, 314, 315]
    # through the worker, never the coordinator's own executor
    assert all("distributed=True" in ln and "fallback=None" in ln
               for ln in statements)
    assert "compiles in the window: 0 " in p.stdout
    assert all(v == [0, 0] for v in out["checks"].values())
    assert out["checks"]["off_device_statements"] == [0, 0]
    bench = benchmark_json()
    if trace == 0:
        assert set(out["metrics"]) == {m["name"] for m in
                                       bench["end_to_end"]}
        return
    listed = {m["name"] for m in bench["per_layer"]
              if "workloads" not in m or "worker.heavyagg" in m["workloads"]}
    assert set(NEW_METRICS) <= listed
    # the CPU backend reports no peak memory; everything else reads
    assert set(out["metrics"]) == listed - {"peak_hbm_gb"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compiles_in_window"] == 0
    # the warm-up's fold put lineitem's two columns, and the worker's
    # executor keeps what it scans whole: a statement of the window
    # finds them
    assert m["subquery_put_mb"] == 0
    # the fold is part of the `orders` stage, a build stage
    assert 0 < m["subquery_fold_ms"] < m["build_stage_ms"]
    assert m["build_stage_ms"] < out["device"]["window_s"] * 1e3
    labels = {label for label, _ in out["breakdown"]["idle_gaps"]}
    assert any(label.startswith("q18:") for label in labels), labels


def test_control_is_rejected(rehearsal_sf1):
    p = run_tool("prove.py", ["--workload", CELL, "--seeds", "4100000042",
                              "--seconds", "600", "--control", "1",
                              "--benchmark-file", rehearsal_sf1])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    (line,) = [json.loads(ln) for ln in p.stdout.splitlines()
               if ln.startswith("{")]
    assert line["correct"] and line["attempted"] == 4
    assert line["checks"]["off_device_statements"] == [0, 0]
    # at sf1 every total price of the answer is past 2^24 cents
    assert line["control_mismatched_cells"] > 0
    # a window compares its first statement and two more
    assert sum("] control q18 " in ln for ln in p.stdout.splitlines()) == 3


def span(name, ms, **attributes):
    return {"name": name, "durationMs": ms, "attributes": attributes}


@pytest.fixture(scope="module")
def readers():
    return {n: importlib.import_module(f"layers.{n}") for n in NEW_METRICS}


def test_readers_on_spans_with_the_fold(readers):
    new = {"statements": [
        {"spans": [span("build-stage", 600.0, fragment=1),
                   span("build-stage", stage, fragment=2),
                   span("worker-task", stage - 50.0, splits=60),
                   span("subquery-fold", fold, kind="in", split=0,
                        inputRows=60_010_503, members=624,
                        putBytes=put),
                   span("aggregate", fold / 2)]}
        for stage, fold, put in ((9000.0, 6000.0, 960_168_048),
                                 (9400.0, 6100.0, 960_168_048),
                                 (12900.0, 9900.0, 0))]}
    assert readers["subquery_fold_ms"].read(new) == 6100.0
    assert readers["subquery_put_mb"].read(new) == 960.168048
    assert readers["build_stage_ms"].read(new) == 10000.0
    # two folds in one statement (an IN and a scalar subquery) are one sum
    two = {"statements": [{"spans": [
        span("subquery-fold", 10.0, kind="in", putBytes=4_000_000),
        span("subquery-fold", 2.5, kind="scalar", putBytes=0)]}]}
    assert readers["subquery_fold_ms"].read(two) == 12.5
    assert readers["subquery_put_mb"].read(two) == 4.0
    assert readers["build_stage_ms"].read(two) is None


def test_readers_on_spans_without_the_fold(readers):
    """The parent's shape: the stages and the task, no `subquery-fold`:
    the stage metric reads, the fold's two have nothing to read."""
    old = {"statements": [{"spans": [
        span("build-stage", 600.0, fragment=1),
        span("build-stage", 9000.0, fragment=2),
        span("worker-task", 8950.0, splits=60, literalSlots=1),
        span("scan", 900.0, table="lineitem", putBytes=960_168_048),
        span("aggregate", 3000.0)]}]}
    assert readers["subquery_fold_ms"].read(old) is None
    assert readers["subquery_put_mb"].read(old) is None
    assert readers["build_stage_ms"].read(old) == 9600.0
    # the single-node route has no stage
    single = {"statements": [{"spans": [
        span("execute", 1180.0, scanPutBytes=0),
        span("subquery-fold", 470.0, kind="in", putBytes=0)]}]}
    assert readers["build_stage_ms"].read(single) is None
    assert readers["subquery_put_mb"].read(single) == 0.0
    for r in readers.values():
        assert r.read({"statements": []}) is None
        assert r.read({"statements": [{"spans": []}]}) is None


def test_the_configurations_file_says_what_a_deployment_is():
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        conf = json.load(f)
    assert conf["name"] == CONFIG
    for key in ("source", "deployment", "data", "guarantees", "reduced",
                "assumed"):
        assert conf[key], key
    assert sorted(conf["reduced"]) == ["scale_factor", "templates"]
    with open(os.path.join(BENCH, "configs", "tpch_sf10_worker.json")) as f:
        sibling = json.load(f)
    for key in ("platform", "chips", "workers", "catalog", "schema"):
        assert conf["deployment"][key] == sibling["deployment"][key], key
    assert conf["data"]["rows"] == sibling["data"]["rows"]
    assert set(conf["guarantees"]) >= {"results", "caches", "execution"}
    assert "2.4.18" in conf["source"] and "4.1.3" in conf["source"]


def test_benchmark_json_gained_entries_and_lost_nothing():
    """The older entries are what the parent commit has, byte for byte,
    and the new ones follow them."""
    p = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
                       capture_output=True, text=True)
    if p.returncode:
        pytest.skip("not a git checkout")
    old = json.loads(p.stdout)
    if any(w["name"] == "worker.heavyagg" for w in old["workloads"]):
        pytest.skip("HEAD has the cell: nothing to compare it with")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    new = json.loads(text)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert new[key] == old[key], key
    for key, added in (("configs", 1), ("workloads", 1), ("per_layer", 3)):
        assert new[key][:len(old[key])] == old[key], key
        assert len(new[key]) == len(old[key]) + added, key
    # byte for byte: every line of the parent's file is a line of this
    # one (a list's last entry gained its comma), in the same order
    lines = iter(ln.rstrip(",") for ln in text.splitlines())
    assert all(any(ln == want.rstrip(",") for ln in lines)
               for want in p.stdout.splitlines())
    (config,) = new["configs"][-1:]
    (cell,) = new["workloads"][-1:]
    assert config["name"] == CONFIG and config["file"] == \
        f"benchmark/configs/{CONFIG}.json"
    assert config["reduced"] == ["scale_factor", "templates"]
    assert cell == dict(cell, name="worker.heavyagg", config=CONFIG,
                        traffic="heavyagg", chips=1)
    assert [m["name"] for m in new["per_layer"][-3:]] == list(NEW_METRICS)
    for m in new["per_layer"][-3:]:
        assert m["moves"] == "query_geomean_s" and m["better"] == "lower"
        assert os.path.exists(os.path.join(BENCH, "layers",
                                           f"{m['name']}.py"))
    fold, put, stage = new["per_layer"][-3:]
    assert fold["workloads"] == put["workloads"] == \
        ["worker.heavyagg", "single.heavyagg"]
    assert stage["workloads"] == ["worker.heavyagg"]
    assert (fold["source"], put["source"], stage["source"]) == \
        ("program_span", "program_counter", "program_span")
    assert fold["layer"] == put["layer"] == "worker tasks and executor"
    assert stage["layer"] == "scheduling and exchange"
    for entry in (config, cell):
        assert 0 < len(entry["why"]) <= 200
    assert len(config["source"]) <= 200
