"""`worker.streams3` rehearsed on the CPU at sf0.05: three closed-loop
clients on a coordinator and a worker, q6 and q1 in rotation one
template apart, every stream's texts its own; the run ends with a result
line and is correct, the traced run reads the three lock metrics, a
broken answer and the float32 control are rejected. The three readers on
spans of a program that writes none of the new spans. Numbers here are
the CPU's, never a device's."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from test_rehearsal import BROKEN

CELL = "rehearsal.worker.streams3"
NEW_METRICS = ("exec_lock_wait_ms", "exec_lock_held_ms", "task_lock_wait_ms")


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rehearsal_streams3(tmp_path_factory):
    """BENCHMARK.json with its configurations and cells swapped for the
    one rehearsal cell, so the metrics and their readers are the real
    ones."""
    bench = bench_json()
    cell, = [w for w in bench["workloads"] if w["name"] == "worker.streams3"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tpch_sf10_worker_streams3", "streams3", 1)
    bench["configs"] = [{
        "name": "rehearsal_tiny_worker",
        "file": "benchmark/configs/rehearsal_tiny_worker.json"}]
    bench["workloads"] = [{"name": CELL, "config": "rehearsal_tiny_worker",
                           "traffic": "streams3", "chips": 1}]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [f"rehearsal.{w}" for w in m["workloads"]]
    path = str(tmp_path_factory.mktemp("streams3") / "rehearsal.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def run_tool(tool, args):
    return subprocess.run([sys.executable, os.path.join(BENCH, tool)] + args,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=900)


def sent_by_client(stdout):
    """{client: [(template, parameters text), ...]} from the `statement`
    lines, in the order each client sent them."""
    out = {}
    for ln in stdout.splitlines():
        if "] statement " not in ln:
            continue
        who, template, rest = ln.split("] statement ")[1].split(" ", 2)
        client, n = who.split(".")
        params = rest.split("} ")[0] + "}"
        out.setdefault(int(client), {})[int(n)] = (template, params)
    return {c: [by_n[n] for n in sorted(by_n)] for c, by_n in out.items()}


def test_the_traffic_is_three_closed_loop_streams_of_q6_and_q1():
    import traffic
    mix = traffic.load_mix("streams3")
    assert (mix["loop"], mix["clients"], mix["templates"]) == \
        ("closed", 3, ["q6", "q1"])
    streams = [traffic.Stream(mix, 3400000007, "tpch.sf10", c)
               for c in range(3)]
    firsts = [[next(s)[0].NAME for _ in range(4)] for s in streams]
    # one template apart
    assert firsts == [["q6", "q1", "q6", "q1"], ["q1", "q6", "q1", "q6"],
                      ["q6", "q1", "q6", "q1"]]
    # disjoint slices: q1's 60 sets give each stream 20
    pools = [[json.dumps(p, sort_keys=True) for p in s.pools[1]]
             for s in streams]
    assert [len(p) for p in pools] == [20, 20, 20]
    assert len(set(sum(pools, []))) == 60


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_run(rehearsal_streams3, trace):
    p = run_tool("run.py", ["--workload", CELL, "--seed", "3400000011",
                            "--seconds", "2", "--trace", str(trace),
                            "--benchmark-file", rehearsal_streams3])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0, p.stdout[-3000:]
    assert all(v == [0, 0] for v in out["checks"].values())
    assert out["device"]["platform"] == "cpu"
    # three clients, whole rounds each, every one ended by the clock
    sent = sent_by_client(p.stdout)
    assert sorted(sent) == [0, 1, 2]
    assert sum(len(v) for v in sent.values()) == out["attempted"] >= 6
    for c in range(3):
        assert f"client {c}: window ended after {len(sent[c])} " \
            "statements" in p.stdout
        assert len(sent[c]) % 2 == 0
        first = ("q6", "q1")[c % 2]
        assert [t for t, _ in sent[c][:2]] == \
            [first, "q1" if first == "q6" else "q6"]
    assert p.stdout.count("by the clock") == 3
    # no text twice, within a stream or across them
    texts = [s for v in sent.values() for s in v]
    assert len(set(texts)) == len(texts)
    assert "compiles in the window: 0 " in p.stdout
    bench = bench_json()
    if trace == 0:
        assert set(out["metrics"]) == {m["name"] for m in
                                       bench["end_to_end"]}
        return
    listed = {m["name"] for m in bench["per_layer"]
              if "workloads" not in m or "worker.streams3" in m["workloads"]}
    assert set(NEW_METRICS) <= listed
    # the CPU backend reports no peak memory; everything else reads
    assert set(out["metrics"]) == listed - {"peak_hbm_gb"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["spool_hits"] == 0 and m["compiles_in_window"] == 0
    # three streams meet at the coordinator's lock, and nowhere else
    assert m["exec_lock_wait_ms"] > 0 and m["exec_lock_held_ms"] > 0
    assert 0 <= m["task_lock_wait_ms"] < m["exec_lock_held_ms"]
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]


def test_broken_answer_is_not_correct(rehearsal_streams3):
    argv = ["--workload", CELL, "--seed", "77", "--seconds", "1",
            "--trace", "0", "--benchmark-file", rehearsal_streams3]
    p = subprocess.run(
        [sys.executable, "-c", BROKEN.format(
            bench=BENCH, root=ROOT,
            needle="sum(l_extendedprice * l_discount)", column=0,
            argv=argv)],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False
    assert any(name.startswith("mismatched_cells.q6.") and value > limit
               for name, (value, limit) in out["checks"].items())


def test_control_is_rejected_and_sound_windows_pass(rehearsal_streams3):
    p = run_tool("prove.py", ["--workload", CELL, "--seeds",
                              "3400000012,5", "--seconds", "1",
                              "--control", "1", "--benchmark-file",
                              rehearsal_streams3])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 2
    assert all(ln["correct"] and ln["control_mismatched_cells"] > 0
               for ln in lines)


def span(name, ms, **attributes):
    return {"name": name, "durationMs": ms, "attributes": attributes}


def test_readers_on_spans_with_and_without_the_new_spans():
    readers = {n: importlib.import_module(f"layers.{n}")
               for n in NEW_METRICS}
    # an older program: `exec-lock-wait` without `ahead`, no held time,
    # no span around the worker's lock
    old = {"statements": [{"spans": [
        span("query", 2100.0), span("exec-lock-wait", 4000.0),
        span("source-stage", 2000.0, splits=240),
        span("worker-task", 1950.0)]}]}
    assert [readers[n].read(old) for n in NEW_METRICS] == [None] * 3
    assert all(r.read({"statements": []}) is None
               for r in readers.values())
    assert all(r.read({"statements": [{}]}) is None
               for r in readers.values())
    new = {"statements": [
        {"spans": [span("query", wait + held + 5.0),
                   span("exec-lock-wait", wait, ahead=ahead),
                   span("exec-lock-held", held),
                   span("task-lock-wait", 0.02),
                   span("task-lock-wait", task),
                   span("worker-task", held - 100.0)]}
        for wait, held, ahead, task in ((4400.0, 1500.0, 2, 0.01),
                                        (3000.0, 3000.0, 2, 0.03),
                                        (0.1, 1600.0, 0, 0.05))]}
    assert readers["exec_lock_wait_ms"].read(new) == 3000.0
    assert readers["exec_lock_held_ms"].read(new) == 1600.0
    assert readers["task_lock_wait_ms"].read(new) == pytest.approx(0.05)
    # a statement that took the lock twice (the cluster path declined,
    # the local path asked again): both waits, both holds
    twice = {"statements": [{"spans": [
        span("exec-lock-wait", 10.0, ahead=1), span("exec-lock-held", 5.0),
        span("exec-lock-wait", 20.0, ahead=0),
        span("exec-lock-held", 7.0)]}]}
    assert readers["exec_lock_wait_ms"].read(twice) == 30.0
    assert readers["exec_lock_held_ms"].read(twice) == 12.0
    assert readers["task_lock_wait_ms"].read(twice) is None
