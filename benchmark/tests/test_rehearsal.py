"""run.py end to end on the CPU: both deployments, both trace modes, on
the rehearsal configurations (tpch sf0.05, platform cpu, in no cell of
BENCHMARK.json). What it shows is that the harness runs and checks; its
numbers are the CPU's and are never a device's.

Also here: the cells of BENCHMARK.json refuse to run without their chip;
a run whose timed path is broken underneath comes out not correct; and
the float32 control, at the rehearsal's size, is rejected.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

RUN = os.path.join(BENCH, "run.py")
CELLS = ["worker.scan", "worker.join", "single.join", "single.scan"]


def run_cell(args, cwd=ROOT):
    return subprocess.run([sys.executable, RUN] + args, cwd=cwd,
                          capture_output=True, text=True, timeout=600)


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_run(rehearsal, cell, trace):
    p = run_cell(["--workload", f"rehearsal.{cell}", "--seed", "2147483777",
                  "--seconds", "2", "--trace", str(trace),
                  "--benchmark-file", rehearsal])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, p.stdout[-3000:]
    assert out["failed"] == 0 and out["attempted"] >= 2
    # the result names the CPU it ran on
    assert out["device"]["platform"] == "cpu"
    bench = bench_json()
    if trace == 0:
        assert set(out["metrics"]) == {m["name"] for m in
                                       bench["end_to_end"]}
        assert "breakdown" not in out
    else:
        listed = {m["name"] for m in bench["per_layer"]
                  if "workloads" not in m or cell in m["workloads"]}
        # the CPU backend reports no peak memory; everything else reads
        assert set(out["metrics"]) == listed - {"peak_hbm_gb"}
        assert out["metrics"]["spool_hits"]["value"] == 0
        # new literals in every statement compile nothing (PR 30). A
        # whole-table q3 at the rehearsal's 75,000 orders still does:
        # its join and aggregate capacities follow the data, and at this
        # size every parameter set lands them on another
        if cell != "single.join":
            assert out["metrics"]["compiles_in_window"]["value"] == 0
        assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
        assert out["breakdown"]["device_ops"]
        assert out["breakdown"]["idle_gaps"]
    for m in out["metrics"].values():
        assert isinstance(m["value"], (int, float)) and m["unit"]
    assert len(sent_once(p.stdout)) == out["attempted"]
    # each number compared, beside its limit: the last lines of standard
    # error, and the result line's last key
    assert list(out)[-1] == "checks"
    assert p.stderr.strip().splitlines()[-len(out["checks"]):] == [
        f"check {name}: {value} (limit {limit})"
        for name, (value, limit) in out["checks"].items()]
    assert "by the clock" in p.stdout


def sent_once(stdout):
    """The `statement` lines of a run, one a statement: template and
    parameters, none printed twice (none was sent twice)."""
    sent = [ln.split("] statement ")[1].split("s distributed=")[0]
            .rsplit(" ", 1)[0].split(" ", 1)[1]
            for ln in stdout.splitlines() if "] statement " in ln]
    assert len(sent) == len(set(sent))
    return sent


def test_window_ends_when_q1s_domain_is_spent(rehearsal):
    """A window far longer than the traffic's TPC-H domains last: 60
    rounds of q6 + q1, then a result line, not `domain exhausted`."""
    p = run_cell(["--workload", "rehearsal.single.scan", "--seed",
                  "3000000019", "--seconds", "600", "--trace", "0",
                  "--benchmark-file", rehearsal])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 120 == len(sent_once(p.stdout))
    assert out["checks"]["repeated_statements"] == [0, 0]
    assert "after 120 statements" in p.stdout
    assert "by q1's domain: all 60 sets drawn" in p.stdout


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  bench_json()["workloads"]])
def test_no_tpu_no_number(cell):
    """Here JAX is held to the CPU: every cell of BENCHMARK.json exits
    non-zero and prints no result line."""
    p = run_cell(["--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert p.returncode == 2, p.stdout[-2000:] + p.stderr[-2000:]
    assert "nothing was run" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_same_seed_same_statements(rehearsal):
    """Two runs with one seed send the same statements in the same
    order and compile as often inside the window."""
    outs = []
    for _ in range(2):
        p = run_cell(["--workload", "rehearsal.single.scan", "--seed", "31",
                      "--seconds", "0.5", "--trace", "0",
                      "--benchmark-file", rehearsal])
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        sent = [ln.split("] statement ")[1].split("} ")[0]
                for ln in p.stdout.splitlines() if "] statement " in ln]
        outs.append(sent)
    n = min(len(outs[0]), len(outs[1]))
    assert n >= 2 and outs[0][:n] == outs[1][:n]


BROKEN = """
import sys
sys.path.insert(0, {bench!r})
sys.path.insert(0, {root!r})
from trino_tpu.client import client as c
real = c.Client.execute
def broken(self, sql):
    res = real(self, sql)
    if {needle!r} in sql and res.rows:
        res.rows[0] = list(res.rows[0])
        cell = str(res.rows[0][{column}])
        # the answer altered where it is produced: one unit in the last
        # place of one cell of one row
        res.rows[0][{column}] = cell[:-1] + str((int(cell[-1]) + 1) % 10)
    return res
c.Client.execute = broken
import run
sys.exit(run.main({argv!r}))
"""


@pytest.mark.parametrize("cell,needle,column", [
    ("worker.scan", "sum(l_extendedprice * l_discount)", 0),
    ("single.join", "GROUP BY l_orderkey", 1),
    ("single.scan", "count(*) AS count_order", 2)])
def test_broken_timed_path_is_not_correct(rehearsal, cell, needle, column):
    argv = ["--workload", f"rehearsal.{cell}", "--seed", "77",
            "--seconds", "1", "--trace", "0", "--benchmark-file", rehearsal]
    p = subprocess.run(
        [sys.executable, "-c", BROKEN.format(
            bench=BENCH, root=ROOT, needle=needle, column=column,
            argv=argv)],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False
    assert any(name.startswith("mismatched_cells.") and value > limit
               for name, (value, limit) in out["checks"].items())
    assert "check mismatched_cells." in p.stderr


@pytest.mark.parametrize("cell", ["worker.scan", "single.join",
                                  "single.scan"])
def test_control_is_rejected_and_sound_windows_pass(rehearsal, cell):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "prove.py"), "--workload",
         f"rehearsal.{cell}", "--seeds", "3000000019,5", "--seconds", "1",
         "--control", "1", "--benchmark-file", rehearsal],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 2
    assert all(ln["correct"] and ln["control_mismatched_cells"] > 0
               for ln in lines)


def test_bare_checkout_fails_without_a_result(tmp_path, rehearsal):
    """A directory that holds only BENCHMARK.json and benchmark/: the
    program is not there, so no number comes out (a rehearsal cell, so
    that the look for a chip is not what stops it)."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "rehearsal.worker.scan", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--benchmark-file", rehearsal],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "trino_tpu" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
