#!/usr/bin/env python3
"""Several windows of one cell in one process, one a seed, each checked
as a run is; with `--control 1` the lower-precision control beside them.
The tool the limits were read with (PERF.md section 2): set-up is most
of a run, and this pays it once for a dozen seeds.

    python3 benchmark/prove.py --workload worker.scan \\
        --seeds 11,12,13 --seconds 15 --control 1

The control is the plain reference itself, accumulating in float32 where
the types demand 64-bit integers, put in the engine's place: the same
comparison must call it not correct. It prints one JSON line a seed and
exits 1 if a sound window is not correct or a control passes.
"""

import argparse
import json
import sys
import time

import run as harness
import compare


def control(cell, run) -> int:
    """Cells of the narrow reference that the comparison rejects, over
    the run's sample. 0 means the comparison cannot tell 32 from 64 bits."""
    by_name = {t.NAME: t for t in cell.templates}
    total = 0
    for s in cell.sample(run):
        t = by_name[s["template"]]
        exact = t.reference(cell.tables, s["params"])
        narrow = t.reference(cell.tables, s["params"], narrow=True)
        as_protocol = [[_text(v, kind) for v, (_, kind) in
                        zip(row, t.COLUMNS)] for row in narrow]
        n, first = compare.mismatched_cells(as_protocol, exact, t.COLUMNS)
        harness.say(f"control {t.NAME} {json.dumps(s['params'])}: "
                    f"mismatched_cells {n} (limit 0): {first}")
        total += n
    return total


def _text(value, kind):
    """A reference's value as the protocol would carry it."""
    if isinstance(kind, tuple):
        sign, digits = ("-" if value < 0 else ""), str(abs(value))
        scale = kind[1]
        digits = digits.rjust(scale + 1, "0")
        return f"{sign}{digits[:-scale]}.{digits[-scale:]}"
    return value


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark-file",
                    default=harness.os.path.join(harness.ROOT,
                                                 "BENCHMARK.json"))
    args = ap.parse_args(argv)
    cell = harness.Cell(args.benchmark_file, args.workload, False)
    bad = 0
    try:
        cell.setup()
        setup_s = time.monotonic() - harness.T_PROCESS
        for seed in (int(x) for x in args.seeds.split(",")):
            harness.say(f"window of {args.seconds:g}s, seed {seed}")
            cell.dep.clear_spool()
            run = cell.window(seed, args.seconds)
            out = cell.report(run, setup_s)
            checks = cell.check(run)
            harness.print_checks(checks)
            correct = harness.passed(checks)
            line = dict(seed=seed, correct=correct, **out, checks=checks)
            if args.control:
                line["control_mismatched_cells"] = control(cell, run)
                bad += line["control_mismatched_cells"] == 0
            bad += not correct
            print(json.dumps(line), flush=True)
    finally:
        cell.close()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
