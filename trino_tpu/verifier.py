"""Query verifier: control-vs-test result diffing.

Reference: service/trino-verifier (Verifier.java:57, Validator.java) runs
every query against a control and a test cluster and reports row-level
differences — the correctness harness behind "identical results" claims.

Here: control = sqlite3 over the same generated data (the oracle), test =
this engine. Usable as a library (`Verifier.run_suite`) or a CLI:

    python -m trino_tpu.verifier --suite tpch
    python -m trino_tpu.verifier --suite tpcds
    python -m trino_tpu.verifier -e "SELECT count(*) FROM nation"
"""

from __future__ import annotations

import argparse
import sqlite3
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .exec.session import Session


@dataclass
class VerifyResult:
    name: str
    status: str                  # MATCH | MISMATCH | CONTROL_ERROR |
                                 # TEST_ERROR | SKIPPED
    detail: str = ""
    control_rows: int = 0
    test_rows: int = 0
    control_ms: float = 0.0
    test_ms: float = 0.0


class Verifier:
    def __init__(self, session: Session, tables: List[str],
                 rel_tol: float = 1e-9, abs_tol: float = 0.01):
        self.session = session
        self.rel_tol = rel_tol
        self.abs_tol = abs_tol
        self._load_control(tables)

    def _load_control(self, tables: List[str]) -> None:
        from .connectors.tpch.datagen import TableData  # noqa: F401
        conn = self.session.catalog.connector(self.session.default_cat)
        datasets = [conn.get_table(self.session.default_schema, t)
                    for t in tables]
        # reuse the oracle loader living beside the tests when available;
        # otherwise load directly
        self.control = _load_sqlite(datasets)

    # per-query wall cap: a query stuck inside a native call (a long
    # compile, a device that another process holds) cannot be interrupted
    # by a signal, so the watchdog is a thread that records the timeout
    # and hard-exits the process — with --resume, the next invocation
    # picks up after the recorded queries (Verifier.java's per-query
    # timeout)
    query_timeout_s: Optional[float] = None
    on_timeout = None          # callable(name) -> None, set by the CLI

    def verify(self, name: str, sql: str,
               control_sql: Optional[str] = None) -> VerifyResult:
        t0 = time.monotonic()
        watchdog = None
        try:
            if self.query_timeout_s:
                import os
                import threading

                def _expired():
                    if self.on_timeout is not None:
                        try:
                            self.on_timeout(name)
                        except Exception:    # noqa: BLE001
                            pass
                    print(f"TIMEOUT {name}: exceeded "
                          f"{self.query_timeout_s}s; "
                          f"exiting — rerun with --resume", flush=True)
                    os._exit(3)
                watchdog = threading.Timer(self.query_timeout_s, _expired)
                watchdog.daemon = True
                watchdog.start()
            test_rows = self.session.execute(sql).rows
        except Exception as e:            # noqa: BLE001
            return VerifyResult(name, "TEST_ERROR", f"{e}")
        finally:
            if watchdog is not None:
                watchdog.cancel()
        test_ms = (time.monotonic() - t0) * 1000
        t0 = time.monotonic()
        try:
            cur = self.control.execute(
                _translate(control_sql or sql))
            control_rows = cur.fetchall()
        except Exception as e:            # noqa: BLE001
            return VerifyResult(name, "CONTROL_ERROR", f"{e}")
        control_ms = (time.monotonic() - t0) * 1000
        diff = self._diff(test_rows, control_rows)
        return VerifyResult(
            name, "MATCH" if diff is None else "MISMATCH", diff or "",
            len(control_rows), len(test_rows), control_ms, test_ms)

    def _diff(self, got, want) -> Optional[str]:
        if len(got) != len(want):
            return f"row count: test={len(got)} control={len(want)}"
        for i, (g, w) in enumerate(zip(got, want)):
            if len(g) != len(w):
                return f"row {i} arity: {len(g)} vs {len(w)}"
            for j, (a, b) in enumerate(zip(g, w)):
                if a is None or b is None:
                    if a is not b and not (a is None and b is None):
                        return f"row {i} col {j}: {a!r} != {b!r}"
                    continue
                if isinstance(a, float) or isinstance(b, float) or \
                        type(a).__name__ == "Decimal":
                    af, bf = float(a), float(b)
                    tol = max(self.abs_tol,
                              self.rel_tol * max(abs(af), abs(bf)))
                    if abs(af - bf) > tol:
                        return f"row {i} col {j}: {af} != {bf}"
                elif str(a) != str(b) and a != b:
                    return f"row {i} col {j}: {a!r} != {b!r}"
        return None

    def run_suite(self, queries: Dict[object, str],
                  on_result=None) -> List[VerifyResult]:
        out = []
        for k, sql in sorted(queries.items(), key=lambda kv: str(kv[0])):
            r = self.verify(str(k), sql)
            if on_result is not None:
                on_result(r)
            out.append(r)
        return out


# -- sqlite loading / dialect translation (shared with tests/oracle.py) ----

class _SqliteVar:
    """Welford variance aggregate for the sqlite control (it ships none)."""
    samp = True
    sqrt = False

    def __init__(self):
        self.n, self.mean, self.m2 = 0, 0.0, 0.0

    def step(self, x):
        if x is None:
            return
        self.n += 1
        d = x - self.mean
        self.mean += d / self.n
        self.m2 += d * (x - self.mean)

    def finalize(self):
        denom = (self.n - 1) if self.samp else self.n
        if denom <= 0:
            return None
        v = self.m2 / denom
        return v ** 0.5 if self.sqrt else v


def _load_sqlite(datasets) -> sqlite3.Connection:
    import numpy as np

    from .types import TypeKind
    conn = sqlite3.connect(":memory:")
    for name, samp, sq in [("var_samp", True, False),
                           ("variance", True, False),
                           ("var_pop", False, False),
                           ("stddev", True, True),
                           ("stddev_samp", True, True),
                           ("stddev_pop", False, True)]:
        cls = type(name, (_SqliteVar,), {"samp": samp, "sqrt": sq})
        conn.create_aggregate(name, 1, cls)
    for t in datasets:
        cols = []
        for f in t.schema:
            k = f.dtype.kind
            if k in (TypeKind.VARCHAR, TypeKind.DATE):
                cols.append(f"{f.name} TEXT")
            elif k in (TypeKind.DOUBLE, TypeKind.DECIMAL):
                cols.append(f"{f.name} REAL")
            else:
                cols.append(f"{f.name} INTEGER")
        conn.execute(f"CREATE TABLE {t.name} ({', '.join(cols)})")
        host_cols = []
        for f, arr in zip(t.schema, t.columns):
            k = f.dtype.kind
            if k is TypeKind.VARCHAR:
                pool = np.array(f.dictionary, dtype=object)
                host_cols.append(pool[np.asarray(arr)])
            elif k is TypeKind.DATE:
                base = np.datetime64("1970-01-01")
                host_cols.append((base + np.asarray(arr)).astype(str))
            elif k is TypeKind.DECIMAL:
                host_cols.append(np.asarray(arr) / (10 ** f.dtype.scale))
            else:
                host_cols.append(np.asarray(arr))
        if t.valids is not None:
            for j, v in enumerate(t.valids):
                if v is None:
                    continue
                col = np.asarray(host_cols[j], dtype=object)
                col[~np.asarray(v)] = None
                host_cols[j] = col
        rows = list(zip(*[c.tolist() for c in host_cols]))
        ph = ", ".join("?" * len(t.schema))
        conn.executemany(f"INSERT INTO {t.name} VALUES ({ph})", rows)
        # surrogate-key indexes keep sqlite's nested-loop plans tractable
        # on star-join benchmark queries
        for f in t.schema:
            if f.name.endswith("_sk") or f.name.endswith("key"):
                conn.execute(f"CREATE INDEX IF NOT EXISTS "
                             f"idx_{t.name}_{f.name} ON {t.name}({f.name})")
    conn.execute("ANALYZE")
    conn.commit()
    return conn


def _translate(sql: str) -> str:
    """Engine dialect -> sqlite (DATE literals, interval folding,
    EXTRACT)."""
    import datetime
    import re

    def fold_interval(m):
        d = datetime.date.fromisoformat(m.group(1))
        n = int(m.group(3))
        unit = m.group(4).lower().rstrip("s")
        sign = -1 if m.group(2) == "-" else 1
        if unit == "day":
            d2 = d + datetime.timedelta(days=sign * n)
        else:
            months = sign * n * (12 if unit == "year" else 1)
            y, m0 = divmod(d.year * 12 + d.month - 1 + months, 12)
            day = min(d.day, 28)
            d2 = datetime.date(y, m0 + 1, day)
        return f"'{d2.isoformat()}'"

    sql = re.sub(
        r"DATE\s*'(\d{4}-\d{2}-\d{2})'\s*([+-])\s*INTERVAL\s*"
        r"'(\d+)'\s*(\w+)", fold_interval, sql, flags=re.I)
    sql = re.sub(r"DATE\s*'(\d{4}-\d{2}-\d{2})'", r"'\1'", sql,
                 flags=re.I)
    sql = re.sub(r"EXTRACT\s*\(\s*YEAR\s+FROM\s+([^)]+)\)",
                 r"CAST(strftime('%Y', \1) AS INTEGER)", sql, flags=re.I)
    sql = re.sub(r"\bsubstring\s*\(", "substr(", sql, flags=re.I)
    return sql


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="trino-tpu-verifier")
    ap.add_argument("--suite", choices=["tpch", "tpcds"])
    ap.add_argument("--execute", "-e", help="verify one statement")
    ap.add_argument("--schema", default="tiny")
    ap.add_argument("--platform", choices=["cpu", "tpu"],
                    help="force a JAX platform through the config API: "
                         "cpu to verify off-chip on a machine that has a "
                         "chip, tpu to fail instead of falling back")
    ap.add_argument("--timeout-s", type=float, default=0,
                    help="per-query wall cap (0 = none): turns a query "
                         "stuck in a native call into TEST_TIMEOUT")
    ap.add_argument("--resume", metavar="FILE",
                    help="append results to FILE (jsonl) and skip "
                         "queries already recorded there — a killed "
                         "sweep resumes instead of restarting")
    args = ap.parse_args(argv)

    if args.platform:
        import jax
        jax.config.update("jax_platforms",
                          "cpu" if args.platform == "cpu" else None)

    if args.suite == "tpcds":
        from .connectors.tpcds.connector import TABLE_NAMES
        session = Session(default_cat="tpcds", default_schema=args.schema)
        tables = list(TABLE_NAMES)
    else:
        from .connectors.tpch.connector import TABLE_NAMES
        session = Session(default_cat="tpch", default_schema=args.schema)
        tables = list(TABLE_NAMES)
    verifier = Verifier(session, tables)

    if args.execute:
        r = verifier.verify("adhoc", args.execute)
        print(f"{r.status}: {r.detail or f'{r.test_rows} rows'}")
        return 0 if r.status == "MATCH" else 1

    queries: Dict[object, str] = {}
    if args.suite == "tpch":
        sys.path.insert(0, "tests")
        try:
            from tpch_full import QUERIES as queries  # type: ignore
        except ImportError:
            pass
    elif args.suite == "tpcds":
        sys.path.insert(0, "tests")
        try:
            from tpcds_queries import QUERIES as queries  # type: ignore
        except ImportError:
            pass
    if args.timeout_s:
        verifier.query_timeout_s = args.timeout_s
        if args.resume:
            def _record_timeout(name):
                import json
                with open(args.resume, "a") as f:
                    f.write(json.dumps(
                        {"name": name, "status": "TEST_TIMEOUT",
                         "test_ms": args.timeout_s * 1000,
                         "detail": "watchdog hard-exit"}) + "\n")
            verifier.on_timeout = _record_timeout

    done = {}
    if args.resume:
        import json
        import os.path
        timeouts = {}
        if os.path.exists(args.resume):
            with open(args.resume) as f:
                for line in f:
                    rec = json.loads(line)
                    done[rec["name"]] = rec["status"]
                    if rec["status"] == "TEST_TIMEOUT":
                        timeouts[rec["name"]] = \
                            timeouts.get(rec["name"], 0) + 1
        # retry non-MATCH (a fresh attempt resumes cached compiles and
        # gets further), but give up on a query that timed out 3 times —
        # those count as FAILURES in the summary/exit code, never as
        # verified.
        gave_up = sorted(k for k in list(queries)
                         if str(k) in done and done[str(k)] != "MATCH"
                         and timeouts.get(str(k), 0) >= 3)
        queries = {k: v for k, v in queries.items()
                   if str(k) not in done or
                   (done[str(k)] != "MATCH" and
                    timeouts.get(str(k), 0) < 3)}
        if done:
            print(f"resuming: {len(done)} recorded, "
                  f"{len(queries)} to run, "
                  f"{len(gave_up)} given up (count as FAIL)", flush=True)
    else:
        gave_up = []

    def show(r):
        mark = "OK " if r.status == "MATCH" else "FAIL"
        print(f"{mark} {r.name:>6}  {r.status:14} test={r.test_ms:8.1f}ms "
              f"control={r.control_ms:8.1f}ms rows={r.test_rows}"
              + (f"  {r.detail}" if r.detail else ""), flush=True)
        if args.resume:
            import json
            with open(args.resume, "a") as f:
                f.write(json.dumps({"name": r.name, "status": r.status,
                                    "test_ms": r.test_ms,
                                    "detail": r.detail[:200]}) + "\n")

    results = verifier.run_suite(queries, on_result=show)
    fails = sum(r.status != "MATCH" for r in results) + len(gave_up)
    prior = sum(1 for s in done.values() if s == "MATCH")
    total = len(results) + prior + len(gave_up)
    print(f"{total - fails}/{total} queries verified identical"
          + (f" ({len(gave_up)} permanently timed out: "
             f"{', '.join(str(g) for g in gave_up)})" if gave_up else ""))
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
