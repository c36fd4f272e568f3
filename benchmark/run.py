#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload worker.scan --seed 7 \\
        --seconds 51 --trace 0

One process (a chip belongs to one process): brings the cell's deployment
up, warms its templates up once each with their validation parameters,
closes the persistent compile cache, sends the cell's traffic for
`--seconds` seconds, then checks answers against the plain references.
Earlier lines say what happened; the last line of standard output is the
result as one JSON object. Without the platform the configuration needs,
the exit code is 2 and there is no result line.

Everything that belongs to one configuration, traffic mix, template or
per-layer metric is a file found by its name: configs/<config>.json,
traffic/<traffic>.json, queries/<template>.py, layers/<metric>.py.
"""

import time

T_PROCESS = time.monotonic()

import argparse            # noqa: E402
import importlib           # noqa: E402
import json                # noqa: E402
import math                # noqa: E402
import os                  # noqa: E402
import random              # noqa: E402
import shutil              # noqa: E402
import statistics          # noqa: E402
import sys                 # noqa: E402
import tempfile            # noqa: E402
import threading           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import compare             # noqa: E402
import traffic             # noqa: E402
import trace_reduce        # noqa: E402

# statements of the references' seeded sample, besides each template's
# first statement
CHECK_SAMPLE = 2


def say(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One cell's deployment, kept up for as many windows as the caller
    runs (run.py: one; prove.py: one a seed)."""

    def __init__(self, bench_file: str, workload: str, traced: bool):
        self.bench = load_json(bench_file)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            sys.exit(f"benchmark: no workload {workload!r} in {bench_file}; "
                     f"it has {sorted(cells)}")
        self.cell = cells[workload]
        conf = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(os.path.join(
            ROOT, conf[self.cell["config"]]["file"]))
        self.mix = traffic.load_mix(self.cell["traffic"])
        self.templates = [traffic.load_template(t)
                          for t in self.mix["templates"]]
        self.traced = traced
        self.cache_closed = False
        self.tmp = tempfile.mkdtemp(prefix="trino_tpu_bench_")

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        # the program's own variables, set before it is imported. Tables
        # are generated once a checkout and mapped from disk after that;
        # what earlier runs learned (routing history, host decisions
        # keyed by literals) stays out, or the second run of a seed
        # would not be the first one's equal
        os.environ["TRINO_TPU_DATA_CACHE"] = os.path.join(
            HERE, ".cache", "data")
        os.environ["TRINO_TPU_DECISION_CACHE"] = "0"
        os.environ["TRINO_TPU_HISTORY_PATH"] = os.path.join(
            self.tmp, "query_history.jsonl")
        import deploy
        self.deploy = deploy
        dep = self.config["deployment"]
        self.device = deploy.device_info(dep["platform"],
                                         int(self.cell["chips"]))
        self.cache = deploy.CacheCounter()
        t0 = time.monotonic()
        self.dep = deploy.Deployment(self.config,
                                     clients=int(self.mix["clients"]))
        wanted = {}
        for t in self.templates:
            for table, cols in t.TABLES.items():
                wanted.setdefault(table, set()).update(cols)
        self.tables = self.dep.tables(wanted)
        say(f"device {self.device}; deployment "
            f"{self.cell['config']} up and tables "
            + ", ".join(f"{k}={v['rows']:,}"
                        for k, v in self.tables.items())
            + f" loaded in {time.monotonic() - t0:.1f}s")
        client = self.dep.clients[0]
        if self.traced:
            # through the client, as a user would
            client.execute("SET SESSION enable_profiling = true")
            client.execute("SET SESSION enable_tracing = true")
        for t in self.templates:
            c0 = self.dep.counters()
            t0 = time.monotonic()
            res = client.execute(t.render(t.VALIDATION, self.dep.schema))
            facts = self.dep.statement_facts(client, res.query_id, False)
            c1 = self.dep.counters()
            say(f"warm-up {t.NAME} {t.VALIDATION}: "
                f"{time.monotonic() - t0:.2f}s, "
                f"{c1['compiles'] - c0['compiles']} compiles "
                f"({c1['compile_s'] - c0['compile_s']:.1f}s), "
                f"{route_of(facts['info'])}")
        say(f"persistent compile cache in set-up: {self.cache.hits} hits, "
            f"{self.cache.misses} misses")

    def close(self) -> None:
        try:
            if hasattr(self, "dep"):
                self.dep.close()
        finally:
            shutil.rmtree(self.tmp, ignore_errors=True)

    # -- the window --------------------------------------------------------

    def window(self, seed: int, seconds: float) -> dict:
        if not self.cache_closed:
            self.deploy.close_persistent_compile_cache()
            self.cache_closed = True
        n_clients = int(self.mix["clients"])
        streams = [traffic.Stream(self.mix, seed, self.dep.schema, c)
                   for c in range(n_clients)]
        statements, lock = [], threading.Lock()
        slice_n = len(self.templates) if self.traced else 0
        trace_dir = os.path.join(HERE, ".cache", "trace",
                                 self.cell["name"])
        import jax          # loaded long since, by the deployment
        if slice_n:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        before = self.dep.counters()
        t_open = time.monotonic()

        def one(client_no: int, n: int, stream) -> None:
            template, params, sql = next(stream)
            rec = {"template": template.NAME, "params": params, "sql": sql,
                   "client": client_no, "n": n,
                   "anchor": f"{trace_reduce.ANCHOR_PREFIX}"
                             f"{template.NAME}:{n}"}
            client = self.dep.clients[client_no]
            sliced = client_no == 0 and n < slice_n
            if sliced:
                note = jax.profiler.TraceAnnotation(rec["anchor"])
                note.__enter__()
            rec["t_post_ns"] = time.time_ns()
            t0 = time.monotonic()
            try:
                res = client.execute(sql)
                rec["t_done"] = time.monotonic()
                rec["latency_s"] = rec["t_done"] - t0
                rec["rows"] = res.rows
                rec["query_id"] = res.query_id
            except Exception as e:      # noqa: BLE001 — a failed
                # statement is counted, not fatal
                rec["error"] = f"{type(e).__name__}: {e}"
            finally:
                if sliced:
                    note.__exit__(None, None, None)
            if sliced and n == slice_n - 1:
                jax.profiler.stop_trace()
            rec["hbm_in_use"] = self.deploy.device_bytes("bytes_in_use")
            if "error" not in rec:
                rec.update(self.dep.statement_facts(
                    client, rec["query_id"], self.traced))
            with lock:
                statements.append(rec)

        def loop(client_no: int) -> None:
            # whole rotations: a client starts another round of its
            # templates while the clock has not passed `seconds` and its
            # stream has a round left, and the round in flight is read to
            # its end and counted. A window cut between a 3 s and a 7 s
            # template would make the rate jump with whichever got one
            # statement more; a second pass over a spent TPC-H domain
            # would re-send texts, and a re-sent text finds more than
            # its programs cached
            stream, n = streams[client_no], 0
            while time.monotonic() - t_open < seconds and \
                    stream.round_left():
                for _ in self.templates:
                    one(client_no, n, stream)
                    n += 1
            spent = stream.spent()
            say(f"client {client_no}: window ended after {n} statements "
                f"in {time.monotonic() - t_open:.1f}s, "
                + ("by the clock" if spent is None else
                   f"by {spent[0]}'s domain: all {spent[1]} sets drawn"))

        threads = [threading.Thread(target=loop, args=(c,),
                                    name=f"bench-client-{c}")
                   for c in range(1, n_clients)]
        for th in threads:
            th.start()
        try:
            loop(0)
        finally:
            for th in threads:
                th.join()
        after = self.dep.counters()
        done = [s for s in statements if "error" not in s]
        t_last = max((s["t_done"] for s in done), default=time.monotonic())
        run = {"seed": seed, "statements": done,
               "failed": [s for s in statements if "error" in s],
               "before": before, "after": after,
               "window_s": t_last - t_open,
               "peak_bytes": self.deploy.device_bytes(),
               "clients": n_clients, "trace": None}
        if slice_n:
            run["trace"] = self._reduce(trace_dir, run)
        return run

    def _reduce(self, trace_dir: str, run: dict):
        import glob
        files = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not files:
            say("trace: the profiler wrote no .xplane.pb")
            return None
        red = trace_reduce.reduce_file(files[0], self.device["platform"])
        sliced = [s for s in run["statements"]
                  if s["anchor"] in red["anchors"]]
        red["statements"] = len(sliced)
        red["idle_gaps"] = trace_reduce.label_gaps(red, sliced)
        red["fenced_device_s"] = sum(
            (s.get("fenced") or {}).get("device_ms", 0.0)
            for s in sliced) / 1e3
        red["file_bytes"] = os.path.getsize(files[0])
        return red

    # -- after the window --------------------------------------------------

    def sample(self, run: dict) -> list:
        """The statements whose answers are compared: each template's
        first, and a seeded sample of the rest."""
        firsts, rest = {}, []
        for s in sorted(run["statements"],
                        key=lambda s: (s["client"], s["n"])):
            if s["template"] not in firsts:
                firsts[s["template"]] = s
            else:
                rest.append(s)
        return list(firsts.values()) + random.Random(
            f"{run['seed']}:check").sample(rest, min(CHECK_SAMPLE,
                                                     len(rest)))

    def check(self, run: dict) -> dict:
        """Every number compared, beside its limit (all 0), as
        {name: [value, limit]}: see `passed` and `print_checks`."""
        done = run["statements"]
        checks = {}
        off = [s for s in done if not on_device(
            s["info"], bool(self.config["deployment"]["workers"]))]
        checks["off_device_statements"] = [len(off), 0]
        for s in off[:3]:
            say(f"check: {s['template']} {s['params']} not on the device "
                f"path: {route_of(s['info'])}")
        texts = [s["sql"] for s in done + run["failed"]]
        checks["repeated_statements"] = [len(texts) - len(set(texts)), 0]
        checks["windows_without_a_completion"] = [int(not done), 0]
        sample = self.sample(run)
        by_name = {t.NAME: t for t in self.templates}
        t0 = time.monotonic()
        for s in sample:
            t = by_name[s["template"]]
            want = t.reference(self.tables, s["params"])
            n, first = compare.mismatched_cells(s["rows"], want, t.COLUMNS)
            # named by the statement: `statement <client>.<n>` above
            # gives its parameters
            checks[f"mismatched_cells.{t.NAME}.{s['client']}.{s['n']}"] = \
                [n, 0]
            if first:
                say(f"check: {t.NAME} {s['params']}: {first}")
        say(f"references: {len(sample)} statements in "
            f"{time.monotonic() - t0:.1f}s")
        return checks

    def report(self, run: dict, setup_s: float) -> dict:
        done = run["statements"]
        by_t = {}
        for s in done:
            by_t.setdefault(s["template"], []).append(s["latency_s"])
        for name, lat in sorted(by_t.items()):
            say(f"{name}: {len(lat)} statements, median "
                f"{statistics.median(lat):.3f}s, each "
                + " ".join(f"{x:.3f}" for x in lat))
        for s in done:
            say(f"statement {s['client']}.{s['n']} {s['template']} "
                f"{json.dumps(s['params'])} {s['latency_s']:.3f}s "
                f"{route_of(s['info'])} "
                f"hbm_in_use={s['hbm_in_use'] / 1e9:.2f}GB")
        for s in run["failed"]:
            say(f"FAILED {s['template']} {s['params']} "
                f"hbm_in_use={s['hbm_in_use'] / 1e9:.2f}GB: {s['error']}")
        sites = {}
        for key, n in run["after"]["sites"].items():
            d = n - run["before"]["sites"].get(key, 0)
            if d:
                sites[key[0]] = sites.get(key[0], 0) + d
        say(f"compiles in the window: "
            f"{run['after']['compiles'] - run['before']['compiles']} in "
            f"{run['after']['compile_s'] - run['before']['compile_s']:.2f}s"
            f", by site {sites}")
        expected = set(self.mix.get("literal_keyed_sites", ()))
        stray = sorted(set(sites) - expected)
        if stray:
            say(f"compile sites in the window that the traffic file does "
                f"not list as literal-keyed: {stray}")
        metrics = {}
        if self.traced:
            tr = run["trace"]
            if tr:
                say(f"trace: {tr['file_bytes']:,} bytes, slice of "
                    f"{tr['statements']} statements {tr['window_s']:.3f}s, "
                    f"device busy {tr['busy_s']:.4f}s on {tr['devices']} "
                    f"device plane(s); fenced deviceMs of the same "
                    f"statements {tr['fenced_device_s']:.4f}s")
            for m in self.bench["per_layer"]:
                if "workloads" in m and \
                        self.cell["name"] not in m["workloads"]:
                    continue
                reader = importlib.import_module(f"layers.{m['name']}")
                value = reader.read(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            units = {m["name"]: m["unit"] for m in self.bench["end_to_end"]}
            medians = [statistics.median(v) for v in by_t.values()]
            if medians:
                metrics["query_geomean_s"] = {
                    "value": math.exp(sum(map(math.log, medians))
                                      / len(medians)),
                    "unit": units["query_geomean_s"]}
                metrics["queries_per_min"] = {
                    "value": 60.0 * len(done) / run["window_s"],
                    "unit": units["queries_per_min"]}
            metrics["setup_s"] = {"value": setup_s,
                                  "unit": units["setup_s"]}
        device = dict(self.device,
                      memory_peak_bytes=run["peak_bytes"])
        out = {"attempted": len(done) + len(run["failed"]),
               "failed": len(run["failed"]), "metrics": metrics,
               "device": device}
        if self.traced and run["trace"]:
            tr = run["trace"]
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            out["breakdown"] = {"device_ops": tr["device_ops"],
                                "idle_gaps": tr["idle_gaps"]}
        return out


def on_device(info: dict, has_workers: bool) -> bool:
    """Split tasks on a worker's device executor, or the coordinator's
    device route: never the host interpreter, a cache or a micro-batch,
    and never the coordinator's local re-run after a task failure."""
    fallback = info.get("fallbackReason") or ""
    if fallback.startswith("task failure"):
        return False
    if has_workers:
        return bool(info.get("distributed"))
    return not info.get("distributed") and info.get("route") == "device"


def passed(checks: dict) -> bool:
    return all(value == limit for value, limit in checks.values())


def print_checks(checks: dict) -> None:
    """Each number compared beside its limit: a run's last lines on
    standard error (the result line carries them too, under `checks`)."""
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr,
              flush=True)


def route_of(info: dict) -> str:
    return (f"distributed={info.get('distributed')} "
            f"route={info.get('route')!r} "
            f"fallback={info.get('fallbackReason')!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark-file",
                    default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="the rehearsal's own list of cells")
    args = ap.parse_args(argv)
    cell = Cell(args.benchmark_file, args.workload, bool(args.trace))
    try:
        cell.setup()
        setup_s = time.monotonic() - T_PROCESS
        say(f"set-up {setup_s:.1f}s; window of {args.seconds:g}s, "
            f"seed {args.seed}")
        run = cell.window(args.seed, args.seconds)
        out = cell.report(run, setup_s)
        checks = cell.check(run)
    finally:
        cell.close()
    print_checks(checks)
    print(json.dumps(dict(correct=passed(checks), **out, checks=checks)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
