"""The system under test, brought up in this process: the only file of
the benchmark that imports the program. A chip belongs to one process,
so coordinator, workers and the client's HTTP calls all live here.

From the program it takes the servers, the client, the connector's
tables (the input data), and its counters and spans. It computes no
metric and no reference.
"""

import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def device_info(platform: str, chips: int) -> dict:
    """The device as JAX reports it. Exits 2, with no result line, when
    it is not the platform the configuration needs or holds fewer chips
    than the cell asks for. Never sets JAX_PLATFORMS, never falls back."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        print(f"benchmark: the configuration needs {chips} x {platform} "
              f"and JAX found {len(devs)} x {devs[0].platform} "
              f"({devs[0].device_kind}); nothing was run",
              file=sys.stderr, flush=True)
        sys.exit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_bytes(key: str = "peak_bytes_in_use") -> int:
    """`memory_stats()[key]` of the fullest chip. The CPU backend of the
    rehearsal reports none and reads 0; on a chip a missing reading is an
    error."""
    import jax
    readings = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats is None:
            if d.platform != "cpu":
                raise RuntimeError(f"{d} reports no memory_stats")
            return 0
        readings.append(int(stats[key]))
    return max(readings)


class CacheCounter:
    """Persistent compile-cache hits and misses, as JAX reports them."""

    def __init__(self):
        self.hits = self.misses = 0
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def close_persistent_compile_cache() -> None:
    """From here on a program this process has not compiled is compiled,
    not read from disk, and nothing is written: a statement with new
    literals costs what it costs the user who sends it first, on the
    first run in a checkout and on the sixth alike."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


class Deployment:
    """Coordinator, `workers` workers and one client per traffic client,
    over HTTP on localhost."""

    def __init__(self, config: dict, clients: int = 1):
        sys.path.insert(0, REPO)
        from trino_tpu.client.client import Client
        from trino_tpu.exec.session import Session
        from trino_tpu.server.coordinator import CoordinatorServer
        from trino_tpu.server.worker import WorkerServer
        dep = config["deployment"]
        self.schema = f"{dep['catalog']}.{dep['schema']}"
        self._catalog = dep["catalog"]
        self._schema = dep["schema"].strip('"')
        self.workers = []
        self.session = Session()
        self.coord = CoordinatorServer(self.session).start()
        try:
            for i in range(int(dep["workers"])):
                # one process, one catalog: the worker scans the tables
                # the coordinator planned against
                self.workers.append(WorkerServer(
                    f"bench-w{i}", self.coord.uri, announce_interval_s=0.5,
                    catalog=self.session.catalog).start())
            deadline = time.monotonic() + 30
            while len(self.coord.state.active_nodes()) < len(self.workers):
                if time.monotonic() > deadline:
                    raise RuntimeError("a worker never announced")
                time.sleep(0.05)
        except BaseException:
            self.close()
            raise
        # the client's own patience, not a session property: a cold
        # statement spends minutes in the TPU compiler
        self.clients = [Client(self.coord.uri, user=f"bench-{i}",
                               timeout_s=900.0) for i in range(clients)]

    def close(self) -> None:
        for w in self.workers:
            w.stop()
        self.coord.stop()

    def clear_spool(self) -> None:
        """Between the windows of prove.py only: a later window may draw
        a statement an earlier one sent, and the exchange spool would
        answer it. A run has one window and never calls this."""
        self.coord.state.scheduler.spool.clear()

    # -- the input data ----------------------------------------------------

    def tables(self, wanted: dict) -> dict:
        """{table: {"columns": {name: array}, "dictionary": {name: pool}}}
        of the connector's generated tables, for the references: plain
        numpy, nothing the engine computed."""
        out = {}
        for table, names in wanted.items():
            t = self.session.catalog.get_table(self._catalog, self._schema,
                                               table)
            cols, pools = {}, {}
            for name in names:
                i = t.schema.index_of(name)
                cols[name] = np.asarray(t.columns[i])
                pool = t.schema.fields[i].dictionary
                if pool is not None:
                    pools[name] = tuple(pool)
            out[table] = {"columns": cols, "dictionary": pools,
                          "rows": int(t.num_rows)}
        return out

    # -- the program's counters and spans ----------------------------------

    def counters(self) -> dict:
        from trino_tpu.exec.profiler import RECORDER
        totals = RECORDER.totals()
        return {"compiles": totals["compiles"],
                "compile_s": totals["compileSeconds"],
                "sites": {(e["site"], e["fingerprint"]): e["compiles"]
                          for e in RECORDER.snapshot()},
                "spool_hits": int(
                    self.coord.state.scheduler.stats.get("spool_hits", 0))}

    def statement_facts(self, client, query_id: str, traced: bool) -> dict:
        """What the coordinator says of one finished statement:
        GET /v1/query/{id}, and in a traced run its timeline, its spans
        and the fenced operator times."""
        facts = {"info": client.query_info(query_id)}
        if traced:
            base = f"{self.coord.uri}/v1/query/{query_id}"
            facts["timeline"] = client._request("GET", f"{base}/timeline")
            facts["spans"] = client._request("GET", f"{base}/trace")["spans"]
            facts["fenced"] = self._fenced(query_id)
        return facts

    def _fenced(self, query_id: str):
        """Fenced times of a statement under `enable_profiling`, in ms:
        wall of the work that was fenced, seconds blocked on the device
        (each operator's own fence, so they add up), compile seconds
        (operators are inclusive, so the root's, the largest)."""
        tq = self.coord.state.tracker.get(query_id)
        stage = getattr(tq, "stage_stats", None) or {}
        ops = stage.get("operators") or {}
        if ops:                 # worker tasks: the scheduler's rollup
            return {"wall_ms": sum(t["wall_ms"] for t in stage["tasks"]),
                    "device_ms": sum(o["device_ms"] for o in ops.values()),
                    "compile_ms": max(o["compile_ms"]
                                      for o in ops.values())}
        nodes = list(self.session.executor.node_stats.values())
        nodes = [n for n in nodes if len(n) >= 5]
        if not nodes:
            return None
        return {"wall_ms": max(n[0] for n in nodes) * 1e3,
                "device_ms": sum(n[2] for n in nodes) * 1e3,
                "compile_ms": max(n[4] for n in nodes) * 1e3}
