"""Client and protocol layer: the benchmark's client wall of a statement
minus the coordinator's own elapsed time for it (GET /v1/query/{id}),
median over the window's statements, in ms."""

import statistics


def read(run):
    gaps = [(s["latency_s"] - s["info"]["elapsedSeconds"]) * 1e3
            for s in run["statements"] if s.get("info")]
    return statistics.median(gaps) if gaps else None
