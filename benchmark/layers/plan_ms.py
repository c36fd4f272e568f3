"""Parse, plan and admission: timeline phases `queued` + `plan`
(GET /v1/query/{id}/timeline), median per statement, in ms. The `plan`
phase is read from the coordinator's plan spans, which every route
writes (split-streamed or whole on the coordinator's device); a
statement that has none gives nothing to read."""

import statistics

PLAN_SPANS = ("plan", "optimize", "plan-distributed")


def read(run):
    vals = []
    for s in run["statements"]:
        if not any(sp.get("name") in PLAN_SPANS
                   for sp in s.get("spans") or ()):
            continue
        ph = s["timeline"]["phases"]
        vals.append((ph["queued"] + ph["plan"]) * 1e3)
    return statistics.median(vals) if vals else None
