"""Scheduling and exchange: wall of a statement's source stages (the
coordinator's `source-stage` spans) over the splits they ran, median per
statement, in ms a split. Worker deployments only: a statement that ran
no split task gives nothing to read."""

import statistics


def read(run):
    vals = []
    for s in run["statements"]:
        stages = [sp for sp in s.get("spans") or ()
                  if sp.get("name") == "source-stage"]
        splits = sum(int((sp.get("attributes") or {}).get("splits", 0))
                     for sp in stages)
        if splits:
            vals.append(sum(float(sp["durationMs"]) for sp in stages)
                        / splits)
    return statistics.median(vals) if vals else None
