"""Worker tasks and executor: what staging one split's input costs,
wherever it runs: the column slices, the pad and the put
(`tasks._split_decoder`). The task's pipeline decodes and puts a split
ahead of the loop and says so on `worker-task`: `stageMs` (the decodes'
summed wall, whichever thread ran them), `prefetchedSplits` (splits
whose batch the loop took from staging), `prefetchStalls` (times the
loop waited over 0.1 ms for one). This metric: the statement's `stageMs`
summed, over its count of `split` laps, median per statement, in ms a
split. What `split_put_ms` read before the put left the loop's thread;
beside `split_run_ms` it says whether the feeder can keep ahead.

A program whose `worker-task` spans carry no `stageMs` stages on the
loop's own thread inside `split-put`: nothing to read, None."""

from layers import _spans


def per_lap(run, value_of_task):
    """`value_of_task(attributes)` summed over the statement's
    `worker-task` spans that carry `stageMs`, over its count of `split`
    laps, then the median over statements."""
    def value(spans):
        laps = len(_spans.named(spans, "split"))
        tasks = [sp.get("attributes") or {}
                 for sp in _spans.named(spans, "worker-task")]
        tasks = [a for a in tasks if "stageMs" in a]
        if not laps or not tasks:
            return None
        return sum(value_of_task(a) for a in tasks) / laps
    return _spans.per_statement_median(run, value)


def read(run):
    return per_lap(run, lambda a: float(a["stageMs"]))
