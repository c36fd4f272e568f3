"""Worker tasks and executor: the share of a statement's splits whose
input was on the device when the loop asked for it: `prefetchedSplits`
less `prefetchStalls` on its `worker-task` spans, over its count of
`split` laps, median per statement, in %. 0 at `prefetch_depth` 0.
Nothing to read on a program whose `worker-task` carries no `stageMs`
(layers/split_stage_ms.py)."""

from layers import split_stage_ms


def read(run):
    return split_stage_ms.per_lap(
        run, lambda a: 100.0 * (int(a.get("prefetchedSplits", 0))
                                - int(a.get("prefetchStalls", 0))))
