"""Worker tasks and executor: how long a statement's tasks waited for
the worker's own executor lock (`TaskManager._exec_lock`): summed wall
of its `task-lock-wait` spans, median per statement, in ms. Near 0 while
the coordinator's lock admits one statement at a time: the queue stands
there, not here. A program that writes no `task-lock-wait` span gives
nothing to read."""

from layers import _spans


def read(run):
    def value(spans):
        mine = _spans.named(spans, "task-lock-wait")
        if not mine:
            return None
        return sum(float(sp["durationMs"]) for sp in mine)
    return _spans.per_statement_median(run, value)
