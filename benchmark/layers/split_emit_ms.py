"""Worker tasks and executor: encoding the split's result into the task's output buffer (`_emit`): summed wall of the statement's
`split-emit` spans over its `split` spans, median per statement, in ms a
split."""

from layers import _spans


def read(run):
    return _spans.per_split_ms(run, "split-emit")
