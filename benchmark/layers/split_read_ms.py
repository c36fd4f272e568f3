"""Worker tasks and executor: reading the split's rows out of the table (lookup and slicing): summed wall of the statement's
`split-read` spans over its `split` spans, median per statement, in ms a
split."""

from layers import _spans


def read(run):
    return _spans.per_split_ms(run, "split-read")
