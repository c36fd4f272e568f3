"""Worker tasks and executor, inside a split: the filter and projection
programs of the split's fragment (`jit_filter_project`, the device time
of the scan cells; fenced in the traced run): summed wall of the
statement's `filter-project` spans that carry `split`, over its count of
`split` spans, median per statement, in ms a split. Nothing to read on a
program that names no operator inside a split."""

from layers import _split_ops


def read(run):
    return _split_ops.operator_ms(run, "filter-project")
