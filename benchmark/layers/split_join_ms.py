"""Worker tasks and executor, inside a split: the joins of the split's
fragment (each probe of a pinned build, its dynamic filter included;
fenced in the traced run, so device time is in it): summed wall of the
statement's `join` spans that carry `split`, over its count of `split`
spans, median per statement, in ms a split. 0 where the fragments have
no join; nothing to read on a program that names no operator inside a
split."""

from layers import _split_ops


def read(run):
    return _split_ops.operator_ms(run, "join")
