"""Worker tasks and executor, inside a split: what of `ex.run(root)`
still has no name: the `split` spans' wall that neither a `compile`
child nor an operator span of that split (`filter-project`, `join`,
`aggregate`, `sort`) covers, over their count, median per statement, in
ms a split: plan-node bookkeeping between operators, expression binding,
reservations. With the three operator metrics it adds up to
`split_run_ms`. Nothing to read on a program that names no operator
inside a split."""

from layers import _split_ops


def read(run):
    return _split_ops.unnamed_ms(run)
