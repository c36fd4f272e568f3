"""Worker tasks and executor, inside a split: programs a split
dispatches through `recorded_jit`, hits and misses (the recorder's
per-thread call count, differenced around `ex.run(root)`): `dispatches`
summed over the statement's `split` spans, over their count, median per
statement. What "one program a split" would bring to 1. Eager `jnp`
calls are not in it. Nothing to read on a program whose `split` spans
carry no `dispatches`."""

from layers import _split_ops


def read(run):
    return _split_ops.dispatches(run)
