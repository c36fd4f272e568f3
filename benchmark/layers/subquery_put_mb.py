"""Worker tasks and executor: bytes the scans of a statement's folded
subqueries copied from the host to the device (`putBytes` on its
`subquery-fold` spans), median per statement, in MB (1e6 bytes). 0: the
columns were found resident, as they are on the single node and, from a
worker's first Q18 on, on the worker's executor too."""

from layers import _span_sums


def read(run):
    return _span_sums.per_statement(
        run, "subquery-fold",
        lambda sp: (sp.get("attributes") or {}).get("putBytes", 0) / 1e6)
