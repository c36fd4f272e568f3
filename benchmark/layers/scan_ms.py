"""Worker tasks and executor: table scans of a statement (finding each
column on the device, or taking it from the connector and putting it
there; zone-map evaluation): summed wall of its `scan` spans, median per
statement, in ms. A program that writes no `scan` span gives nothing to
read."""

from layers import _spans


def read(run):
    def value(spans):
        mine = _spans.named(spans, "scan")
        if not mine:
            return None
        return sum(float(sp["durationMs"]) for sp in mine)
    return _spans.per_statement_median(run, value)
