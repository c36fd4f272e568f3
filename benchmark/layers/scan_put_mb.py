"""Worker tasks and executor: bytes a statement's scans copied from the
host to the device (`scanPutBytes` on its `execute` span), median per
statement, in MB (1e6 bytes). 0 is the steady state: every column was
found resident. A program without the counter gives nothing to read."""

from layers import _spans


def read(run):
    def value(spans):
        puts = [(sp.get("attributes") or {}).get("scanPutBytes")
                for sp in _spans.named(spans, "execute")]
        puts = [p for p in puts if p is not None]
        return sum(puts) / 1e6 if puts else None
    return _spans.per_statement_median(run, value)
