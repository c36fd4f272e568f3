"""Worker tasks and executor, single-node route: times a statement's
sort aggregations ran again because their groups did not fit the output
capacity the plan gave them (`aggCapacityRetries` on its `execute`
span), median per statement, a count. 0 is the sound state: every retry
sorts the aggregate's whole input once more. A program without the
counter gives nothing to read."""

from layers import _spans


def read(run):
    def value(spans):
        counts = [(sp.get("attributes") or {}).get("aggCapacityRetries")
                  for sp in _spans.named(spans, "execute")]
        counts = [c for c in counts if c is not None]
        return sum(counts) if counts else None
    return _spans.per_statement_median(run, value)
