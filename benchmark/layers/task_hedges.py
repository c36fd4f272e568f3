"""Scheduling and exchange: twins the scheduler started for a
statement's stragglers (a task past `hedge_min_s` and `hedge_multiplier`
times its stage's median): `hedges` on its `source-stage` spans, summed,
median per statement. 0 is sound; more says a worker fell so far behind
its peers that its splits ran twice. (`build-stage` carries the count of
the `source-stage` under it and is not added again.) A program whose
stage spans carry no `hedges` gives nothing to read."""

from layers import _spans


def read(run):
    def value(spans):
        counts = [(sp.get("attributes") or {}).get("hedges")
                  for sp in _spans.named(spans, "source-stage")]
        counts = [c for c in counts if c is not None]
        return sum(counts) if counts else None
    return _spans.per_statement_median(run, value)
