"""Scheduling and exchange: the coordinator's final stage (decode and
put of the partial pages, their merge, the rest of the plan, the fetch
and decoding of the rows): `final-stage` wall, median per statement, in
ms."""

from layers import _spans


def read(run):
    def value(spans):
        final = _spans.named(spans, "final-stage")
        if not final:
            return None
        return sum(float(sp["durationMs"]) for sp in final)
    return _spans.per_statement_median(run, value)
