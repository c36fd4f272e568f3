"""Kernels and XLA programs: device-busy time (union of the device-op
intervals of the profiler trace) per statement of the traced slice, in
ms. A time, not a roofline share: see PERF.md, Open questions."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["statements"] or not tr["busy_s"]:
        return None
    return tr["busy_s"] * 1e3 / tr["statements"]
