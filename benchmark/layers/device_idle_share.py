"""Device: 1 - busy over the traced slice (one whole statement of each
template of the cell), from the JAX profiler trace, in %."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["window_s"] or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
