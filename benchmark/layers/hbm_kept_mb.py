"""Device: what a statement leaves behind in device memory: the
difference of `bytes_in_use` (read by the benchmark after every
statement) between consecutive completed statements, median over the
window, in MB (1e6 bytes). About 0 where nothing accumulates. A window
of fewer than two statements gives nothing to read."""

import statistics


def read(run):
    used = [s["hbm_in_use"] for s in
            sorted(run["statements"], key=lambda s: s["t_done"])
            if "hbm_in_use" in s]
    if len(used) < 2:
        return None
    return statistics.median(b - a for a, b in zip(used, used[1:])) / 1e6
