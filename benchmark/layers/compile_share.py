"""Compile: `RECORDER` compile seconds inside the window over the
window's seconds, in %."""


def read(run):
    if not run["window_s"]:
        return None
    return 100.0 * (run["after"]["compile_s"] - run["before"]["compile_s"]) \
        / run["window_s"]
