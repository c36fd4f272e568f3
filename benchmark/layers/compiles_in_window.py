"""Compile: programs compiled inside the window (`RECORDER.totals()`
delta). With literals as static arguments every statement with new
literals compiles; a shape-keyed site here is a missed warm-up."""


def read(run):
    return run["after"]["compiles"] - run["before"]["compiles"]
