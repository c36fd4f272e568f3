"""Compile: programs compiled because the array shapes were new at the
site (a pinned build's capacity in the join programs): the statement's
`compile` spans with `key == "shape"`, summed, median per statement, in
ms."""

from layers import _spans


def read(run):
    return _spans.compile_ms(run, "shape")
