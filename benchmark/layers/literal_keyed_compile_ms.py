"""Compile: programs compiled because a static argument was new at a
site that had seen the array shapes before (`filter_project`'s IR with
new literals): the statement's `compile` spans with `key == "literal"`,
summed, median per statement, in ms."""

from layers import _spans


def read(run):
    return _spans.compile_ms(run, "literal")
