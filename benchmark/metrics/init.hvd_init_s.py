"""Seconds in ``hvd.init()`` (the program's own span ``hvd.init``)."""

from benchmark.lib import program_spans


def read(r):
    return program_spans.seconds(r, "hvd.init")
