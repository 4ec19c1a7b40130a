"""Seconds of Python in ``DistributedOptimizer``'s ``update`` while JAX
traces the step (the program's span ``hvd.trainer.trace_update``): a part
of ``init.trace_lower_s``."""

from benchmark.lib import program_spans


def read(r):
    return program_spans.seconds(r, "hvd.trainer.trace_update")
